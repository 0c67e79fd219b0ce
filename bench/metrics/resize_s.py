"""Mean seconds of the window's resizes: the program's ``elastic.grow``
and ``elastic.shrink`` spans (``ElasticRuntime.grow`` / ``.shrink``),
from the request through the queue to the training state on the new
mesh with its step ready.  The train driver attaches a ``SpanCollector``
to the runtime's scheduler in a traced run; a program without these
spans gives nothing."""
NAMES = ("elastic.grow", "elastic.shrink")


def read(ctx):
    spans = [s for s in ctx.extra.get("program_spans", ())
             if s["name"] in NAMES]
    return sum(s["dur"] for s in spans) / len(spans) if spans else None
