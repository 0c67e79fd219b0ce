"""Mean milliseconds of the window's ``match_grow`` spans: the
scheduler's grow path (``GrowEngine.grow``: the local MATCHGROW and the
splice of the new chips into the job's allocation), inside the elastic
job's grow through the queue."""


def read(ctx):
    spans = [s for s in ctx.extra.get("program_spans", ())
             if s["name"] == "match_grow"]
    return 1e3 * sum(s["dur"] for s in spans) / len(spans) if spans else None
