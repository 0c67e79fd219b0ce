"""Mean host time of one match (``Matcher.match``, which dispatches to
the dict DFS or to ``FlatMatcher``), from the benchmark's wrapper."""


def read(ctx):
    return ctx.spans.get("match").mean_ms()
