"""Milliseconds of catalogue work per reader request: the ``catalogue.*``
spans that belong to a reader's request (its ``bench.retrieve`` trace),
over the reader requests of the window."""

from fdbbench.spans import duration, traces_of


def read(ctx):
    n = len(ctx.requests("retrieve"))
    readers = traces_of(ctx.spans, "bench.retrieve")
    spans = [s for s in ctx.spans
             if s["name"].startswith("catalogue.") and s["trace_id"] in readers]
    if not n or not spans:
        return None
    return 1e3 * sum(duration(s) for s in spans) / n
