"""Milliseconds a reader's request waits on the server for a thread: the
mean ``queued_s`` of the ``server.*`` spans of reader requests (their
``bench.retrieve`` traces), from the frame being read to a server thread
starting it."""

from fdbbench.spans import traces_of
from fdbbench.splits import queued_ms


def read(ctx):
    return queued_ms(ctx.spans, traces_of(ctx.spans, "bench.retrieve"))
