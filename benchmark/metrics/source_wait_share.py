"""Host ingest: the share of the traced window covered by the program's
``source`` spans (their union), the executor's wait for its next
source batch (Flink's idle time): the pull itself, or with parse-ahead
the wait on the parsed-batch queue."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.span_share("tpustream.source")
