"""Host ingest: the share of the traced window covered by the program's
``parse.remap`` spans (their union): inside each batch's parse, the
native intern table's sync into the plan's string tables and the
gather of native ids through it."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.span_share("tpustream.parse.remap")
