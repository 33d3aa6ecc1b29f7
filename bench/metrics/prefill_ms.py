"""prefill_ms: device time of one execution of the serve engine's
batch-1 prefill program (``jit_prefill``), from the trace."""


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    n, s = red.module("jit_prefill")
    return s / n * 1e3 if n else None
