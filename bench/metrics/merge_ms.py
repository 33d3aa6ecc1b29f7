"""merge_ms: device time of one execution of the program that writes an
admitted request's prefill cache into its slot of the serve engine's
caches (``jit_merge_slot``), from the trace."""


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    n, s = red.module("jit_merge_slot")
    return s / n * 1e3 if n else None
