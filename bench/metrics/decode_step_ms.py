"""decode_step_ms: device time of one execution of the jitted
``decode_step`` program, from the trace."""


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    n, s = red.module("jit_decode_step")
    return s / n * 1e3 if n else None
