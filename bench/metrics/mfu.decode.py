"""mfu.decode: model FLOPs of the decode steps in the traced slice (the
architecture's ``decode_step``) over the slice's length at the chip's
bf16 peak, in percent: the whole decode step's share of the chip."""


def read(ctx):
    red, pk = ctx["trace"], ctx["peaks"]
    if red is None or pk is None or red.host_s <= 0:
        return None
    flops = sum(ctx["arch"].decode_step(ctx["cj"], lens)[0]
                for _, lens in red.steps if lens)
    if not flops:
        return None
    return flops / (red.host_s * pk["bf16_flops_per_s"]) * 100.0
