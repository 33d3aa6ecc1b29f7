"""mfu.decode: model FLOPs of the decode steps in the traced slice
(``bench/cost.py``) over the slice's length at the chip's bf16 peak, in
percent: the whole decode step's share of the chip."""

from bench import cost


def read(ctx):
    red, pk = ctx["trace"], ctx["peaks"]
    if red is None or pk is None or red.host_s <= 0:
        return None
    flops = sum(cost.decode_step(ctx["shape"], lanes, live)[0]
                for _, lanes, live in red.steps if lanes)
    if not flops:
        return None
    return flops / (red.host_s * pk["bf16_flops_per_s"]) * 100.0
