"""decode_roofline: the least time of a decode step at the chip's peaks
(the architecture's ``decode_step``, ``bench/cost.py``,
``bench/peaks.py``) over its measured device time, in percent, averaged
over the steps of the traced slice.  Which bound applies goes to
standard error."""

import sys

from bench import cost


def read(ctx):
    red, pk = ctx["trace"], ctx["peaks"]
    if red is None or pk is None:
        return None
    n, s = red.module("jit_decode_step")
    steps = [lens for _, lens in red.steps if lens]
    if not n or not steps:
        return None
    least, bounds = 0.0, {}
    for lens in steps:
        t, bound = cost.least_time(*ctx["arch"].decode_step(ctx["cj"], lens),
                                   pk)
        least += t
        bounds[bound] = bounds.get(bound, 0) + 1
    print(f"decode_roofline: bound by {bounds}, least time "
          f"{least / len(steps) * 1e3:.4f} ms a step", file=sys.stderr)
    return least / len(steps) / (s / n) * 100.0
