"""The least time of a step at the chip's peaks.

The work of a step, its FLOPs and the bytes it has to move, is counted
from the model's shapes by its architecture module
(``bench/arch/<arch>.py``, ``decode_step``); the peaks come from
``bench/peaks.py``.
"""

from __future__ import annotations


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound): the larger of compute and memory time at the
    chip's peaks, and which of the two it is."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
