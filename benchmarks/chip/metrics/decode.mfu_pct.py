"""Decode step's share of the chip's roofline.

The least time the chip could take for the traced decode steps (the
larger of their needed FLOPs over peak FLOP/s and their needed bytes over
peak HBM bandwidth, counted from shapes by ``work.decode_step``) over the
time from the first step's start to the last step's end.
"""

import numpy as np

from benchmarks.chip import work, xplane


def read(ctx):
    steps = xplane.programs(ctx["device0"]["modules"], "serve_step")
    if not steps:
        return None
    a, cfg, pk = ctx["args"], ctx["config"], ctx["peaks"]
    least = 0.0
    for i in range(len(steps)):
        w = work.decode_step(cfg, a["batch"],
                             np.full(a["batch"], a["prompt-len"] + i + 1))
        least += max(w["flops"] / pk["flops_bf16"],
                     w["bytes"] / pk["hbm_bytes_s"])
    span = (steps[-1][2] - steps[0][1]) / 1e9
    return 100.0 * least / span
