"""The whole step's share of the chip's bf16 peak: scorer model FLOPs of
the real candidates of every question served, over the window's seconds
(host clock), over the peak. Under float32 matmuls at HIGHEST precision
each FLOP costs several bf16 passes, so its ceiling sits far below 100%."""

import numpy as np

from perfbench import work


def read(ctx):
    if not hasattr(ctx.dep, "n_cand_of_calls") or ctx.peaks is None:
        return None
    calls = ctx.dep.n_cand_of_calls()
    if not calls:
        return None
    flops = work.scorer_flops(np.concatenate(calls), *ctx.dep.widths)
    elapsed = ctx.window.close - ctx.window.t0
    return 100.0 * flops / elapsed / ctx.peaks["bf16_flops_per_s"]
