"""Least time for the work of the real candidates of the questions served
in the window (`perfbench.work_kg.question_work`: bytes over HBM
bandwidth, or operations over the bf16 peak, whichever is larger), as a
share of the device time inside the session calls."""

from perfbench import tracing, work, work_kg


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or \
            not hasattr(ctx.dep, "n_cand_of_calls"):
        return None
    calls = ctx.dep.n_cand_of_calls()
    device_s = tracing.device_seconds_in(ctx.trace, "session_call")
    if not calls or device_s <= 0:
        return None
    flops, nbytes = work_kg.question_work(
        calls, int(ctx.config["d_emb"]), int(ctx.config["d_hidden"]),
        int(ctx.config["top_k"]))
    return 100.0 * work.roofline_seconds(flops, nbytes, ctx.peaks) / device_s
