"""Least time for the decision work of the real score rows served in the
window (bytes over HBM bandwidth, or operations over the bf16 peak,
whichever is larger), as a share of the device time inside the session
calls: every program a call launches (the decision program, oracle or
kernel, and the copies of its inputs), whatever its name."""

from perfbench import tracing, work


def read(ctx):
    if ctx.trace is None or not hasattr(ctx.dep, "n_valid_of"):
        return None
    device_s = tracing.device_seconds_in(ctx.trace, "session_call")
    if device_s <= 0:
        return None
    flops, nbytes = work.decision_work(ctx.dep.n_valid_of(ctx.dep.served_ids()))
    return 100.0 * work.roofline_seconds(flops, nbytes, ctx.peaks) / device_s
