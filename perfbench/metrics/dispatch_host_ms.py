"""Host time of a session call: its span less the device-busy time inside
it, averaged over the calls of the traced window."""

from perfbench import tracing


def read(ctx):
    if ctx.trace is None:
        return None
    self_s = tracing.host_self_seconds(ctx.trace, "session_call")
    return float(self_s.mean()) * 1e3 if len(self_s) else None
