"""Share of the traced window in which no operation ran on the device."""

from perfbench import tracing


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * tracing.idle_share(ctx.trace)
