"""device_idle: share of the traced rounds in which no op ran on the
device, in %: 1 - (union of the device ops' intervals) / (traced window),
averaged over the chips used (device trace)."""
from fedbench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    share = trace.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
