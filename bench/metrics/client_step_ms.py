"""client_step_ms: device time of one client step, in ms.

The executions of the program's jitted client step (``client_grad_fim``
of ``repro.fed.client``) on the device trace's ``XLA Modules`` line,
their total time over their count."""
from fedbench import trace

PROGRAM = "client_grad_fim"


def read(ctx):
    if ctx.trace is None:
        return None
    runs = trace.module_runs(ctx.trace, lambda m: PROGRAM in m)
    if not runs:
        return None
    return sum(d for _, d in runs) / len(runs) / 1e6
