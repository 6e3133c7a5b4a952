"""fim_diag_roofline: the Fisher-diagonal kernel's share of its roofline,
in %.

``repro.kernels.fim_diag`` reduces one leaf's (B, D) per-example
gradients to the mean of their squares.  What the call requires: read
B*D floats, read the old diagonal and write the new one, 4*(B*D + 2*D)
bytes, and 2*B*D FLOPs (a square and an add per element).  The least
time of a client step's calls, one per leaf, at the peaks of
``peaks.json`` (the bf16 rate bounds the FLOPs from above, so the memory
bound binds), over the kernel's device time in the trace."""
from fedbench import trace

PROGRAM = "client_grad_fim"


def cost(batch: int, leaf_sizes) -> list:
    """[(flops, bytes)] of one client step's calls, one per leaf."""
    return [(2.0 * batch * d, 4.0 * (batch * d + 2 * d)) for d in leaf_sizes]


def is_kernel(op) -> bool:
    """The kernel's custom call, ``%fim_diag.<n> = ... custom-call(``,
    inside the client step's program."""
    name, module, _, _, text = op
    return (PROGRAM in module and name.startswith("fim_diag")
            and "custom-call(" in text)


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    steps = len(trace.module_runs(ctx.trace, lambda m: PROGRAM in m))
    ops = trace.op_events(ctx.trace, is_kernel)
    sizes = ctx.cell.family().leaf_sizes(ctx.cell.config)
    if not steps or len(ops) != steps * len(sizes):
        return None
    calls = cost(ctx.cell.workload["client_examples"], sizes)
    flops = steps * sum(f for f, _ in calls)
    nbytes = steps * sum(b for _, b in calls)
    seconds = sum(o[3] for o in ops) / 1e9
    share, bound = trace.roofline(flops, nbytes, seconds,
                                  ctx.peaks["bf16_flops_per_s"],
                                  ctx.peaks["hbm_bytes_per_s"])
    print(f"[bench] fim_diag calls={len(ops)} kernel_s={seconds:.6f} "
          f"bound={bound}", flush=True)
    return share
