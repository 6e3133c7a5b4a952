"""gram_roofline: the L-BFGS Gram kernel's share of its roofline, in %.

``repro.kernels.vlbfgs.gram`` forms the (n, n) Gram matrix of the
(n, D) basis [s_1..s_m, y_1..y_m, g], n = 2m + 1, in one pass.  What it
requires: read the basis once, 4*n*D bytes, and 2*n*n*D FLOPs.  Its
least time at the peaks of ``peaks.json`` over the kernel's device time
in the trace."""
from fedbench import trace

PROGRAM = "jit_gram"


def cost(m: int, n_params: int) -> tuple:
    n = 2 * m + 1
    return 2.0 * n * n * n_params, 4.0 * n * n_params


def is_kernel(op) -> bool:
    """The kernel's custom call, ``%gram.<n> = ... custom-call(``, inside
    its own program."""
    name, module, _, _, text = op
    return (module.startswith(PROGRAM) and name.startswith("gram")
            and "custom-call(" in text)


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    ops = trace.op_events(ctx.trace, is_kernel)
    if not ops:
        return None
    flops, nbytes = cost(ctx.cell.traffic["lbfgs_m"],
                         ctx.cell.family().n_params(ctx.cell.config))
    seconds = sum(o[3] for o in ops) / 1e9
    share, bound = trace.roofline(len(ops) * flops, len(ops) * nbytes,
                                  seconds, ctx.peaks["bf16_flops_per_s"],
                                  ctx.peaks["hbm_bytes_per_s"])
    print(f"[bench] gram calls={len(ops)} kernel_s={seconds:.6f} "
          f"bound={bound}", flush=True)
    return share
