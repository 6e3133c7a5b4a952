"""mfu: the whole round's model FLOP utilisation, in %.

The FLOPs a round requires, 3 x one example's forward FLOPs (taps that
touch the input; ``forward_flops`` of the model family) for every
example through the client function, over the round time of the run's
untraced window, over the chips' bf16 peak from ``peaks.json``.  The
program's second per-example backward and the server step are work it
does beyond the requirement and do not count."""


def read(ctx):
    if ctx.peaks is None:
        return None
    cell = ctx.cell
    t = cell.traffic
    clients = max(1, int(t["participation"] * t["num_clients"]))
    examples = clients * cell.workload["client_examples"] * t["local_epochs"]
    flops = 3 * cell.family().forward_flops(cell.config) * examples
    round_s = ctx.window["wall_s"] / ctx.window["rounds"]
    return 100.0 * flops / round_s / (ctx.peaks["bf16_flops_per_s"]
                                      * ctx.chips)
