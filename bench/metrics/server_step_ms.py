"""server_step_ms: host time of the server step, in ms.

The mean duration of the program's ``fed.server_step`` spans (program
spans on the profiler's clock): ``strategy.server_step`` on the round's
aggregate, FIM-L-BFGS's Gram matrix, two-loop recursion and update,
dispatched op by op."""
from fedbench import program_spans


def read(ctx):
    return program_spans.mean_ms(ctx, "fed.server_step")
