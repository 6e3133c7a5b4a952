"""h2d_mb_per_round: bytes copied host to device a round, in MB (10^6).

The program's ``h2d_bytes_total`` counter (every ``fed.put``: each
landed client's batch, and the test images of an evaluation) over the
traced rounds, divided by their number."""
from fedbench import program_spans


def read(ctx):
    total = program_spans.per_round(ctx, "h2d_bytes_total")
    return None if total is None else total / 1e6
