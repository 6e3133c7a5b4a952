"""lowered_per_round: programs JAX lowers a round.

The program's ``jax_lowered_total`` counter (JAX's compile events while
a tracer is attached, labelled by the span that lowered them) over the
traced rounds, divided by their number.  A warm round that lowers
programs builds new closures each round (Open question 1 of PERF.md)."""
from fedbench import program_spans


def read(ctx):
    return program_spans.per_round(ctx, "jax_lowered_total")
