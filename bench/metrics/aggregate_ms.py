"""aggregate_ms: host time of aggregation, in ms.

The mean duration of the program's ``fed.aggregate`` spans (program
spans on the profiler's clock): ``strategy.aggregate``, the weighted
mean of the round's client payloads."""
from fedbench import program_spans


def read(ctx):
    return program_spans.mean_ms(ctx, "fed.aggregate")
