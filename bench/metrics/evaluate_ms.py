"""evaluate_ms: host time of an evaluation, in ms.

The mean duration of the program's ``fed.evaluate`` spans (program
spans on the profiler's clock): ``FederatedRun.evaluate``, the test
images' copy to the device and the accuracy read back."""
from fedbench import program_spans


def read(ctx):
    return program_spans.mean_ms(ctx, "fed.evaluate")
