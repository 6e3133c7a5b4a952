"""client_host_ms: host time of one client in a round while the device
has nothing of it queued, in ms.

The program's ``fed.client`` spans (``FederatedRun.round``, one landed
client from its ``client_step`` call to its payload), each less the
``fed.wait`` spans it holds (the host blocked on the client's loss, the
device busy): the batch's copy to the device, the step's dispatch, the
codec.  Their mean (program spans on the profiler's clock)."""
from fedbench import program_spans


def read(ctx):
    if ctx.trace is None:
        return None
    return program_spans.host_ms(ctx.trace, program_spans.CLIENT,
                                 program_spans.WAIT)
