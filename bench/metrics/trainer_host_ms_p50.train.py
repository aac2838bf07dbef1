"""trainer_host_ms_p50.train: median over the traced steps'
`trainer.step` spans of the host's own time in the step: the span less
the part `trainer.loss.wait` covers (where the host blocks on the
device), in ms.  The batch, the step's dispatch and the bookkeeping
after the loss are in it."""
from bench import progtrace


def read(ctx):
    return progtrace.median_self_ms(progtrace.of(ctx)["spans"],
                                    "trainer.step")
