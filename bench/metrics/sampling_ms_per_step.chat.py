"""sampling_ms_per_step.chat: device time of the operations in the
sampler's named scope (`sample`: the top-p and top-k threshold search,
the Gumbel draw and the argmax of serve/sampling.py) in the traced
window, per decode step the engine ran there (its `engine.decode`
spans), in ms.  The completion program's one-row samples count too;
they are a small part."""
from bench import progtrace


def read(ctx):
    prog = progtrace.of(ctx)
    took = progtrace.scope_seconds(prog["ops"], "sample")
    steps = len(progtrace.named(prog["spans"], "engine.decode"))
    if took is None or not steps:
        return None
    return 1e3 * took / steps
