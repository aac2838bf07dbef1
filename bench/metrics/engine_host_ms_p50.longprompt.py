"""engine_host_ms_p50.longprompt: median over the traced window's
`engine.step` spans of the host's own time in the step: the span less
the part its `.wait` spans cover (where the host blocks on the device),
in ms.  Admission, dispatch, the per-slot emit loop and the host side of
prefill windows are in it."""
from bench import progtrace


def read(ctx):
    return progtrace.median_self_ms(progtrace.of(ctx)["spans"],
                                    "engine.step")
