"""prefill_window_ms_p50.longprompt: median time of one chunked-prefill
window in the window (repro.obs ServeTracer, which syncs each window
under tracing so the span is device time)."""
from bench.readers import median_span


def read(ctx):
    return median_span(ctx, "prefill_window_ms")
