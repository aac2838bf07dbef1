"""train_mfu: model FLOPs of the tokens trained in the traced window
(bench/flops.py: 6 per matmul parameter, unembedding included, plus
linear attention's forward and backward; no recomputation) over the
window's length times the chip's bf16 peak, in per cent."""
from bench import flops


def read(ctx):
    if ctx.peak is None:
        return None
    t = ctx.result.get("traced")
    if not t or not t["tokens"] or not t["window_s"]:
        return None
    work = flops.train_flops_per_token(ctx.cfg) * t["tokens"]
    return 100.0 * work / (t["window_s"] * ctx.peak["bf16_flops_per_s"])
