"""serve_mfu.longprompt: model FLOPs of the tokens the engine processed
in the traced window (prompt tokens prefilled and tokens decoded, with
logits where a token was sampled) over the window's length times the
chip's bf16 peak, in per cent."""
from bench import flops


def read(ctx):
    if ctx.peak is None:
        return None
    t = ctx.result.get("traced")
    if not t or not t["window_s"]:
        return None
    tokens = t["prefill_tokens"] + t["decode_tokens"]
    if not tokens:
        return None
    work = flops.forward_flops(ctx.cfg, tokens, t["emitted_tokens"])
    return 100.0 * work / (t["window_s"] * ctx.peak["bf16_flops_per_s"])
