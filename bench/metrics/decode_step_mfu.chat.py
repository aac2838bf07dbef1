"""decode_step_mfu.chat: model FLOPs of the tokens decoded in the
traced window (one token per decoding slot per step, bench/flops.py)
over the device time of the decode program's runs in the trace times
the bf16 peak, in per cent."""
from bench import devtrace, flops


def read(ctx):
    if ctx.peak is None:
        return None
    t = ctx.result.get("traced") or {}
    took = sum(d for _, d in devtrace.program_events(ctx.trace, r"decode_fn"))
    if not t.get("decode_tokens") or took <= 0:
        return None
    work = flops.forward_flops(ctx.cfg, t["decode_tokens"], t["decode_tokens"])
    return 100.0 * work / (took * ctx.peak["bf16_flops_per_s"])
