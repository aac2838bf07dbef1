"""la_fwd_bwd_roofline: the Pallas linear-attention training kernels
(kernels/linear_attention.py: forward, grad q, grad k and v) against
their roofline: for every call in the traced window, the larger of its
FLOPs at the bf16 peak and its bytes at the HBM bandwidth
(bench/flops.py), over the device time the calls took, in per cent.
The trace names no Pallas kernel, so each is known by its outputs:
(o, g) for the forward, dq alone, (dk, dv)."""
from bench import flops
from bench.readers import kernel_roofline


def read(ctx):
    c, t = ctx.cfg, ctx.traffic
    h = c["num_attention_heads"]
    hkv = c.get("num_key_value_heads", h)
    b, n, d = t["batch"], t["seq_len"], c["hidden_size"] // h
    shape = (b, h, hkv, n, d, c["attention"]["chunk"])
    q, kv = f"bf16[{b},{h},{n},{d}]", f"bf16[{b},{hkv},{n},{d}]"
    return kernel_roofline(ctx, [
        (f"({q}, f32[{b},{h},1,{n}])", flops.la_fwd(*shape)),
        (q, flops.la_bwd_dq(*shape)),
        (f"({kv}, {kv})", flops.la_bwd_dkv(*shape)),
    ])
