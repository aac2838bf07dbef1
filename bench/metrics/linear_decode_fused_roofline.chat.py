"""linear_decode_fused_roofline.chat: the fused Pallas linear decode
kernel (kernels/decode_fused.py) against its roofline: per call (one
layer of the batched decode step), the larger of FLOPs at the bf16
peak and bytes at the HBM bandwidth (bench/flops.py), over the device
time the calls took, in per cent.  The trace names no Pallas kernel; this
one is known by its outputs: the new state S and normalizer row, and
the output o of every query head."""
from bench import flops
from bench.readers import kernel_roofline


def read(ctx):
    c = ctx.cfg
    h = c["num_attention_heads"]
    hkv = c.get("num_key_value_heads", h)
    d = c["hidden_size"] // h
    b = ctx.traffic["engine"]["slots"]
    sig = (f"(f32[{b},{hkv},{d},{d + 1}], f32[{b},{hkv},1,{d + 1}], "
           f"bf16[{b},{hkv},{h // hkv},{d}])")
    return kernel_roofline(ctx, [(sig, flops.linear_decode_fused(b, h, hkv,
                                                                 d))])
