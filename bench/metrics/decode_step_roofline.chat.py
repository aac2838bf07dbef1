"""decode_step_roofline.chat: the batched decode program against its
roofline, counted independently of the implementation: every weight
read once at the published bf16 and every f32 recurrent state read and
written once (bench/flops.py), against the device time of its runs in
the trace, in per cent."""
from bench import flops
from bench.readers import program_share


def read(ctx):
    fl, nb = flops.decode_step(ctx.cfg, ctx.traffic["engine"]["slots"])
    return program_share(ctx, r"decode_fn", fl, nb)
