"""Operations and bytes from shapes: the yardstick of every utilization
and roofline share the benchmark reports.

Multiply-adds count 2.  Model FLOPs count the work the model needs, not
what an implementation spends: projections and MLP as matmuls over
their parameters, linear attention in its recurrent form (per token, a
rank-1 state update per KV head and a state read-out per query head,
each 2 D (D + 1)), the unembedding only where logits are needed, no
embedding gather, no recomputation.  Kernel costs count what the named
Pallas kernel must do for its call: the chunked algorithm's matmuls at
the configured chunk, and each operand crossing HBM once.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"], "h": h,
            "hkv": cfg.get("num_key_value_heads", h), "hd": d // h,
            "mlp_mats": 3 if cfg["hidden_act"] == "silu" else 2}


def layer_matmul_params(cfg: dict) -> int:
    """Matmul parameters of one block: q, k, v, o and the MLP."""
    m = dims(cfg)
    attn = m["d"] * m["hd"] * (2 * m["h"] + 2 * m["hkv"])
    return attn + m["mlp_mats"] * m["d"] * m["f"]


def la_flops_per_token(cfg: dict) -> int:
    """Linear attention's forward work per token over all layers."""
    m = dims(cfg)
    state = 2 * m["hd"] * (m["hd"] + 1)
    return m["layers"] * state * (m["hkv"] + m["h"])


def forward_flops(cfg: dict, tokens: int, logit_rows: int) -> float:
    """Model FLOPs of a forward pass over `tokens` tokens that needs
    logits at `logit_rows` of them."""
    m = dims(cfg)
    per_token = 2 * m["layers"] * layer_matmul_params(cfg) \
        + la_flops_per_token(cfg)
    return float(per_token) * tokens + 2.0 * m["d"] * m["v"] * logit_rows


def train_flops_per_token(cfg: dict) -> float:
    """Forward and backward: 6 per matmul parameter (the unembedding at
    every position included), 3 times linear attention's forward."""
    m = dims(cfg)
    params = m["layers"] * layer_matmul_params(cfg) + m["d"] * m["v"]
    return 6.0 * params + 3.0 * la_flops_per_token(cfg)


def weight_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Every parameter once, at the published bf16 by default."""
    m = dims(cfg)
    layer = layer_matmul_params(cfg) + 4 * m["d"]        # + norms, biases
    tied = cfg.get("tie_word_embeddings", False)
    table = m["v"] * m["d"] * (1 if tied else 2)
    return float(itemsize) * (m["layers"] * layer + table)


def state_bytes(cfg: dict, slots: int) -> float:
    """f32 recurrent state (S and its normalizer row) of all layers."""
    m = dims(cfg)
    per = m["hkv"] * (m["hd"] + 1) * (m["hd"] + 1) * 4
    return float(m["layers"] * slots * per)


def decode_step(cfg: dict, slots: int) -> tuple:
    """(FLOPs, bytes) of one decode step over `slots` rows: every weight
    read once at bf16, every state read and written."""
    flops = forward_flops(cfg, slots, slots)
    return flops, weight_bytes(cfg) + 2.0 * state_bytes(cfg, slots)


# ---------------------------------------------------------------------------
# Pallas kernels: (flops, bytes) of one call
# ---------------------------------------------------------------------------

def la_fwd(b, h, hkv, n, d, chunk, itemsize=2) -> tuple:
    """kernels/linear_attention.py forward: per chunk of c rows, q k^T
    (c c d), the masked scores times [v, 1] (c c e), q S (c d e) per
    query head, and the state update k^T [v, 1] (c d e) per KV head."""
    c = min(chunk, n)
    t = -(-n // c)
    e = d + 1
    flops = 2.0 * b * t * (h * (c * c * d + c * c * e + c * d * e)
                           + hkv * c * d * e)
    nbytes = b * n * (itemsize * d * (2 * h + 2 * hkv) + 4 * h)
    return flops, float(nbytes)


def la_bwd_dq(b, h, hkv, n, d, chunk, itemsize=2) -> tuple:
    """Backward, grad q: per chunk [om, -h] [v, 1]^T (c c e), times k
    (c c d), [om, -h] A^T (c e d) per query head; A += k^T [v, 1] (c d e)
    per KV head.  Reads k, v, om_hat (f32), h; writes dq."""
    c = min(chunk, n)
    t = -(-n // c)
    e = d + 1
    flops = 2.0 * b * t * (h * (c * c * e + c * c * d + c * e * d)
                           + hkv * c * d * e)
    nbytes = b * n * (itemsize * d * (2 * hkv + h) + 4 * h * (d + 1))
    return flops, float(nbytes)


def la_bwd_dkv(b, h, hkv, n, d, chunk, itemsize=2) -> tuple:
    """Backward, grad k and v, per KV head over its group of g query
    heads and chunk c: scores g2 [v, -1]^T (g c c e), their transpose
    times q (g c c d), [v, -1] u^T (c e d), q k^T (g c c d), its
    transpose times om (g c c d), k u (c d d), u += [q, 1]^T g2
    (g c e e).  Reads q, k, v, om_hat (f32), h; writes dk, dv."""
    c = min(chunk, n)
    t = -(-n // c)
    e = d + 1
    g = h // hkv
    per = (g * c * c * e + 3 * g * c * c * d + c * e * d + c * d * d
           + g * c * e * e)
    flops = 2.0 * b * t * hkv * per
    nbytes = b * n * (itemsize * d * (h + 4 * hkv) + 4 * h * (d + 1))
    return flops, float(nbytes)


def linear_decode_fused(b, h, hkv, d, itemsize=2) -> tuple:
    """kernels/decode_fused.py linear step for one layer: per slot and
    KV head, S += k^T [v, 1] (d e), o = q S over the g query heads
    (g d e); the f32 state and normalizer are read and written once."""
    e = d + 1
    g = h // hkv
    flops = 2.0 * b * hkv * (d * e + g * d * e)
    state = 4.0 * b * hkv * (d * e + e)
    nbytes = 2 * state + itemsize * b * (2 * h * d + 2 * hkv * d)
    return flops, float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
