"""Qwen2 block, as the configuration file states it.

Pre-norm sequential residual with RMSNorm, SwiGLU MLP, full rotary
embedding at rope_theta, biases on q/k/v only, GQA, unembedding tied to
the token embedding.  Departure from the published model (listed in the
configuration file): linear attention in place of softmax.
"""
from __future__ import annotations

from bench.reference import common as c


def program_fields(cfg: dict) -> dict:
    """The program's ModelConfig fields that this block implies."""
    if cfg["hidden_act"] != "silu":
        raise ValueError("qwen2 runs a SwiGLU MLP, not "
                         f"{cfg['hidden_act']!r}")
    return dict(num_kv_heads=cfg["num_key_value_heads"], qkv_bias=True,
                mlp_act="swiglu", norm="rmsnorm", parallel_residual=False,
                rope_kind="standard", rope_fraction=1.0,
                rope_theta=float(cfg["rope_theta"]))


def param_shapes(cfg: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n = cfg["num_hidden_layers"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    return {
        "embed": {"table": (v, d)},
        "ln_f": {"scale": (d,)},
        "blocks": {
            "ln1": {"scale": (n, d)}, "ln2": {"scale": (n, d)},
            "mixer": {"wq": {"w": (n, d, d), "b": (n, d)},
                      "wk": {"w": (n, d, kv), "b": (n, kv)},
                      "wv": {"w": (n, d, kv), "b": (n, kv)},
                      "wo": {"w": (n, d, d)}},
            "ffn": {"wi": {"w": (n, d, f)}, "wg": {"w": (n, d, f)},
                    "wo": {"w": (n, f, d)}},
        },
    }


def layer(mat, p, x, positions, cfg: dict):
    eps = cfg["rms_norm_eps"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    x = x + c.attention(mat, p["mixer"], c.rms_norm(p["ln1"], x, eps),
                        positions, cfg, hd, cfg["rope_theta"],
                        cfg["num_key_value_heads"])
    h = c.rms_norm(p["ln2"], x, eps)
    gate = c.silu(c.linear(mat, p["ffn"]["wg"], h))
    return x + c.linear(mat, p["ffn"]["wo"],
                        gate * c.linear(mat, p["ffn"]["wi"], h))


def final_norm(p, x, cfg: dict):
    return c.rms_norm(p, x, cfg["rms_norm_eps"])


def unembedding(params):
    """(weight, tied): logits = h @ weight.T for a tied table."""
    return params["embed"]["table"], True
