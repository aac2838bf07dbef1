"""GPT-NeoX (Pythia) block, as the configuration file states it.

Parallel residual: x + attn(LN1(x)) + mlp(LN2(x)); partial rotary
embedding on rotary_pct of each head; tanh GELU (`hidden_act`); untied
unembedding.  Departures from the published model are listed in the
configuration file: linear attention in place of softmax, no biases.
"""
from __future__ import annotations

from bench.reference import common as c


def program_fields(cfg: dict) -> dict:
    """The program's ModelConfig fields that this block implies."""
    if cfg["hidden_act"] != "gelu_pytorch_tanh":
        raise ValueError("the program's GELU is the tanh form, not "
                         f"{cfg['hidden_act']!r}")
    # no biases: a departure that the configuration file lists
    return dict(num_kv_heads=cfg["num_attention_heads"], qkv_bias=False,
                mlp_act="gelu", norm="layernorm",
                parallel_residual=cfg["use_parallel_residual"],
                rope_kind="partial", rope_fraction=cfg["rotary_pct"],
                rope_theta=float(cfg["rotary_emb_base"]))


def param_shapes(cfg: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n = cfg["num_hidden_layers"]
    ln = {"scale": (n, d), "bias": (n, d)}
    return {
        "embed": {"table": (v, d)},
        "lm_head": {"w": (d, v)},
        "ln_f": {"scale": (d,), "bias": (d,)},
        "blocks": {
            "ln1": ln, "ln2": dict(ln),
            "mixer": {"wq": {"w": (n, d, d)}, "wk": {"w": (n, d, d)},
                      "wv": {"w": (n, d, d)}, "wo": {"w": (n, d, d)}},
            "ffn": {"wi": {"w": (n, d, f)}, "wo": {"w": (n, f, d)}},
        },
    }


def layer(mat, p, x, positions, cfg: dict):
    eps = cfg["layer_norm_eps"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    rot = int(hd * cfg["rotary_pct"])
    rot -= rot % 2
    attn = c.attention(mat, p["mixer"], c.layer_norm(p["ln1"], x, eps),
                       positions, cfg, rot, cfg["rotary_emb_base"],
                       cfg["num_attention_heads"])
    h = c.layer_norm(p["ln2"], x, eps)
    mlp = c.linear(mat, p["ffn"]["wo"],
                   c.gelu_tanh(c.linear(mat, p["ffn"]["wi"], h)))
    return x + attn + mlp


def final_norm(p, x, cfg: dict):
    return c.layer_norm(p, x, cfg["layer_norm_eps"])


def unembedding(params):
    """(weight, tied): logits = h @ weight."""
    return params["lm_head"]["w"], False
