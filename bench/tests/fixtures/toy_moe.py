"""A toy mixture-of-experts decoder, known to no file of the harness.

The benchmark's tests put it in place as `bench.reference.toy_moe` to
show that an architecture plugs in as a module of its own: its mapping
to the program, a stack whose leading dense layers are a list, an
expert layer, a term added to the loss, and draw scales for leaves that
the name rule cannot tell.

The block: pre-norm sequential residual, RMSNorm, linear attention
with full rotary embedding and no biases, untied unembedding.  The
first `first_k_dense_replace` layers have a SwiGLU MLP of width
`intermediate_size`; the others a routed expert layer: softmax router,
the top `num_experts_per_tok` experts, gates renormalized to sum to 1,
SwiGLU experts of width `moe_intermediate_size`, plus
`n_shared_experts` such experts on every token.  The loss adds
`aux_loss_alpha` times the Switch load-balancing term, summed over the
expert layers.  Every token reaches each expert it chose: the
reference has no capacity, so it agrees with the program only where the
program drops no token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common as c
from bench.reference import model


def program_fields(cfg: dict) -> dict:
    return dict(
        family="moe", num_kv_heads=cfg["num_key_value_heads"],
        qkv_bias=False, mlp_act="swiglu", norm="rmsnorm",
        parallel_residual=False, rope_kind="standard", rope_fraction=1.0,
        rope_theta=float(cfg["rope_theta"]),
        moe=dict(num_experts=cfg["n_routed_experts"],
                 top_k=cfg["num_experts_per_tok"],
                 d_expert=cfg["moe_intermediate_size"],
                 num_shared=cfg["n_shared_experts"],
                 capacity_factor=cfg["capacity_factor"],
                 first_dense_layers=cfg["first_k_dense_replace"],
                 dense_d_ff=cfg["intermediate_size"],
                 aux_loss_weight=cfg["aux_loss_alpha"]))


def smoke_keys(cfg: dict) -> dict:
    """The program's moonshot-v1-16b-a3b smoke shape: one dense layer
    and two expert layers, 8 experts of width 32, top 2, 2 shared."""
    return {"num_hidden_layers": 3, "num_key_value_heads": 4,
            "n_routed_experts": 8, "num_experts_per_tok": 2,
            "moe_intermediate_size": 32}


def _block(lead: tuple, d: int, kv: int, ffn: dict) -> dict:
    return {"ln1": {"scale": lead + (d,)}, "ln2": {"scale": lead + (d,)},
            "mixer": {"wq": {"w": lead + (d, d)}, "wk": {"w": lead + (d, kv)},
                      "wv": {"w": lead + (d, kv)}, "wo": {"w": lead + (d, d)}},
            "ffn": ffn}


def _experts(lead: tuple, d: int, f: int) -> dict:
    return {"wi": lead + (d, f), "wg": lead + (d, f), "wo": lead + (f, d)}


def param_shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    k = cfg["first_k_dense_replace"]
    n = (cfg["num_hidden_layers"] - k,)
    f, wide = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    dense = {"wi": {"w": (d, wide)}, "wg": {"w": (d, wide)},
             "wo": {"w": (wide, d)}}
    experts = {"router": {"w": n + (d, cfg["n_routed_experts"])},
               "experts": _experts(n + (cfg["n_routed_experts"],), d, f),
               "shared": _experts(n + (cfg["n_shared_experts"],), d, f)}
    return {"embed": {"table": (v, d)}, "lm_head": {"w": (d, v)},
            "ln_f": {"scale": (d,)},
            "prefix_blocks": [_block((), d, kv, dense) for _ in range(k)],
            "blocks": _block(n, d, kv, experts)}


def draw_scale(path: tuple, shape: tuple):
    """Expert stacks are bare (E, in, out) arrays: 1/sqrt(fan-in)."""
    if len(path) > 1 and path[-2] in ("experts", "shared"):
        return 1.0 / np.sqrt(shape[-2])
    return None


def _attend(mat, p, x, positions, cfg: dict):
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return x + c.attention(mat, p["mixer"],
                           c.rms_norm(p["ln1"], x, cfg["rms_norm_eps"]),
                           positions, cfg, hd, cfg["rope_theta"],
                           cfg["num_key_value_heads"])


def _swiglu(mat, wi, wg, wo, h):
    return mat("nf,fd->nd", c.silu(mat("nd,df->nf", h, wg))
               * mat("nd,df->nf", h, wi), wo)


def dense_layer(mat, p, x, positions, cfg: dict):
    x = _attend(mat, p, x, positions, cfg)
    h = c.rms_norm(p["ln2"], x, cfg["rms_norm_eps"])
    f = p["ffn"]
    return x + _swiglu(mat, f["wi"]["w"], f["wg"]["w"], f["wo"]["w"], h)


def _expert_layer(mat, p, x, positions, cfg: dict):
    """(the layer's output, its Switch load-balancing term)."""
    x = _attend(mat, p, x, positions, cfg)
    h = c.rms_norm(p["ln2"], x, cfg["rms_norm_eps"])
    f = p["ffn"]
    e = cfg["n_routed_experts"]
    probs = jax.nn.softmax(mat("nd,de->ne", h, f["router"]["w"]), axis=-1)
    gates, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    gates = gates / gates.sum(-1, keepdims=True)
    # every expert on every token, weighted by its gate where chosen
    weight = jnp.einsum("nk,nke->ne", gates, jax.nn.one_hot(chosen, e))
    y = sum(weight[:, i:i + 1] * _swiglu(
        mat, f["experts"]["wi"][i], f["experts"]["wg"][i],
        f["experts"]["wo"][i], h) for i in range(e))
    for i in range(cfg["n_shared_experts"]):
        y = y + _swiglu(mat, f["shared"]["wi"][i], f["shared"]["wg"][i],
                        f["shared"]["wo"][i], h)
    first = jax.nn.one_hot(chosen[:, 0], e).mean(0)
    return x + y, e * jnp.sum(first * probs.mean(0))


def layer(mat, p, x, positions, cfg: dict):
    return _expert_layer(mat, p, x, positions, cfg)[0]


def stack(mat, params, x, positions, cfg: dict):
    for p in params["prefix_blocks"]:
        x = dense_layer(mat, p, x, positions, cfg)
    return model.scan_blocks(mat, params, x, positions, cfg)


def loss_terms(mat, params, tokens, cfg: dict):
    """The load-balancing terms of one row, summed over expert layers."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"]["table"][tokens]
    for p in params["prefix_blocks"]:
        x = dense_layer(mat, p, x, positions, cfg)

    def body(x, p):
        return _expert_layer(mat, p, x, positions, cfg)

    _, aux = jax.lax.scan(body, x, params["blocks"])
    return aux.sum()


def loss_weight(cfg: dict) -> float:
    return cfg["aux_loss_alpha"]


def final_norm(p, x, cfg: dict):
    return c.rms_norm(p, x, cfg["rms_norm_eps"])


def unembedding(params):
    return params["lm_head"]["w"], False
