"""Transformer / SSM blocks: norm + mixer + FFN with residuals.

The token mixer is resolved ONCE per call through the attention-backend
registry (`repro.mixers.get_backend`) — blocks never branch on backend
or mixer strings.  Every backend exposes (init, apply, init_cache,
prefill, decode), so model.py can scan over stacked layer params
uniformly; `backend.fuses_ffn` tells the block whether the mixer already
contains its channel mixing (mamba2).  `apply` returns (y, aux) where
aux is the MoE load-balancing loss (0.0 otherwise).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.mixers import get_backend
from repro.models import moe as moe_mod
from repro.models.common import mlp_apply, mlp_init, norm_apply, norm_init

ZERO = jnp.float32(0.0)


def _ffn_init(key, cfg, dtype, dense_ffn: bool = False):
    """MoE unless cfg.moe is None or this layer is forced dense."""
    if cfg.moe is not None and not dense_ffn:
        return moe_mod.moe_init(key, cfg, dtype)
    d_ff = cfg.d_ff
    if cfg.moe is not None and dense_ffn:
        d_ff = cfg.moe.dense_d_ff or cfg.d_ff
    return mlp_init(key, cfg.d_model, d_ff, cfg.mlp_act, dtype)


def _ffn_apply(p, cfg, x, compute_dtype, dropless: bool = False):
    with jax.named_scope("mlp"):
        if "router" in p:  # structural marker: MoE FFN
            return moe_mod.moe_apply(p, cfg, x, compute_dtype, dropless)
        return mlp_apply(p, x, cfg.mlp_act, compute_dtype), ZERO


# ---------------------------------------------------------------------------
# Decoder block (causal mixer + FFN)
# ---------------------------------------------------------------------------

def block_init(key, cfg, dtype=jnp.float32, dense_ffn: bool = False):
    backend = get_backend(cfg)
    k1, k2 = jax.random.split(key)
    p = {"ln1": norm_init(cfg.d_model, cfg.norm, dtype),
         "mixer": backend.init(k1, cfg, dtype)}
    if not backend.fuses_ffn:
        p["ln2"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["ffn"] = _ffn_init(k2, cfg, dtype, dense_ffn)
    return p


def block_apply(p, cfg, x, positions, compute_dtype=None):
    backend = get_backend(cfg)
    h = norm_apply(p["ln1"], x, cfg.norm)
    with jax.named_scope("attention"):
        attn_out = backend.apply(p["mixer"], cfg, h, positions,
                                 compute_dtype)
    if backend.fuses_ffn:
        return x + attn_out, ZERO
    if cfg.parallel_residual:
        ffn_out, aux = _ffn_apply(p["ffn"],
                                  cfg, norm_apply(p["ln2"], x, cfg.norm),
                                  compute_dtype)
        return x + attn_out + ffn_out, aux
    x = x + attn_out
    ffn_out, aux = _ffn_apply(p["ffn"], cfg,
                              norm_apply(p["ln2"], x, cfg.norm),
                              compute_dtype)
    return x + ffn_out, aux


def block_init_cache(cfg, batch, max_len, dtype=jnp.bfloat16):
    return get_backend(cfg).init_cache(cfg, batch, max_len, dtype)


def block_prefill(p, cfg, x, positions, cache, compute_dtype=None):
    backend = get_backend(cfg)
    h = norm_apply(p["ln1"], x, cfg.norm)
    with jax.named_scope("attention"):
        attn_out, cache = backend.prefill(p["mixer"], cfg, h, positions,
                                          cache, compute_dtype)
    if backend.fuses_ffn:
        return x + attn_out, cache
    if cfg.parallel_residual:
        ffn_out, _ = _ffn_apply(p["ffn"], cfg,
                                norm_apply(p["ln2"], x, cfg.norm),
                                compute_dtype)
        return x + attn_out + ffn_out, cache
    x = x + attn_out
    ffn_out, _ = _ffn_apply(p["ffn"], cfg,
                            norm_apply(p["ln2"], x, cfg.norm),
                            compute_dtype)
    return x + ffn_out, cache


def block_decode(p, cfg, x, position, cache, compute_dtype=None):
    backend = get_backend(cfg)
    h = norm_apply(p["ln1"], x, cfg.norm)
    with jax.named_scope("attention"):
        attn_out, cache = backend.decode(p["mixer"], cfg, h, position,
                                         cache, compute_dtype)
    if backend.fuses_ffn:
        return x + attn_out, cache
    if cfg.parallel_residual:
        ffn_out, _ = _ffn_apply(p["ffn"], cfg,
                                norm_apply(p["ln2"], x, cfg.norm),
                                compute_dtype, dropless=True)
        return x + attn_out + ffn_out, cache
    x = x + attn_out
    ffn_out, _ = _ffn_apply(p["ffn"], cfg,
                            norm_apply(p["ln2"], x, cfg.norm),
                            compute_dtype, dropless=True)
    return x + ffn_out, cache


# ---------------------------------------------------------------------------
# Encoder block (bidirectional self-attention; whisper encoder)
# ---------------------------------------------------------------------------

def enc_block_init(key, cfg, dtype=jnp.float32):
    backend = get_backend(cfg)
    k1, k2 = jax.random.split(key)
    return {"ln1": norm_init(cfg.d_model, cfg.norm, dtype),
            "attn": backend.init(k1, cfg, dtype),
            "ln2": norm_init(cfg.d_model, cfg.norm, dtype),
            "ffn": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)}


def enc_block_apply(p, cfg, x, compute_dtype=None):
    backend = get_backend(cfg)
    h = norm_apply(p["ln1"], x, cfg.norm)
    x = x + backend.apply_noncausal(p["attn"], cfg, h, h,
                                    compute_dtype=compute_dtype)
    x = x + mlp_apply(p["ffn"], norm_apply(p["ln2"], x, cfg.norm),
                      cfg.mlp_act, compute_dtype)
    return x


# ---------------------------------------------------------------------------
# Cross-attention decoder block (whisper decoder)
# ---------------------------------------------------------------------------

def xdec_block_init(key, cfg, dtype=jnp.float32):
    backend = get_backend(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": norm_init(cfg.d_model, cfg.norm, dtype),
            "self": backend.init(k1, cfg, dtype),
            "ln_x": norm_init(cfg.d_model, cfg.norm, dtype),
            "cross": backend.init(k2, cfg, dtype),
            "ln2": norm_init(cfg.d_model, cfg.norm, dtype),
            "ffn": mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)}


def xdec_block_apply(p, cfg, x, enc, positions, compute_dtype=None):
    backend = get_backend(cfg)
    h = norm_apply(p["ln1"], x, cfg.norm)
    x = x + backend.apply(p["self"], cfg, h, positions, compute_dtype)
    h = norm_apply(p["ln_x"], x, cfg.norm)
    x = x + backend.apply_noncausal(p["cross"], cfg, h, enc,
                                    compute_dtype=compute_dtype)
    x = x + mlp_apply(p["ffn"], norm_apply(p["ln2"], x, cfg.norm),
                      cfg.mlp_act, compute_dtype)
    return x


def xdec_block_prefill(p, cfg, x, enc, positions, cache, compute_dtype=None):
    """cache: {"self": mixer cache, "cross": CrossState}."""
    backend = get_backend(cfg)
    h = norm_apply(p["ln1"], x, cfg.norm)
    attn_out, self_cache = backend.prefill(p["self"], cfg, h, positions,
                                           cache["self"], compute_dtype)
    x = x + attn_out
    cross_state = backend.cross_precompute(p["cross"], cfg, enc,
                                           compute_dtype)
    h = norm_apply(p["ln_x"], x, cfg.norm)
    x = x + backend.apply_noncausal(p["cross"], cfg, h, enc,
                                    compute_dtype=compute_dtype)
    x = x + mlp_apply(p["ffn"], norm_apply(p["ln2"], x, cfg.norm),
                      cfg.mlp_act, compute_dtype)
    return x, {"self": self_cache, "cross": cross_state}


def xdec_block_decode(p, cfg, x, position, cache, compute_dtype=None):
    backend = get_backend(cfg)
    h = norm_apply(p["ln1"], x, cfg.norm)
    attn_out, self_cache = backend.decode(p["self"], cfg, h, position,
                                          cache["self"], compute_dtype)
    x = x + attn_out
    h = norm_apply(p["ln_x"], x, cfg.norm)
    x = x + backend.cross_decode(p["cross"], cfg, h, cache["cross"],
                                 compute_dtype)
    x = x + mlp_apply(p["ffn"], norm_apply(p["ln2"], x, cfg.norm),
                      cfg.mlp_act, compute_dtype)
    return x, {"self": self_cache, "cross": cache["cross"]}
