"""Minimal functional layer library (no external NN framework).

Params are plain nested dicts of jnp arrays; every layer is an
(init, apply) pair.  Matmuls run in the config's compute dtype with f32
accumulation; norms always compute in f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.act_sharding import BATCH, MODEL, constrain

F32 = jnp.float32


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, bias: bool = False,
               dtype=F32, scale: float | None = None):
    scale = (1.0 / d_in) ** 0.5 if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_in, d_out), F32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, compute_dtype=None, gather_weight: bool = False):
    w = p["w"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
        x = x.astype(compute_dtype)
    if gather_weight:
        # FSDP semantics: un-shard the weight's data (FSDP) dim at the
        # point of use — the partitioner otherwise all-gathers the much
        # larger ACTIVATIONS over the contracting dim (observed on the
        # mamba2 in_proj).  Opt-in per call site: replicating weights is
        # a LOSS where the activation path was already collective-free.
        w = constrain(w, None, MODEL)
    y = jnp.einsum("...i,io->...o", x, w)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str = "rmsnorm", dtype=F32):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p, x, kind: str = "rmsnorm", eps: float = 1e-5):
    xf = x.astype(F32)
    if kind == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(F32) + p["bias"].astype(F32)
    else:
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(F32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, act: str = "swiglu", dtype=F32):
    k1, k2, k3 = jax.random.split(key, 3)
    if act == "swiglu":
        return {
            "wi": dense_init(k1, d_model, d_ff, dtype=dtype),
            "wg": dense_init(k2, d_model, d_ff, dtype=dtype),
            "wo": dense_init(k3, d_ff, d_model, dtype=dtype),
        }
    return {
        "wi": dense_init(k1, d_model, d_ff, dtype=dtype),
        "wo": dense_init(k2, d_ff, d_model, dtype=dtype),
    }


def mlp_apply(p, x, act: str = "swiglu", compute_dtype=None):
    if act == "swiglu":
        h = jax.nn.silu(dense(p["wg"], x, compute_dtype)) * dense(
            p["wi"], x, compute_dtype)
    else:
        h = jax.nn.gelu(dense(p["wi"], x, compute_dtype))
    if h.ndim == 3:
        h = constrain(h, BATCH, None, MODEL)
    return dense(p["wo"], h, compute_dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d: int, dtype=F32):
    return {"table": (jax.random.normal(key, (vocab, d), F32) * 0.02
                      ).astype(dtype)}


def embed_lookup(p, tokens, compute_dtype=None):
    with jax.named_scope("embed"):
        t = p["table"]
        if compute_dtype is not None:
            t = t.astype(compute_dtype)
        return jnp.take(t, tokens, axis=0)


def unembed(p, x, compute_dtype=None):
    t = p["table"]
    if compute_dtype is not None:
        t = t.astype(compute_dtype)
        x = x.astype(compute_dtype)
    return jnp.einsum("...d,vd->...v", x, t)


def sinusoid_positions(n: int, d: int, offset=0) -> jnp.ndarray:
    """Computed sinusoidal absolute position encodings (whisper-style)."""
    pos = jnp.arange(n, dtype=F32) + offset
    inv = jnp.exp(-jnp.arange(0, d, 2, dtype=F32) / d * jnp.log(10000.0))
    ang = pos[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
