"""The plain reference model: logits at chosen positions, the training
loss and its gradient, and AdamW, in float32 jax.numpy.

It imports nothing of the program.  The block of each architecture is in
`bench/reference/<model_type>.py`; this file runs the stack.  Weights
come from `bench/weights.py`, drawn anew from the seed.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from bench.reference import common as c

F32 = jnp.float32


def arch(cfg: dict):
    """The module of the configuration's architecture,
    bench/reference/<model_type>.py.  Besides `program_fields`,
    `param_shapes`, `layer`, `final_norm` and `unembedding` it may give
    `stack` (the layers from the embedding to the final norm; default
    `scan_blocks`), `loss_terms` with `loss_weight` (a term added to the
    loss; default none), `draw_scale` (bench/weights.py) and `smoke_keys`
    (bench/tests/tiny.py)."""
    name = "bench.reference." + cfg["model_type"]
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"no architecture module for model_type "
                         f"{cfg['model_type']!r}: add bench/reference/"
                         f"{cfg['model_type']}.py") from None


def scan_blocks(mat, params, x, positions, cfg: dict):
    """The default stack: the architecture's `layer` over the stacked
    `params["blocks"]`, each layer recomputed in the backward."""
    a = arch(cfg)

    @jax.checkpoint
    def body(x, lp):
        return a.layer(mat, lp, x, positions, cfg), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return x


def hidden(params, cfg: dict, tokens, precision: str = "f32"):
    """Final-normed hidden states (N, d) of one sequence of tokens (N,)."""
    a = arch(cfg)
    mat = c.Mat(precision)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"]["table"].astype(F32)[tokens]
    x = getattr(a, "stack", scan_blocks)(mat, params, x, positions, cfg)
    return a.final_norm(params["ln_f"], x, cfg)


def logits_at(params, cfg: dict, tokens, rows, precision: str = "f32"):
    """Logits (len(rows), V) at positions `rows` of the sequence."""
    a = arch(cfg)
    h = hidden(params, cfg, tokens, precision)[rows]
    w, tied = a.unembedding(params)
    return c.Mat(precision)("nd,vd->nv" if tied else "nd,dv->nv", h, w)


def loss(params, cfg: dict, tokens, precision: str = "f32"):
    """Mean next-token cross-entropy over a batch (B, N): every position
    but the last predicts the token after it.  Where the architecture
    gives `loss_terms`, their mean over the rows is added with the
    weight its `loss_weight(cfg)` states."""
    a = arch(cfg)
    mat = c.Mat(precision)
    w, tied = a.unembedding(params)
    n = tokens.shape[1]
    mask = jnp.ones((n,), F32).at[-1].set(0.0)
    total = 0.0
    for row in tokens:
        h = hidden(params, cfg, row, precision)
        labels = jnp.concatenate([row[1:], row[:1]])
        total = total + c.cross_entropy_rows(mat, h, w, labels, mask, tied)
    ce = total / (tokens.shape[0] * (n - 1))
    if not hasattr(a, "loss_terms"):
        return ce
    terms = sum(a.loss_terms(mat, params, row, cfg) for row in tokens)
    return ce + a.loss_weight(cfg) * terms / tokens.shape[0]


def lr_at(step, opt: dict):
    """Linear warm-up to the peak, then cosine decay (the paper's 5.2);
    `step` is the 0-based index of the step, a traced integer."""
    step = jnp.asarray(step, F32)
    warm = opt["learning_rate"] * step / max(opt["warmup_steps"], 1)
    t = jnp.clip((step - opt["warmup_steps"])
                 / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    lo, hi = opt["min_learning_rate"], opt["learning_rate"]
    cos = lo + 0.5 * (hi - lo) * (1 + jnp.cos(jnp.pi * t))
    return jnp.where(step < opt["warmup_steps"], warm, cos)


def adamw_step(params, grads, mu, nu, step, opt: dict):
    """One AdamW update at step index `step` (0-based): gradients clipped
    to a global norm, then decoupled weight decay on every leaf stored
    with two or more dimensions (the traffic file's `decay_rule`).
    Returns (params, mu, nu, the gradient as clipped)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    if opt["grad_clip"]:
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    lr = lr_at(step, opt)
    k = jnp.asarray(step, F32) + 1
    b1c, b2c = 1 - b1 ** k, 1 - b2 ** k
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def upd(p, m, v):
        delta = (m / b1c) / (jnp.sqrt(v / b2c) + eps)
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p
        return p - lr * delta

    return jax.tree.map(upd, params, mu, nu), mu, nu, grads
