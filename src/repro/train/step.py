"""train_step / prefill_step / serve_step builders.

build_train_step returns a pure (params, opt_state, batch, [err]) ->
(params, opt_state, metrics, [err]) function ready for jax.jit with the
shardings from distributed/.  Features:

  * microbatched gradient accumulation (lax.scan over microbatches —
    XLA's latency-hiding scheduler overlaps the per-microbatch grad
    all-reduce with the next microbatch's compute on TPU);
  * optional int8 gradient compression with error feedback on the DP
    all-reduce (distributed/compression.py) via an explicit psum form;
  * mixed precision: bf16 compute, f32 master params/moments handled by
    the model layer + AdamW.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.distributed import compression
from repro.models import model as mdl
from repro.optim import adamw, schedules

F32 = jnp.float32


def make_loss(cfg):
    def loss(params, batch):
        return mdl.loss_fn(params, cfg, batch)
    return loss


def _microbatches(batch, n: int):
    """Split batch dim into (n, b/n, ...) for scan."""
    def split(x, bdim):
        b = x.shape[bdim]
        shape = x.shape[:bdim] + (n, b // n) + x.shape[bdim + 1:]
        return jnp.moveaxis(x.reshape(shape), bdim, 0)
    return {k: split(v, 1 if k == "positions" else 0)
            for k, v in batch.items()}


def build_train_step(cfg, train_cfg):
    """Returns step(params, opt_state, batch, step_idx) -> (...)."""
    loss = make_loss(cfg)

    def lr_at(step_idx):
        return schedules.cosine_warmup_decay(
            step_idx, max_lr=train_cfg.learning_rate,
            min_lr=train_cfg.min_learning_rate,
            warmup_steps=train_cfg.warmup_steps,
            total_steps=train_cfg.total_steps)

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def compute_grads(params, batch):
        if train_cfg.microbatch and train_cfg.microbatch > 1:
            n = train_cfg.microbatch
            mb = _microbatches(batch, n)

            def body(acc, mbatch):
                (l, aux), g = grad_fn(params, mbatch)
                acc = jax.tree.map(lambda a, b: a + b.astype(F32), acc, g)
                return acc, (l, aux)

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
            gsum, (losses, auxes) = jax.lax.scan(body, zero, mb)
            grads = jax.tree.map(lambda g: g / n, gsum)
            metrics = {"loss": losses.mean(),
                       "ce": auxes["ce"].mean(), "aux": auxes["aux"].mean()}
        else:
            (l, aux), grads = grad_fn(params, batch)
            metrics = {"loss": l, **aux}
        return grads, metrics

    def step(params, opt_state, batch, step_idx):
        grads, metrics = compute_grads(params, batch)
        lr = lr_at(step_idx)
        with jax.named_scope("adamw"):
            params, opt_state, om = adamw.apply(
                params, grads, opt_state, lr=lr, beta1=train_cfg.beta1,
                beta2=train_cfg.beta2, weight_decay=train_cfg.weight_decay,
                grad_clip=train_cfg.grad_clip)
        metrics.update(om)
        metrics["lr"] = lr
        return params, opt_state, metrics

    return step


def build_compressed_train_step(cfg, train_cfg, axis_name: str = "data"):
    """Explicit-DP variant with int8 grad all-reduce + error feedback.

    Meant to be shard_map'd over the DP axis (per-device batch in, psum
    inside).  Carries the error-feedback pytree in the train state.
    """
    loss = make_loss(cfg)
    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def step(params, opt_state, err, batch, step_idx):
        (l, aux), grads = grad_fn(params, batch)
        grads, err = compression.compressed_psum(grads, err, axis_name)
        lr = schedules.cosine_warmup_decay(
            step_idx, max_lr=train_cfg.learning_rate,
            min_lr=train_cfg.min_learning_rate,
            warmup_steps=train_cfg.warmup_steps,
            total_steps=train_cfg.total_steps)
        with jax.named_scope("adamw"):
            params, opt_state, om = adamw.apply(
                params, grads, opt_state, lr=lr, beta1=train_cfg.beta1,
                beta2=train_cfg.beta2, weight_decay=train_cfg.weight_decay,
                grad_clip=train_cfg.grad_clip)
        metrics = {"loss": jax.lax.pmean(l, axis_name), **om}
        return params, opt_state, err, metrics

    return step


def build_prefill_step(cfg, window: int | None = None):
    """Prefill step; with `window`, the prompt is fed window-by-window
    carrying the recurrent state (chunked prefill) — peak activation
    memory drops ~N/window-fold, exact for every recurrent-state mixer
    (LA / SSD / hybrid).  Whisper stays single-shot (its cross-attention
    state is precomputed from the encoder, not accumulated)."""
    def prefill_step(params, batch):
        b, n = batch["tokens"].shape
        cache = mdl.init_cache(cfg, b, n)
        if window is None or n <= window or cfg.family == "encdec" \
                or n % window != 0:
            return mdl.prefill(params, cfg, batch, cache)
        t = n // window
        toks = batch["tokens"].reshape(b, t, window).transpose(1, 0, 2)
        xs = {"tokens": toks}
        if "positions" in batch:
            xs["positions"] = batch["positions"].reshape(
                3, b, t, window).transpose(2, 0, 1, 3)

        def body(cache, w):
            logits, cache = mdl.prefill(params, cfg, w, cache)
            return cache, logits

        cache, logits_all = jax.lax.scan(body, cache, xs)
        return logits_all[-1], cache
    return prefill_step


def build_serve_step(cfg):
    """One-token decode against an existing cache (paper's O(D^2)/token
    deployment path for the linear backend)."""
    def serve_step(params, cache, tokens):
        return mdl.decode_step(params, cfg, cache, tokens)
    return serve_step
