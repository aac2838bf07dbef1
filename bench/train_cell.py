"""A training cell: the program's Trainer, driven from the seed.

Set-up builds one Trainer (the compiled step with its state), drives
its first `check_steps` steps through the same `Trainer.run` call the
window uses, each on a batch of its own, and reads what the check needs
before step 4 replaces it: each step's loss, the first gradient as the
optimizer got it (from AdamW's first moment after one step), and each
parameter's change after the check steps.  The window then runs whole
steps until `--seconds` have passed.  Afterwards, with the program's
state freed, the reference repeats the check steps from the same seed.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, generator, harness, spec, weights
from bench.harness import now, span
from bench.reference import model as ref

F32 = jnp.float32


class Feed:
    """The Trainer's data source: batch i of the seed's stream.  A fault
    may alter what the program is fed (the reference is not)."""

    def __init__(self, traffic, vocab, seed, fault=None):
        self.traffic, self.vocab, self.seed, self.fault = \
            traffic, vocab, seed, fault

    def batch_at(self, i: int):
        tokens = generator.train_batch(self.traffic, self.vocab, self.seed, i)
        if self.fault == "token":
            tokens = tokens.copy()
            tokens[:, 0] = (tokens[:, 0] + 1) % self.vocab
        if self.fault == "half_batch":
            mask = np.ones(tokens.shape, np.float32)
            mask[:, tokens.shape[1] // 2:] = 0.0
            return {"tokens": tokens, "loss_mask": mask}
        return tokens


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


def _floats(xs) -> list:
    return [float(x) for x in jax.device_get(xs)]


def train_config(opt: dict):
    from repro.configs.base import TrainConfig
    return TrainConfig(
        learning_rate=opt["learning_rate"],
        min_learning_rate=opt["min_learning_rate"],
        warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
        weight_decay=opt["weight_decay"], beta1=opt["beta1"],
        beta2=opt["beta2"], grad_clip=opt["grad_clip"], checkpoint_every=0)


def _unchanged_step(step_fn):
    """Fault: a step that returns its state as it got it."""
    def step(params, opt_state, batch, step_idx):
        _, _, metrics = step_fn(params, opt_state, batch, step_idx)
        return params, opt_state, metrics
    return step


def build(cfg, traffic, seed, fault=None):
    """The Trainer after its check steps, and the readings they left:
    (trainer, losses, first-gradient leaf norms, leaf changes)."""
    from repro.models import model as mdl
    from repro.train.loop import Trainer

    opt = traffic["optimizer"]
    mc = spec.model_config(cfg)
    weights.check_layout(cfg, jax.eval_shape(
        lambda: mdl.init_params(mc, jax.random.PRNGKey(0))))
    make = weights.make_fn(cfg)
    trainer = Trainer(mc, train_config(opt), make(weights.seed_key(seed)),
                      Feed(traffic, cfg["vocab_size"], seed, fault))
    if fault == "unchanged":
        trainer.step_fn = _unchanged_step(trainer.step_fn)
    with span("Trainer.run"):
        trainer.run(1)
    grads = [g / (1 - opt["beta1"])
             for g in _floats(_leaf_norms(trainer.opt_state.mu))]
    with span("Trainer.run"):
        trainer.run(traffic["check_steps"] - 1)
    losses = [h["loss"] for h in trainer.history]
    change = _floats(_change_norms(trainer.params,
                                   make(weights.seed_key(seed))))
    return trainer, losses, grads, change


def run(run_ctx, fault=None) -> dict:
    cfg, traffic, seed = run_ctx.cfg, run_ctx.traffic, run_ctx.seed
    n_check = traffic["check_steps"]
    tokens_per_step = traffic["batch"] * traffic["seq_len"]
    t0 = now()
    trainer, prog_losses, grad_norms, change = build(cfg, traffic, seed,
                                                     fault)
    setup_s = now() - t0

    lowered0 = run_ctx.counter.snapshot()
    steps = 0
    t_start = now()
    while now() - t_start < run_ctx.seconds:
        with span("Trainer.run"):
            trainer.run(1)
        steps += 1
    t_end = now()
    compiles = [b - a for a, b in zip(lowered0, run_ctx.counter.snapshot())]
    window_s = t_end - t_start
    profile, traced = None, None
    if run_ctx.trace:
        # the profiler runs after the window, over a few more steps, so
        # that writing the trace out stalls nothing the window measures
        profile = harness.Profile(run_ctx.cell)
        profile.start()
        for _ in range(traffic["trace_steps"]):
            with span("Trainer.run"):
                trainer.run(1)
        profile.stop()
        traced = {"steps": traffic["trace_steps"],
                  "tokens": traffic["trace_steps"] * tokens_per_step,
                  "window_s": profile.t1 - profile.t0}
    peak = harness.memory_peak(run_ctx.devices)
    dts = [h["dt"] for h in trainer.history[n_check:]]
    compile_s = trainer.compile_s
    del trainer

    checks = reference_checks(cfg, traffic, seed, prog_losses, grad_norms,
                              change, run_ctx.limits)
    return {
        "setup_s": setup_s, "window_s": window_s,
        "attempted": steps, "failed": 0,
        "e2e": {"train_tokens_per_s": steps * tokens_per_step / window_s},
        "compiles_in_window": compiles, "memory_peak_bytes": peak,
        "profile": profile, "traced": traced,
        "side": {"steps": steps, "step_s_median": float(np.median(dts))
                 if dts else None, "step_s_max": max(dts) if dts else None,
                 "losses": prog_losses,
                 "compile_s": compile_s},
        "checks": checks,
        "flops_per_token": None,
    }


@functools.lru_cache(maxsize=None)
def _reference_fns(cfg_json: str, opt_json: str, precision: str):
    """The reference's jitted loss-and-gradient and AdamW step, built
    once per configuration, optimizer and precision."""
    cfg, opt = json.loads(cfg_json), json.loads(opt_json)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss(p, cfg, t, precision)))
    step_fn = jax.jit(
        lambda p, g, m, v, k: ref.adamw_step(p, g, m, v, k, opt),
        donate_argnums=(0, 1, 2, 3))
    return grad_fn, step_fn


def reference_steps(cfg, traffic, seed, precision="f32"):
    """The reference's check steps from the seed: (losses, the first
    clipped gradient's leaf norms, each leaf's change after the steps)."""
    opt = traffic["optimizer"]
    make = weights.make_fn(cfg)
    params = make(weights.seed_key(seed))
    zeros = jax.tree.map(jnp.zeros_like, params)
    mu, nu = zeros, jax.tree.map(jnp.zeros_like, params)
    del zeros
    grad_fn, step_fn = _reference_fns(json.dumps(cfg, sort_keys=True),
                                      json.dumps(opt, sort_keys=True),
                                      precision)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for k in range(traffic["check_steps"]):
            tokens = jnp.asarray(generator.train_batch(
                traffic, cfg["vocab_size"], seed, k))
            loss, grads = grad_fn(params, tokens)
            losses.append(float(loss))
            params, mu, nu, clipped = step_fn(params, grads, mu, nu,
                                              jnp.int32(k))
            if first is None:
                first = _floats(_leaf_norms(clipped))
            del clipped
    del mu, nu
    change = _floats(_change_norms(params, make(weights.seed_key(seed))))
    return losses, first, change


def reference_checks(cfg, traffic, seed, losses, grads, change, limits,
                     precision="f32") -> dict:
    r_losses, r_grads, r_change = reference_steps(cfg, traffic, seed,
                                                  precision)
    return compare.train_checks(losses, grads, change,
                                r_losses, r_grads, r_change, limits)
