"""Each plain reference against the program at smoke size on the CPU,
and the fp8 control against the same reference."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.reference import model as ref
from bench.tests.tiny import tiny

CONFIGS = [c["name"] for c in spec.benchmark()["configs"]]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_logits_match_program(name):
    from repro.models import model as mdl
    cfg = tiny(spec.config(name))
    mc = spec.model_config(cfg)
    weights.check_layout(cfg, jax.eval_shape(
        lambda: mdl.init_params(mc, jax.random.PRNGKey(0))))
    params = weights.make_fn(cfg)(weights.seed_key(2 ** 33 + 5))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 0, 256)
    with jax.default_matmul_precision("highest"):
        prog = jax.jit(lambda p, t: mdl.forward_logits(
            p, mc, {"tokens": t[None]})[0])(params, tokens)
    want = jax.jit(lambda p, t: ref.logits_at(p, cfg, t, jnp.arange(40)))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_loss_matches_program(name):
    from repro.models import model as mdl
    cfg = tiny(spec.config(name))
    mc = spec.model_config(cfg)
    params = weights.make_fn(cfg)(weights.seed_key(7))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 32), 0, 256)
    with jax.default_matmul_precision("highest"):
        prog, g_prog = jax.jit(jax.value_and_grad(
            lambda p, t: mdl.loss_fn(p, mc, {"tokens": t})[0]))(params, tokens)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss(p, cfg, t)))(params, tokens)
    np.testing.assert_allclose(float(prog), float(want), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-6)


def test_fp8_control_departs_from_reference():
    cfg = tiny(spec.config("qwen2.5-3b-la"))
    params = weights.make_fn(cfg)(weights.seed_key(3))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (32,), 0, 256)
    f32, fp8 = (jax.jit(lambda p, t, prec=prec: ref.logits_at(
        p, cfg, t, jnp.arange(32), prec))(params, tokens)
        for prec in ("f32", "fp8"))
    gap = float(jnp.max(jnp.abs(fp8 - f32)))
    assert gap > 1e-2, gap


def test_weights_are_seeded():
    cfg = tiny(spec.config("pythia-1.4b-la-6l"))
    fn = weights.make_fn(cfg)
    a, b = fn(weights.seed_key(11)), fn(weights.seed_key(11))
    c = fn(weights.seed_key(12))
    la, lb, lc = (jax.tree.leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not any(np.array_equal(x, y) for x, y in zip(la, lc)
                   if x.size > 1)
