"""An architecture is one module, bench/reference/<model_type>.py: the
mapping to the program, the stack, the loss terms and the layout.  The
two configurations' ModelConfig and smoke-size weights are pinned, and a
toy mixture-of-experts architecture that no harness file knows by name
plugs in as a module."""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.reference import model as ref
from bench.tests.tiny import tiny

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")

# the fields of spec.model_config's result that differ from
# ModelConfig's defaults: the cells' ledger lines were measured on these
PINNED = {
    "pythia-1.4b-la-6l": dict(
        name="pythia-1.4b-la-6l", family="dense", num_layers=6,
        d_model=2048, num_heads=16, num_kv_heads=16, d_ff=8192,
        vocab_size=50304, attention_backend="linear",
        la=dict(a=1.0, b=1.0, normalize_qk=True, chunk=512,
                backend="pallas"),
        qkv_bias=False, mlp_act="gelu", norm="layernorm",
        parallel_residual=True, rope_kind="partial", rope_fraction=0.25,
        rope_theta=10000.0, tie_embeddings=False, param_dtype="float32",
        compute_dtype="bfloat16", remat=True),
    "qwen2.5-3b-la": dict(
        name="qwen2.5-3b-la", family="dense", num_layers=36, d_model=2048,
        num_heads=16, num_kv_heads=2, d_ff=11008, vocab_size=151936,
        attention_backend="linear",
        la=dict(a=1.0, b=1.0, normalize_qk=True, chunk=512,
                backend="pallas"),
        qkv_bias=True, mlp_act="swiglu", norm="rmsnorm",
        parallel_residual=False, rope_kind="standard", rope_fraction=1.0,
        rope_theta=1000000.0, tie_embeddings=True, param_dtype="float32",
        compute_dtype="bfloat16", remat=True),
}

# sha256 (first 16 hex digits) of each leaf of the smoke-size weights
# drawn from seed 2**33 + 5: the draw of every leaf, and so every
# reference reading on the chip, stays as the ledger's lines had it
DRAWN = {
    "pythia-1.4b-la-6l": {
        "blocks/ffn/wi/w": "0ef3bc171c776dfb",
        "blocks/ffn/wo/w": "d8f137cfdc43f9d7",
        "blocks/ln1/bias": "38430dcb321cc1f9",
        "blocks/ln1/scale": "25cf491a8d2e4a08",
        "blocks/ln2/bias": "b96a58d827409ca5",
        "blocks/ln2/scale": "4ad5c5e576c63a2f",
        "blocks/mixer/wk/w": "c0d6277f0def981e",
        "blocks/mixer/wo/w": "d5b0d8d45d0a2583",
        "blocks/mixer/wq/w": "b10234920c6484c0",
        "blocks/mixer/wv/w": "a2cd41526b743811",
        "embed/table": "b2c6cacdcd3873a7",
        "lm_head/w": "26e64e6839977f7b",
        "ln_f/bias": "a0a8f518796d5ca9",
        "ln_f/scale": "fd72167746931d9d",
    },
    "qwen2.5-3b-la": {
        "blocks/ffn/wg/w": "0ef3bc171c776dfb",
        "blocks/ffn/wi/w": "9c0a217c751f763e",
        "blocks/ffn/wo/w": "9959945dd78d37f9",
        "blocks/ln1/scale": "25cf491a8d2e4a08",
        "blocks/ln2/scale": "1a6d4afdac630d8a",
        "blocks/mixer/wk/b": "aedeeb69f758db3d",
        "blocks/mixer/wk/w": "e21fe4d50cf31d85",
        "blocks/mixer/wo/w": "d5b0d8d45d0a2583",
        "blocks/mixer/wq/b": "59eb96c0590cea6c",
        "blocks/mixer/wq/w": "a2cd41526b743811",
        "blocks/mixer/wv/b": "4e83ddd4d2abd08e",
        "blocks/mixer/wv/w": "5d1770e0e61d069e",
        "embed/table": "fe9eb8543311d2f8",
        "ln_f/scale": "fd72167746931d9d",
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_model_config_is_the_pinned_one(name):
    from repro.configs.base import LACfg, ModelConfig
    pinned = dict(PINNED[name])
    want = ModelConfig(**dict(pinned, la=LACfg(**pinned["la"])))
    got = spec.model_config(spec.config(name))
    for f in dataclasses.fields(ModelConfig):
        assert getattr(got, f.name) == getattr(want, f.name), f.name


@pytest.mark.parametrize("name", sorted(DRAWN))
def test_smoke_weights_are_the_pinned_draw(name):
    params = weights.make_fn(tiny(spec.config(name)))(
        weights.seed_key(2 ** 33 + 5))
    got = {"/".join(str(weights._key(k)) for k in path):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
           for path, leaf in jax.tree_util.tree_leaves_with_path(params)}
    assert got == DRAWN[name]


def test_unknown_model_type_names_the_missing_module():
    cfg = dict(spec.config("qwen2.5-3b-la"), model_type="no_such_arch")
    with pytest.raises(ValueError, match="bench/reference/no_such_arch.py"):
        spec.model_config(cfg)


@pytest.fixture
def toy(monkeypatch):
    """The toy architecture as bench.reference.toy_moe, and its
    configuration at smoke size."""
    name = "bench.reference.toy_moe"
    loader = importlib.util.spec_from_file_location(
        name, os.path.join(FIXTURES, "toy_moe.py"))
    module = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(module)
    monkeypatch.setitem(sys.modules, name, module)
    with open(os.path.join(FIXTURES, "toy_moe.json")) as f:
        return tiny(json.load(f))


def _toy_program(cfg):
    from repro.models import model as mdl
    mc = spec.model_config(cfg)
    weights.check_layout(cfg, jax.eval_shape(
        lambda: mdl.init_params(mc, jax.random.PRNGKey(0))))
    return mdl, mc


def test_toy_moe_maps_to_the_program_smoke_shape(toy):
    from repro.configs.base import MoECfg
    mc = spec.model_config(toy)
    assert (mc.family, mc.num_layers, mc.d_model, mc.num_heads,
            mc.num_kv_heads, mc.vocab_size) == ("moe", 3, 64, 4, 4, 256)
    assert mc.moe == MoECfg(num_experts=8, top_k=2, d_expert=32,
                            num_shared=2, first_dense_layers=1,
                            dense_d_ff=128, capacity_factor=8.0)


def test_toy_moe_logits_match_program(toy):
    mdl, mc = _toy_program(toy)
    params = weights.make_fn(toy)(weights.seed_key(2 ** 33 + 5))
    assert isinstance(params["prefix_blocks"], list)
    # the architecture's draw scale, 1/sqrt(fan-in), not the name rule's
    wi = np.asarray(params["blocks"]["ffn"]["experts"]["wi"])
    assert abs(wi.std() * np.sqrt(toy["hidden_size"]) - 1) < 0.05
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 0, 256)
    with jax.default_matmul_precision("highest"):
        prog = jax.jit(lambda p, t: mdl.forward_logits(
            p, mc, {"tokens": t[None]})[0])(params, tokens)
    want = jax.jit(lambda p, t: ref.logits_at(p, toy, t, jnp.arange(40)))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_toy_moe_loss_matches_program(toy):
    mdl, mc = _toy_program(toy)
    params = weights.make_fn(toy)(weights.seed_key(7))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 32), 0, 256)
    with jax.default_matmul_precision("highest"):
        (prog, parts), g_prog = jax.jit(jax.value_and_grad(
            lambda p, t: mdl.loss_fn(p, mc, {"tokens": t}),
            has_aux=True))(params, tokens)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss(p, toy, t)))(params, tokens)
    np.testing.assert_allclose(float(prog), float(want), rtol=2e-4)
    # the aux term is there: without it the losses would not agree
    assert abs(float(want) - float(parts["ce"])) > 1e-3
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-6)
