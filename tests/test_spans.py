"""Profiler spans and device-side names (repro.obs.spans).

  * under `jax.profiler`, an Engine and a Trainer write every documented
    `engine.*` / `trainer.*` span into the trace, each nested in its
    documented parent, with counts that agree with the engine's own
    per-step budget, and an `engine.decode` span counts the slots that
    sample;
  * the lowered decode, prefill, completion and train programs carry the
    model's named scopes in their op-name metadata;
  * every Pallas kernel of the linear and decode families carries its
    name in the jaxpr.
"""
import collections
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src import core

from helpers import backend_cfg
from repro.models import model as mdl
from repro.obs import ServeTracer, span
from repro.serve.engine import Engine, Request
from repro.serve.sampling import SamplingParams

# span -> the span it opens inside (None: opened by the caller)
ENGINE_PARENTS = {
    "engine.step": None,
    "engine.submit": None,
    "engine.admit": "engine.step",
    "engine.decode": "engine.step",
    "engine.decode.wait": "engine.decode",
    "engine.emit": "engine.step",
    "engine.prefill": "engine.step",
    "engine.prefill.wait": "engine.prefill",
    "engine.complete": "engine.step",
    "engine.complete.wait": "engine.complete",
}
TRAINER_PARENTS = {
    "trainer.step": None,
    "trainer.batch": "trainer.step",
    "trainer.dispatch": "trainer.step",
    "trainer.loss.wait": "trainer.step",
    "trainer.record": "trainer.step",
}


def _profiled(tmp_path, fn):
    """Runs fn() under the profiler (no Python function tracing); the
    host spans whose names start with engine. or trainer., as
    (start, end, name, args, parent name) sorted by start."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "trainer.")):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name, dict(e.stats), line.name))
    spans.sort(key=lambda s: (s[0], -s[1]))
    out, open_ = [], []
    for start, end, name, args, line in spans:
        while open_ and not (open_[-1][3] == line and start < open_[-1][1]):
            open_.pop()
        parent = open_[-1][2] if open_ else None
        out.append((start, end, name, args, parent))
        open_.append((start, end, name, line))
    return out


@pytest.fixture(scope="module")
def linear_setup():
    cfg = backend_cfg("linear")
    return cfg, mdl.init_params(cfg, jax.random.PRNGKey(0))


def test_engine_spans_nest_and_count(linear_setup, tmp_path):
    cfg, params = linear_setup
    eng = Engine(cfg, params, max_slots=2, max_len=64, eos_id=-1,
                 prefill_chunk=5, tracer=ServeTracer())
    budgets = []

    def drive():
        eng.submit(Request(rid=0, prompt=list(range(3, 9)),
                           max_new_tokens=6))        # windows 5, 1
        for _ in range(3):
            eng.step()
            budgets.append(dict(eng.last_step_budget))
        eng.submit(Request(rid=1, prompt=list(range(3, 15)),
                           max_new_tokens=3))        # windows 5, 5, 2
        while eng.scheduler.has_work():
            eng.step()
            budgets.append(dict(eng.last_step_budget))

    spans = _profiled(tmp_path, drive)
    names = collections.Counter(s[2] for s in spans)
    assert set(names) == set(ENGINE_PARENTS), names
    for _, _, name, _, parent in spans:
        assert parent == ENGINE_PARENTS[name], (name, parent)
    assert names["engine.submit"] == 2
    assert names["engine.prefill"] == 3 and names["engine.complete"] == 2

    steps = [s for s in spans if s[2] == "engine.step"]
    assert [s[3]["step_num"] for s in steps] == list(range(len(budgets)))
    for (start, end, *_), budget in zip(steps, budgets):
        inner = [s for s in spans if start <= s[0] and s[1] <= end]
        prefill = sum(s[3]["tokens"] for s in inner
                      if s[2] in ("engine.prefill", "engine.complete"))
        decode = sum(s[3]["slots"] for s in inner
                     if s[2] == "engine.decode")
        assert (prefill, decode) == (budget["prefill"], budget["decode"])
    # per phase, never per slot or per token: one decode and one emit
    # span a step at most
    for start, end, *_ in steps:
        inner = collections.Counter(s[2] for s in spans
                                    if start <= s[0] and s[1] <= end)
        assert inner["engine.decode"] <= 1 and inner["engine.emit"] <= 1


def test_decode_span_counts_sampled_slots(linear_setup, tmp_path):
    """`sampled` counts the decoding slots with temperature > 0: those
    for which the decode program filters and draws, not takes argmax."""
    cfg, params = linear_setup
    hot = SamplingParams(temperature=0.8, top_p=0.9, seed=1)

    def decode_spans(sampling, trace_dir):
        eng = Engine(cfg, params, max_slots=3, max_len=64, eos_id=-1,
                     prefill_chunk=16)
        for rid, (sp, n) in enumerate(zip(sampling, (2, 5, 5))):
            eng.submit(Request(rid=rid, prompt=list(range(3, 7)),
                               max_new_tokens=n, sampling=sp))
        spans = _profiled(tmp_path / trace_dir, eng.run)
        return [(s[3]["slots"], s[3]["sampled"]) for s in spans
                if s[2] == "engine.decode"]

    # the greedy request (2 tokens) leaves after its first decode step
    assert decode_spans([SamplingParams(), hot, hot], "mixed") == \
        [(3, 2)] + [(2, 2)] * 3
    assert decode_spans([SamplingParams()] * 3, "greedy") == \
        [(3, 0)] + [(2, 0)] * 3


def test_trainer_spans_nest_and_count(tmp_path):
    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.data.pipeline import SyntheticLM
    from repro.train.loop import Trainer
    cfg = get_config("pythia-1.4b", smoke=True)
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=2,
                     checkpoint_every=0)
    trainer = Trainer(cfg, tc, mdl.init_params(cfg, jax.random.PRNGKey(0)),
                      SyntheticLM(cfg.vocab_size, 32, 2, seed=0))
    spans = _profiled(tmp_path, lambda: trainer.run(2))
    names = collections.Counter(s[2] for s in spans)
    assert names == {n: 2 for n in TRAINER_PARENTS}, names
    for _, _, name, _, parent in spans:
        assert parent == TRAINER_PARENTS[name], (name, parent)
    steps = [s[3] for s in spans if s[2] == "trainer.step"]
    assert [a["step_num"] for a in steps] == [0, 1]


def test_span_outside_profiler_is_inert():
    with span("engine.step", step_num=3):
        with span("engine.decode", slots=2):
            pass


# ---------------------------------------------------------------------------
# named scopes in the lowered programs
# ---------------------------------------------------------------------------

def _scopes(lowered) -> set:
    """Every component of every op-name path in the lowered module."""
    text = lowered.as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]+)"', text)
    return {c for n in names for c in re.split(r"[/()]", n) if c}


def _args(x):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x)


def test_serving_programs_carry_scopes(linear_setup):
    cfg, params = linear_setup
    eng = Engine(cfg, params, max_slots=2, max_len=64, eos_id=-1,
                 prefill_chunk=5)
    n = eng.num_slots
    f32, i32 = jnp.float32, jnp.int32
    decode = eng._decode.lower(
        params, eng.cache, jax.ShapeDtypeStruct((n,), i32),
        jax.ShapeDtypeStruct((n, 2), jnp.uint32),
        jax.ShapeDtypeStruct((n,), f32), jax.ShapeDtypeStruct((n,), i32),
        jax.ShapeDtypeStruct((n,), f32))
    carry = eng._fresh_carry()
    tokens = jax.ShapeDtypeStruct((1, 5), i32)
    prefill = eng._prefill_fn(5).lower(params, carry, tokens)
    batch_leaves = [x for x, d in zip(jax.tree.leaves(eng.cache),
                                      eng._flat_dims) if d >= 0]
    complete = eng._complete_fn(5).lower(
        params, carry, tokens, _args(batch_leaves), jnp.int32(0),
        jax.ShapeDtypeStruct((1, 2), jnp.uint32), jnp.uint32(0),
        jnp.asarray(True), jax.ShapeDtypeStruct((1,), f32),
        jax.ShapeDtypeStruct((1,), i32), jax.ShapeDtypeStruct((1,), f32))
    model = {"embed", "attention", "mlp", "unembed"}
    assert model | {"sample"} <= _scopes(decode)
    assert model <= _scopes(prefill)
    assert "sample" not in _scopes(prefill)
    assert model | {"sample"} <= _scopes(complete)


def test_train_program_carries_scopes():
    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.optim import adamw
    from repro.train.step import build_train_step
    cfg = get_config("pythia-1.4b", smoke=True)
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=2)
    params = jax.eval_shape(lambda: mdl.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    lowered = jax.jit(build_train_step(cfg, tc)).lower(
        params, jax.eval_shape(adamw.init, params),
        {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)},
        jax.ShapeDtypeStruct((), jnp.int32))
    assert {"embed", "attention", "mlp", "unembed", "adamw"} <= \
        _scopes(lowered)


# ---------------------------------------------------------------------------
# Pallas kernel names
# ---------------------------------------------------------------------------

def _kernel_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.add(eqn.params["name"])
        for sub in core.jaxprs_in_params(eqn.params):
            _kernel_names(sub, out)
    return out


@pytest.mark.parametrize("case, names", [
    ("la_fwd", {"la_fwd"}),
    ("la_bwd", {"la_fwd", "la_bwd_dq", "la_bwd_dkv"}),
    ("linear_decode_fused", {"linear_decode_fused"}),
    ("gla_decode_fused", {"gla_decode_fused"}),
    ("softmax_decode_fused", {"softmax_decode_fused"}),
    ("paged_decode_fused", {"paged_decode_fused"}),
])
def test_pallas_kernels_are_named(case, names):
    from repro.kernels.cases import CASES
    c, = [c for c in CASES if c.name == case]
    args = jax.eval_shape(c.make, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(c.run("pallas_interpret"))(*args)
    assert _kernel_names(jaxpr.jaxpr, set()) == names
    assert all(n in str(jaxpr) for n in names)
