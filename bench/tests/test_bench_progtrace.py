"""The program's spans and named scopes in a trace (bench/progtrace.py)
and the five readers built on them: by hand on a made-up trace, each
reader's None where its spans or scopes are missing, and the loader on
a real profile of a small engine on the CPU."""
from __future__ import annotations

import glob
import types

import pytest

from bench import progtrace, spec

DEV = "/device:TPU:0"
MS = 1_000_000          # ns


def _op(start, dur, name, path=""):
    return [DEV, start * MS, dur * MS, name, "jit_decode_fn(1)", path]


SORT = "%sort.5 = (f32[8,16]) sort(f32[8,16] %a), dimensions={1}"
COND = "%conditional.1 = (s32[8]) conditional(pred[] %p), branch_computations"
FUSION = "%fusion.3 = f32[8,32] fusion(f32[8,16] %x), kind=kOutput"

OPS = [
    _op(0, 10, COND, "jit(decode_fn)/jit(main)/sample/cond"),
    _op(1, 6, SORT, "jit(decode_fn)/jit(main)/sample/cond/branch_1_fun/sort"),
    _op(20, 12, FUSION, "jit(decode_fn)/jit(main)/while/body/mlp/dot_general"),
    _op(40, 3, FUSION, "jit(step)/transpose(jvp(mlp))/dot_general"),
    _op(50, 7, FUSION, "jit(decode_fn)/jit(main)/while/body/mlp_gate/add"),
    _op(60, 5, FUSION, "jit(decode_fn)/jit(main)/while/body/attention/dot"),
]


def _span(start, dur, name, **args):
    return [start * MS, dur * MS, name, args, "main"]


# three engine steps: host self time 100 - 35 - 15 = 50, 200 - 80 - 40
# - 20 = 60 and 40 (no wait) ms; median 50
ENGINE = [
    _span(0, 100, "engine.step", step_num=0),
    _span(0, 5, "engine.admit"),
    _span(5, 55, "engine.decode", slots=3),
    _span(20, 35, "engine.decode.wait"),
    _span(60, 10, "engine.emit"),
    _span(70, 25, "engine.prefill", tokens=8),
    _span(75, 15, "engine.prefill.wait"),
    _span(100, 200, "engine.step", step_num=1),
    _span(100, 100, "engine.decode", slots=3),
    _span(110, 80, "engine.decode.wait"),
    _span(200, 90, "engine.complete", tokens=4),
    _span(210, 40, "engine.complete.wait"),
    _span(260, 20, "engine.complete.wait"),
    _span(300, 40, "engine.step", step_num=2),
]
# two trainer steps: 100 - 50 = 50 and 80 - 60 = 20 ms; median 35
TRAINER = [
    _span(0, 100, "trainer.step", step_num=0),
    _span(0, 10, "trainer.batch"),
    _span(10, 20, "trainer.dispatch"),
    _span(40, 50, "trainer.loss.wait"),
    _span(90, 10, "trainer.record"),
    _span(100, 80, "trainer.step", step_num=1),
    _span(110, 60, "trainer.loss.wait"),
]


def _ctx(spans, ops, path):
    progtrace._loaded[path] = {"spans": sorted(spans), "ops": ops}
    return types.SimpleNamespace(
        result={"profile": types.SimpleNamespace(path=path)})


def _read(metric, ctx):
    return spec.reader(metric)(ctx)


def test_in_scope():
    assert progtrace.in_scope("jit(f)/mlp/dot", "mlp")
    assert progtrace.in_scope("jit(f)/transpose(jvp(mlp))/dot", "mlp")
    assert progtrace.in_scope("jit(f)/jit(main)/sample", "sample")
    assert not progtrace.in_scope("jit(f)/mlp_gate/add", "mlp")
    assert not progtrace.in_scope("jit(f)/attention/dot", "mlp")
    assert not progtrace.in_scope("", "mlp")


def test_readers_by_hand():
    ctx = _ctx(ENGINE, OPS, "hand-engine")
    # sort 6 ms (the conditional around it is a container) over the two
    # engine.decode spans
    assert _read("sampling_ms_per_step.chat", ctx) == pytest.approx(3.0)
    # mlp 12 + 3 ms (not mlp_gate) over 8 + 4 prefilled and 3 + 3 decoded
    assert _read("mlp_us_per_token.longprompt", ctx) == \
        pytest.approx(15e3 / 18)
    assert progtrace.self_ms(ENGINE, "engine.step") == \
        pytest.approx([50.0, 60.0, 40.0])
    for cell in ("chat", "longprompt"):
        assert _read(f"engine_host_ms_p50.{cell}", ctx) == \
            pytest.approx(50.0)
    ctx = _ctx(TRAINER, [], "hand-trainer")
    assert _read("trainer_host_ms_p50.train", ctx) == pytest.approx(35.0)


@pytest.mark.parametrize("metric", [
    "sampling_ms_per_step.chat", "mlp_us_per_token.longprompt",
    "engine_host_ms_p50.chat", "engine_host_ms_p50.longprompt",
    "trainer_host_ms_p50.train"])
def test_readers_without_spans_or_scopes(metric):
    """A program with no spans and no scopes (an older commit), or a run
    with no trace, reads None, never 0."""
    unscoped = [op[:5] + [""] for op in OPS]
    assert _read(metric, _ctx([], unscoped, "hand-bare")) is None
    assert _read(metric, types.SimpleNamespace(
        result={"profile": None})) is None
    # the spans without the scopes, and the scopes without the spans
    spans_only = _read(metric, _ctx(ENGINE + TRAINER, unscoped, "hand-s"))
    scopes_only = _read(metric, _ctx([], OPS, "hand-o"))
    if metric.startswith(("sampling", "mlp")):
        assert spans_only is None and scopes_only is None
    else:
        assert spans_only is not None and scopes_only is None


def test_op_name_among_programs_of_one_name():
    """Programs stored under one bare name (a window program per window
    length) give an operation its op name where they agree on it, and
    none they can vouch for where they disagree."""
    hlo = {"jit_window(1)": {"fusion.3": "jit(window)/mlp/dot",
                             "fusion.4": "jit(window)/attention/dot",
                             "copy.1": ""},
           "jit_window(2)": {"fusion.3": "jit(window)/mlp/dot",
                             "fusion.4": "jit(window)/mlp/add"}}
    both = progtrace._candidates(hlo, "jit_window(77)")
    assert len(both) == 2
    assert progtrace._op_name(both, FUSION) == "jit(window)/mlp/dot"
    assert progtrace._op_name(both, FUSION.replace(".3", ".4")) is None
    assert progtrace._op_name(both, "%copy.1 = f32[8] copy(f32[8] %a)") \
        == ""
    assert progtrace._op_name(both, "%add.9 = f32[8] add(f32[8] %a)") == ""


@pytest.mark.parametrize("metric", [
    "sampling_ms_per_step.chat", "mlp_us_per_token.longprompt"])
def test_scope_readers_with_a_program_missing(metric):
    """An operation of a program the trace holds no HLO for may be in
    any scope: the scope readers then read None, not a part of it."""
    missing = [op[:5] + [None] for op in OPS[:1]] + OPS[1:3] \
        + [OPS[3][:5] + [None]] + OPS[4:]
    ctx = _ctx(ENGINE, missing, "hand-missing")
    assert _read(metric, ctx) is None
    assert progtrace.unresolved_seconds(missing) == \
        {"jit_decode_fn(1)": pytest.approx(0.003)}
    # a loop or conditional without an op name takes no time of its own
    ctx = _ctx(ENGINE, [OPS[0][:5] + [None]] + OPS[1:], "hand-container")
    assert _read(metric, ctx) is not None


def test_top_ops():
    # the sort (6 ms) counts, the conditional around it does not
    top = progtrace.top_ops(OPS, top=2)
    assert top[0] == ["fusion.3 fusion", pytest.approx(0.027),
                      OPS[2][5]]
    assert top[1] == ["sort.5 sort", pytest.approx(0.006), OPS[1][5]]


def test_idle_by_span():
    # device busy [0, 10), [30, 40), [100, 110) ms: the gap [10, 30)
    # has its middle in engine.emit [15, 25), the innermost span there;
    # the gap [40, 100) in the benchmark's Engine.step alone
    ops = [_op(0, 10, FUSION), _op(30, 10, FUSION), _op(100, 10, FUSION)]
    spans = [_span(0, 120, "Engine.step"), _span(0, 50, "engine.step"),
             _span(15, 10, "engine.emit")]
    assert progtrace.idle_by_span(ops, spans) == pytest.approx(
        {"engine.emit": 0.020, "Engine.step": 0.060})


def test_hlo_op_names_of_a_real_profile(tmp_path):
    """The op names come out of the HLO that the profiler stores in the
    trace, per program and instruction; an event's text finds its own."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ x)

    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    hlo = progtrace.hlo_op_names(path)
    program, = [k for k in hlo if k.startswith("jit_f(")]
    ops = hlo[program]
    assert ops and all(progtrace.in_scope(v, "mlp") for k, v in ops.items()
                       if not k.startswith(("x", "param")))
    dot, = [k for k, v in ops.items() if v.endswith("/mlp/dot_general")]
    text = f"%{dot} = f32[8,8]{{1,0}} dot(f32[8,8]{{1,0}} %x.1)"
    assert progtrace._op_name(progtrace._candidates(hlo, program),
                              text) == ops[dot]
    assert progtrace._op_name(progtrace._candidates(hlo, "jit_f(999)"),
                              text) == ops[dot]
    # no HLO for the program: the op name cannot be told
    assert progtrace._candidates(hlo, "jit_g(1)") == []
    assert progtrace._op_name([], text) is None


def test_load_real_profile(tmp_path):
    """The loader on a profile of a small engine run on the CPU: the
    program's spans come back with their arguments, and the readers of
    host time read a number."""
    import jax
    from repro.configs.base import LACfg, ModelConfig
    from repro.models import model as mdl
    from repro.serve.engine import Engine, Request
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      attention_backend="linear",
                      la=LACfg(chunk=8, backend="xla"))
    eng = Engine(cfg, mdl.init_params(cfg, jax.random.PRNGKey(0)),
                 max_slots=2, max_len=32, eos_id=-1, prefill_chunk=4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.submit(Request(rid=0, prompt=list(range(3, 13)),
                           max_new_tokens=3))
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = progtrace.load(path)
    names = [s[2] for s in got["spans"]]
    assert names.count("engine.prefill") == 2
    assert names.count("engine.complete") == 1
    assert progtrace.count(got["spans"], ("engine.prefill",
                                          "engine.complete"), "tokens") == 10
    assert progtrace.count(got["spans"], ("engine.decode",), "slots") == 2
    ctx = types.SimpleNamespace(result={"profile": types.SimpleNamespace(
        path=path)})
    assert _read("engine_host_ms_p50.chat", ctx) > 0
