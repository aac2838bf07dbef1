"""Observability (repro.obs): metrics math against a numpy oracle,
tracer-disabled engine identity, span completeness, the Chrome-trace
schema, the head-of-line stall pin, and the BENCH_serve.json schema
gate.

The load-bearing pins:

  * traced engine output is TOKEN-IDENTICAL to untraced — tracing may
    never change what the engine computes;
  * Histogram.percentile (bucketed) brackets the exact inverted-CDF
    percentile within one BUCKET_RATIO — the snapshot-only derivation
    the metrics artifact relies on;
  * a long prompt's chunked prefill no longer stalls a co-resident
    request's decode: its windows interleave with that request's
    tokens, at most one window between two tokens.
"""
import json
import math

import jax
import numpy as np
import pytest

from helpers import backend_cfg, run_engine_greedy
from repro.models import model as mdl
from repro.obs import (BUCKET_RATIO, LATENCY_BUCKETS, Counter, Gauge,
                       Histogram, MetricsRegistry, RequestRecord,
                       ServeTracer, Tracer, log_buckets, percentiles)
from repro.serve.engine import Engine, Request
from repro.serve.paging import PagePool
from repro.serve.scheduler import RequestState


def _exact_pct(xs, p):
    """Oracle: inverted-CDF order statistic, independent impl."""
    xs = sorted(xs)
    return xs[max(1, math.ceil(p / 100.0 * len(xs))) - 1]


# ---------------------------------------------------------------------------
# metrics: instruments + percentile math
# ---------------------------------------------------------------------------

def test_percentiles_match_oracle():
    rng = np.random.default_rng(0)
    data = rng.lognormal(-6.0, 1.5, size=501).tolist()
    got = percentiles(data, (0, 50, 90, 99, 100))
    for p, v in got.items():
        assert v == _exact_pct(data, p), p
    assert got[100] == max(data)
    assert percentiles([], (50,)) == {50: None}
    with pytest.raises(ValueError):
        percentiles([1.0], (101,))


def test_histogram_percentile_brackets_exact():
    """Bucketed percentile == upper bound of the rank's bucket: at
    least the exact value, at most BUCKET_RATIO times it."""
    rng = np.random.default_rng(1)
    data = rng.lognormal(-5.0, 1.2, size=700).tolist()  # inside bounds
    h = Histogram("h")
    for v in data:
        h.observe(v)
    for p in (50, 90, 99):
        exact = _exact_pct(data, p)
        got = h.percentile(p)
        assert exact <= got <= exact * BUCKET_RATIO * (1 + 1e-12), \
            (p, exact, got)


def test_histogram_edges():
    h = Histogram("h")
    assert h.percentile(50) is None  # empty
    h.observe(1e9)                   # overflow bucket
    assert h.percentile(99) == math.inf
    snap = h.snapshot()
    assert snap["count"] == 1
    assert snap["buckets"] == [[None, 1]]  # None upper bound == +Inf
    with pytest.raises(ValueError):
        Histogram("bad", buckets=[2.0, 1.0])


def test_log_buckets_spec():
    bs = log_buckets()
    assert tuple(bs) == LATENCY_BUCKETS
    assert bs[0] == pytest.approx(1e-5) and bs[-1] == pytest.approx(100.0)
    ratios = [b / a for a, b in zip(bs, bs[1:])]
    assert all(r == pytest.approx(BUCKET_RATIO) for r in ratios)


def test_counter_gauge_registry():
    m = MetricsRegistry()
    c = m.counter("c", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = m.gauge("g")
    assert g.value is None
    g.set(7)
    assert g.value == 7.0
    # get-or-create returns the same instrument; kind mismatch raises
    assert m.counter("c") is c
    with pytest.raises(TypeError):
        m.gauge("c")
    assert len(m) == 2
    doc = m.to_json()
    assert doc["version"] == 1
    assert doc["metrics"]["c"] == {"kind": "counter", "value": 3.5}


# ---------------------------------------------------------------------------
# tracer vs engine: identity, spans, lifecycle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def linear_setup():
    cfg = backend_cfg("linear")
    return cfg, mdl.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def softmax_setup():
    cfg = backend_cfg("softmax")
    return cfg, mdl.init_params(cfg, jax.random.PRNGKey(0))


def test_traced_engine_identity_linear(linear_setup):
    """Tracing may never change what the engine computes: token streams
    with a tracer installed are byte-identical to the untraced run,
    one-shot AND chunked prefill."""
    from helpers import assert_engine_identity
    cfg, params = linear_setup
    assert_engine_identity(
        cfg, params, {"max_slots": 2},
        {"max_slots": 2, "tracer": ServeTracer()},
        {"max_slots": 2, "prefill_chunk": 5, "tracer": ServeTracer()})


def test_traced_engine_identity_softmax_paged(softmax_setup):
    from helpers import assert_engine_identity
    cfg, params = softmax_setup
    assert_engine_identity(
        cfg, params, {"max_slots": 2, "page_size": 8},
        {"max_slots": 2, "page_size": 8, "tracer": ServeTracer()})


def test_span_completeness(linear_setup):
    """Every request that ran to completion has a full, ordered span
    tree: submit <= queued <= admitted <= first token <= finish, all
    tokens stamped, prefill windows covering the whole prompt."""
    cfg, params = linear_setup
    tr = ServeTracer()
    done, eng = run_engine_greedy(cfg, params, max_slots=2,
                                  prefill_chunk=5, tracer=tr)
    recs = tr.records()
    assert len(recs) == len(done)
    for rec in recs:
        assert rec.closed
        assert rec.finish_reason in ("stop", "length")
        assert rec.submit_t <= rec.queued_t <= rec.admitted_t
        assert rec.admitted_t <= rec.first_token_t <= rec.finish_t
        assert rec.tokens == len(done[rec.rid])
        assert list(rec.token_ts) == sorted(rec.token_ts)
        assert sum(n for _, _, n in rec.prefill_windows) == rec.prompt_len
        for t0, t1, _ in rec.prefill_windows:
            assert t1 >= t0
        assert rec.ttft_s > 0 and rec.queue_wait_s >= 0
        assert rec.total_s >= rec.decode_s >= 0
    s = tr.summary()
    assert s["finished"] == s["requests"] == len(recs)
    assert s["tokens"] == sum(len(v) for v in done.values())
    assert s["ttft_ms"]["p50"] is not None
    assert s["ttft_ms"]["p99"] is not None
    assert 0 < s["occupancy"] <= 1
    # metrics agree with the records
    m = tr.metrics
    assert m.get("serve_requests_finished_total").value == len(recs)
    assert m.get("serve_tokens_total").value == s["tokens"]
    assert m.get("serve_ttft_seconds").total == len(recs)


def test_step_output_timestamps_and_finish(linear_setup):
    """Satellite 1: StepOutput.t is a non-decreasing timer.now stamp,
    and finish outputs carry the scheduler's release stamp, which also
    lands on Request.finish_t / finish_reason."""
    cfg, params = linear_setup
    eng = Engine(cfg, params, max_slots=2, max_len=64, eos_id=-1)
    eng.submit(Request(rid=0, prompt=list(range(3, 9)), max_new_tokens=4))
    eng.submit(Request(rid=1, prompt=list(range(4, 12)), max_new_tokens=3))
    outs = list(eng.stream())
    ts = [o.t for o in outs]
    assert all(b >= a for a, b in zip(ts, ts[1:]))
    fins = {o.rid: o for o in outs if o.finished}
    assert set(fins) == {0, 1}
    for rid, out in fins.items():
        req = eng.request(rid)
        assert req.state is RequestState.FINISHED
        assert req.finish_t == out.t
        assert req.finish_reason == out.finish_reason == "length"


def test_decode_stall_inter_token_p99(linear_setup):
    """The head-of-line scenario that used to stall (the FIFO scheduler
    ran ALL of a long prompt's prefill windows inside one step, so a
    co-resident request's inter-token p99 was many times its p50):
    under scheduler v2 the token budget interleaves at most one
    prefill window with each decode step.

    The property is read from the ORDER of the tracer's stamps, not
    from their differences, so a loaded host cannot fail it: between
    any two consecutive tokens of the short request at most one window
    of the long prompt starts, and its 6 windows interleave with at
    least 4 of those tokens."""
    cfg, params = linear_setup
    tr = ServeTracer()
    eng = Engine(cfg, params, max_slots=2, max_len=64, eos_id=-1,
                 prefill_chunk=5, tracer=tr)
    eng.submit(Request(rid=0, prompt=list(range(3, 9)),
                       max_new_tokens=16))
    for _ in range(8):      # rid 0 decodes alone
        eng.step()
    eng.submit(Request(rid=1, prompt=list(range(3, 33)),
                       max_new_tokens=4))   # 6 prefill windows
    while eng.scheduler.has_work():
        eng.step()
    short, long_rec = tr.records()
    assert short.rid == 0 and short.closed and long_rec.rid == 1
    assert len(short.inter_token_s) == 15
    starts = [w[0] for w in long_rec.prefill_windows]
    assert len(starts) == 6
    ts = list(short.token_ts)
    for a, b in zip(ts, ts[1:]):
        between = sum(1 for t in starts if a < t < b)
        assert between <= 1, (between, "windows ran back to back "
                              "between two tokens: head-of-line stall")
    end = long_rec.prefill_windows[-1][1]
    interleaved = [t for t in ts if starts[0] < t < end]
    assert len(interleaved) >= 4, (len(interleaved), "prefill ran as one "
                                   "uninterrupted block — no token "
                                   "interleaving")


def test_rejected_request_traced(linear_setup):
    cfg, params = linear_setup
    tr = ServeTracer()
    eng = Engine(cfg, params, max_slots=1, max_len=16, tracer=tr)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=list(range(3, 13)),
                           max_new_tokens=50))
    rec = tr.records()[0]
    assert rec.finish_reason == "rejected:max_len"
    assert rec.closed
    assert tr.metrics.get("serve_admission_reject_total").value == 1


def test_paged_pool_gauges_and_sink(softmax_setup):
    """Page-pool telemetry: gauges mirror the pool level, every
    finished request re-points its slot at the sink page, and the
    arena drains back to empty."""
    cfg, params = softmax_setup
    tr = ServeTracer()
    done, eng = run_engine_greedy(cfg, params, max_slots=2,
                                  page_size=8, tracer=tr)
    m = tr.metrics
    assert m.get("serve_pages_in_use").value == 0
    assert m.get("serve_pages_free").value == eng.pool.num_pages
    assert m.get("serve_sink_repoints_total").value == len(done)
    s = tr.summary()
    assert s["finished"] == len(done)


def test_cow_fork_counter():
    tr = ServeTracer()
    pool = PagePool(8, 4, tracer=tr)
    pool.allocate(0, 10)          # 3 pages
    pool.fork(0, 1, 6)            # 1 shared + 1 copied tail
    assert tr.metrics.get("serve_page_cow_forks_total").value == 1
    assert tr.metrics.get("serve_pages_in_use").value == \
        pool.pages_in_use == 4
    pool.free(0)
    pool.free(1)
    assert tr.metrics.get("serve_pages_in_use").value == 0


def test_nil_tracer_is_inert():
    """The base Tracer is a pure protocol: every hook is a no-op and
    clock() is the repo timer."""
    t = Tracer()
    t.request_submitted(0, 1, 2)
    t.request_queued(0)
    t.request_rejected(0, "x")
    t.admission_blocked(0, "slots")
    t.request_admitted(0, 0)
    t.prefill_window(0, 0, 5, 0.0)
    t.token_emitted(0, 0)
    t.request_preempted(0, 0, "snapshot")
    t.request_resumed(0, 1, "snapshot")
    t.request_finished(0, "stop")
    t.engine_step(0.0, 1, 2, 0)
    t.pages_changed(1, 2)
    t.cow_fork()
    t.sink_repoint()
    assert isinstance(t.clock(), float)


# ---------------------------------------------------------------------------
# Chrome trace + report CLI
# ---------------------------------------------------------------------------

def test_chrome_trace_schema(linear_setup, tmp_path):
    cfg, params = linear_setup
    tr = ServeTracer()
    done, _ = run_engine_greedy(cfg, params, max_slots=2,
                                prefill_chunk=5, tracer=tr)
    path = tmp_path / "trace.json"
    doc = tr.export_chrome_trace(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(doc))
    ev = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    for e in ev:
        assert e["ph"] in ("X", "i", "M"), e
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    names = {e["name"] for e in ev if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= names
    # one request span per rid on pid 2; step spans on pid 0
    req_spans = [e for e in ev if e["ph"] == "X"
                 and e["name"].startswith("request ")]
    assert len(req_spans) == len(done)
    assert all(e["pid"] == 2 for e in req_spans)
    assert any(e["ph"] == "X" and e["pid"] == 0 and e["name"] == "step"
               for e in ev)
    # slot tracks carry the prefill windows
    assert any(e["ph"] == "X" and e["pid"] == 1
               and e["name"].startswith("prefill rid=") for e in ev)
    # embedded records round-trip for the report CLI
    assert len(doc["repro_records"]) == len(done)
    assert doc["repro_summary"]["finished"] == len(done)


def test_report_cli(linear_setup, tmp_path, capsys):
    from repro.obs.__main__ import main
    cfg, params = linear_setup
    tr = ServeTracer()
    run_engine_greedy(cfg, params, max_slots=2, tracer=tr)
    path = tmp_path / "trace.json"
    tr.export_chrome_trace(str(path))
    assert main(["report", str(path)]) == 0
    out = capsys.readouterr().out
    assert "ttft_ms" in out and "reason" in out and "length" in out
    assert main(["report", str(path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["summary"]["finished"] == len(doc["records"])
    # a non-trace json is a usage error, not a crash
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert main(["report", str(bad)]) == 1


# ---------------------------------------------------------------------------
# BENCH_serve.json schema gate (satellite 2)
# ---------------------------------------------------------------------------

def _serve_cell(**over):
    cell = {"impl": "linear", "backend": "linear",
            "ttft_ms": {"p50": 1.0, "p99": 2.0},
            "inter_token_ms": {"p50": 0.5, "p99": 1.5},
            "occupancy": 0.8, "preemptions": 0}
    cell.update(over)
    return cell


def test_bench_check_serve_schema():
    from repro.tune.bench_check import check_doc
    ok = {"kind": "serve_lat", "cells": [_serve_cell()]}
    assert check_doc(ok, "B") == []
    # null percentile VALUES are fine (unmeasured distribution)
    nulls = {"kind": "serve_lat", "cells": [_serve_cell(
        ttft_ms={"p50": None, "p99": None},
        inter_token_ms={"p50": None, "p99": None})]}
    assert check_doc(nulls, "B") == []
    # missing KEYS are the violation
    missing_p99 = {"kind": "serve_lat",
                   "cells": [_serve_cell(ttft_ms={"p50": 1.0})]}
    errs = check_doc(missing_p99, "B")
    assert any("ttft_ms.p99" in e for e in errs)
    no_occ = {"kind": "serve_lat", "cells": [_serve_cell()]}
    del no_occ["cells"][0]["occupancy"]
    assert any("occupancy" in e for e in check_doc(no_occ, "B"))
    # scheduler v2: the preemption count is part of the schema
    no_preempt = {"kind": "serve_lat", "cells": [_serve_cell()]}
    del no_preempt["cells"][0]["preemptions"]
    assert any("preemptions" in e for e in check_doc(no_preempt, "B"))
    bad_preempt = {"kind": "serve_lat",
                   "cells": [_serve_cell(preemptions="two")]}
    assert any("preemptions" in e for e in check_doc(bad_preempt, "B"))
    not_dict = {"kind": "serve_lat",
                "cells": [_serve_cell(inter_token_ms=3.0)]}
    assert any("inter_token_ms" in e for e in check_doc(not_dict, "B"))
    # without the serve_lat kind the roofline contract applies instead
    legacy = {"cells": [_serve_cell()]}
    assert any("roofline" in e for e in check_doc(legacy, "B"))


def test_bench_check_cli_on_artifact(tmp_path):
    from repro.tune.bench_check import main
    doc = {"kind": "serve_lat", "cells": [_serve_cell()]}
    p = tmp_path / "BENCH_serve.json"
    p.write_text(json.dumps(doc))
    assert main([str(p)]) == 0
    doc["cells"][0].pop("occupancy")
    p.write_text(json.dumps(doc))
    assert main([str(p)]) == 1
