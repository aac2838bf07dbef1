"""The yardstick's arithmetic: FLOPs and bytes against hand counts, the
peaks table, the traffic generator, and BENCHMARK.json's own shape."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench import compare, flops, generator, spec


# -- flops ------------------------------------------------------------------

def test_la_fwd_hand_count():
    # b=1, h=2, hkv=1, n=8, d=2, chunk 4: t=2 chunks, e=3.  Per chunk and
    # query head: q k^T 4*4*2=32, scores [v,1] 4*4*3=48, q S 4*2*3=24;
    # per KV head the state update 4*2*3=24.  (2*(32+48+24) + 24) * 2
    # chunks * 2 flops = 928.  Bytes (bf16 q,k,v,o; f32 g): 8 tokens *
    # (2*2*(2*2 + 2*1) + 4*2) = 8 * 32 = 256.
    assert flops.la_fwd(1, 2, 1, 8, 2, 4) == (928.0, 256.0)


def test_la_bwd_hand_counts():
    # grad q, same shape: per chunk and query head 48 + 32 + 24, per KV
    # head 24: (2*104 + 24) * 2 * 2 = 928.  Bytes: 8 * (2*2*(2*1 + 2)
    # + 4*2*3) = 8 * 40 = 320.
    assert flops.la_bwd_dq(1, 2, 1, 8, 2, 4) == (928.0, 320.0)
    # grad k, v: g=2; per chunk g c c e=96, 3 g c c d=192, c e d=24,
    # c d d=16, g c e e=72 -> 400; * 2 chunks * 1 KV head * 2 = 1600.
    # Bytes: 8 * (2*2*(2 + 4) + 4*2*3) = 8 * 48 = 384.
    assert flops.la_bwd_dkv(1, 2, 1, 8, 2, 4) == (1600.0, 384.0)


def test_decode_kernel_hand_count():
    # b=3, h=4, hkv=2, d=2: e=3, g=2; flops 2*3*2*(6 + 12) = 216; state
    # 4*3*2*(6+3) = 216 bytes read and written; q, o 2*3*2*4*2 = 96,
    # k, v 2*3*2*2*2 = 48 -> 2*216 + 96 + 48 = 576.
    assert flops.linear_decode_fused(3, 4, 2, 2) == (216.0, 576.0)


def test_decode_step_hand_count():
    cfg = {"hidden_size": 4, "intermediate_size": 8, "vocab_size": 10,
           "num_hidden_layers": 2, "num_attention_heads": 2,
           "num_key_value_heads": 1, "hidden_act": "silu",
           "tie_word_embeddings": True}
    # hd=2; per layer q,k,v,o: 4*2*(4+2)=48, MLP 3*4*8=96 -> 144.
    # LA per token: 2 layers * 2*2*3 * (1+2) = 72.  One token, one
    # logit row: 2*2*144 + 72 + 2*4*10 = 728.
    assert flops.forward_flops(cfg, 1, 1) == 728.0
    # weights at bf16: 2*(2*(144 + 16) + 40) = 720; state 2 layers *
    # 1 slot * 1 KV head * 3*3*4 = 72, read and written.
    assert flops.decode_step(cfg, 1) == (728.0, 720.0 + 144.0)


@pytest.mark.parametrize("name", ["pythia-1.4b-la-6l", "qwen2.5-3b-la"])
def test_matmul_params_match_the_program(name):
    cfg = spec.config(name)
    mc = spec.model_config(cfg)
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    tied = cfg["tie_word_embeddings"]
    counted = cfg["num_hidden_layers"] * flops.layer_matmul_params(cfg)
    assert counted == mc.param_count() - emb * (1 if tied else 2)


def test_train_flops_per_token_pythia():
    cfg = spec.config("pythia-1.4b-la-6l")
    # 6 layers * (4*2048^2 + 2*2048*8192) + 2048*50304 = 405,012,480
    # matmul params; LA 6 * 2*128*129 * 32 = 6,340,608 per token.
    assert flops.train_flops_per_token(cfg) == 6 * 405_012_480 + 3 * 6_340_608


# -- peaks ------------------------------------------------------------------

def test_peaks_table_has_a_source_and_refuses_unknown_devices():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("cpu")


# -- traffic ----------------------------------------------------------------


def test_open_loop_is_seeded_and_keeps_its_multiset():
    t = spec.traffic("chat")
    a = generator.open_loop(t, 1000, 2 ** 33 + 1, 20.0)
    b = generator.open_loop(t, 1000, 2 ** 33 + 1, 20.0)
    c = generator.open_loop(t, 1000, 2 ** 33 + 2, 20.0)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
        [(r.due, r.prompt, r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, c))
    assert abs(a[-1].due - c[-1].due) < 1e-9      # same total of gaps
    assert sum(r.greedy for r in a) == len(a) // 4


@pytest.mark.parametrize("mix", ["chat", "longprompt"])
def test_prompts_use_only_warmed_windows(mix):
    t = spec.traffic(mix)
    inner, last = generator.window_shapes(t)
    chunk = t["engine"]["prefill_chunk"]
    warmed = [generator.windows(n, chunk)
              for n in generator.warmup_prompts(t)]
    assert {w[-1] for w in warmed} == set(last)
    assert {x for w in warmed for x in w[:-1]} == set(inner)
    if t["loop"] == "open":
        reqs = generator.open_loop(t, 100, 5, 30.0)
    else:
        loop = generator.ClosedLoop(t, 100, 5)
        reqs = [loop.next() for _ in range(40)]
    for r in reqs:
        w = generator.windows(len(r.prompt), chunk)
        assert set(w[:-1]) <= set(inner) and w[-1] in last
        assert len(r.prompt) + r.max_new - 1 <= t["engine"]["max_len"]


def test_train_batches_are_seeded_and_differ_by_step():
    t = spec.traffic("train-n8192")
    a0 = generator.train_batch(t, 50304, 2 ** 40 + 3, 0)
    assert (a0 == generator.train_batch(t, 50304, 2 ** 40 + 3, 0)).all()
    assert not (a0 == generator.train_batch(t, 50304, 2 ** 40 + 3, 1)).all()
    assert a0.shape == (1, 8192) and a0.max() < 50304


# -- sampled tokens ---------------------------------------------------------


@pytest.mark.parametrize("sampler", ["stated", "top_p", "temperature"])
def test_sampled_numbers_catch_sampler_faults(sampler):
    """Tokens drawn from logits spread as the chat cell's are (about
    N(0, 0.9) over the vocabulary), 800 rows: the stated sampler
    passes the chat cell's limits; one that leaves out the nucleus
    fails nucleus_excess, one at temperature 1 fails sampled_logit_z."""
    import jax
    import jax.numpy as jnp

    from bench import serve_cell
    traffic = spec.traffic("chat")
    stated = serve_cell._stated(traffic)
    drawn = {"stated": stated, "top_p": (stated[0], 1.0),
             "temperature": (1.0, stated[1])}[sampler]
    key = jax.random.PRNGKey(7)
    logits = 0.9 * jax.random.normal(key, (800, 4096), jnp.float32)
    toks = serve_cell._draw_fn(logits, jax.random.fold_in(key, 1), *drawn)
    r = serve_cell.token_readings(logits, toks, *stated)
    readings = {"greedy": {"gap": [0.0]},
                "sampled": {k: [float(x) for x in r[k]]
                            for k in ("excess", "dev", "var")}}
    checks = compare.serve_checks(readings, traffic,
                                  compare.limits("serve-qwen2.5-3b-chat"))
    failed = {k for k, c in checks.items() if c["value"] > c["limit"]}
    assert failed == {"stated": set(), "top_p": {"nucleus_excess"},
                      "temperature": {"sampled_logit_z"}}[sampler], checks


# -- BENCHMARK.json ---------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_names_files_and_metric_readers():
    b = spec.benchmark()
    for c in b["configs"]:
        assert NAME.match(c["name"])
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
        assert json.load(open(os.path.join(spec.ROOT, c["file"])))[
            "name"] == c["name"]
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(spec.BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(spec.BENCH, "limits",
                                           w["name"] + ".json"))
        reported = {m["name"] for m in spec.metrics_of(w["name"],
                                                       "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.metrics_of(w["name"], "per_layer")
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert callable(spec.reader(m["name"]))
        for w in m["workloads"]:
            moved = {x["name"] for x in spec.metrics_of(w, "end_to_end")}
            assert m["moves"] in moved
