"""Where a cell's parts live, found by the names in BENCHMARK.json.

A cell names a configuration (`bench/configs/<config>.json`) and a
traffic mix (`bench/traffic/<traffic>.json`); a per-layer metric is read
by `bench/metrics/<metric>.py`.  Nothing here knows a cell by name, so a
later cell or metric is new files and a new entry, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> dict:
    """The workload entry of BENCHMARK.json called `name`."""
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    for c in benchmark()["configs"]:
        if c["name"] == name:
            return _json(os.path.join(ROOT, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH, "traffic", name + ".json"))


def metrics_of(cell_name: str, kind: str) -> list:
    """The `end_to_end` or `per_layer` entries that this cell reports:
    those that list it, and those that list no cells at all."""
    return [m for m in benchmark()[kind]
            if cell_name in m.get("workloads", [cell_name])]


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip.  A device that is not in the
    table is an error: no number is divided by a guessed peak."""
    table = _json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def reader(metric: str):
    """The `read(ctx)` function of bench/metrics/<metric>.py."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _qwen2(c: dict) -> dict:
    if c["hidden_act"] != "silu":
        raise ValueError(f"qwen2 runs a SwiGLU MLP, not {c['hidden_act']!r}")
    return dict(num_kv_heads=c["num_key_value_heads"], qkv_bias=True,
                mlp_act="swiglu", norm="rmsnorm", parallel_residual=False,
                rope_kind="standard", rope_fraction=1.0,
                rope_theta=float(c["rope_theta"]))


def _gpt_neox(c: dict) -> dict:
    if c["hidden_act"] != "gelu_pytorch_tanh":
        raise ValueError("the program's GELU is the tanh form, not "
                         f"{c['hidden_act']!r}")
    # no biases: a departure that the configuration file lists
    return dict(num_kv_heads=c["num_attention_heads"], qkv_bias=False,
                mlp_act="gelu", norm="layernorm",
                parallel_residual=c["use_parallel_residual"],
                rope_kind="partial", rope_fraction=c["rotary_pct"],
                rope_theta=float(c["rotary_emb_base"]))


ARCHITECTURES = {"qwen2": _qwen2, "gpt_neox": _gpt_neox}


def model_config(cfgjson: dict):
    """The repro ModelConfig that the configuration file runs, derived
    from its published keys by the mapping of its `model_type`."""
    from repro.configs.base import LACfg, ModelConfig
    c = cfgjson
    att = c["attention"]
    la = LACfg(a=att["a"], b=att["b"], normalize_qk=att["normalize_qk"],
               chunk=att["chunk"], backend=att["kernel"])
    return ModelConfig(
        name=c["name"], family="dense", attention_backend=att["kind"], la=la,
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
        param_dtype=c["precision"]["params"],
        compute_dtype=c["precision"]["compute"],
        remat=c.get("remat", True), **ARCHITECTURES[c["model_type"]](c))
