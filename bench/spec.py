"""Where a cell's parts live, found by the names in BENCHMARK.json.

A cell names a configuration (`bench/configs/<config>.json`) and a
traffic mix (`bench/traffic/<traffic>.json`); a per-layer metric is read
by `bench/metrics/<metric>.py`; a configuration's `model_type` names the
module that knows its architecture (`bench/reference/<model_type>.py`).
Nothing here knows a cell or an architecture by name, so a later cell,
metric or architecture is new files and a new entry, never an edit.
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


def model_config(cfgjson: dict):
    """The repro ModelConfig that the configuration file runs: the
    fields every configuration shares, and those that the mapping of its
    architecture (`program_fields` of bench/reference/<model_type>.py)
    derives from its published keys."""
    from bench.reference.model import arch
    from repro.configs.base import LACfg, MLACfg, ModelConfig, MoECfg, \
        SSMCfg
    c = cfgjson
    att = c["attention"]
    la = LACfg(a=att["a"], b=att["b"], normalize_qk=att["normalize_qk"],
               chunk=att["chunk"], backend=att["kernel"])
    fields = {"family": "dense", **arch(c).program_fields(c)}
    for key, kind in (("moe", MoECfg), ("mla", MLACfg), ("ssm", SSMCfg)):
        if fields.get(key) is not None:
            fields[key] = kind(**fields[key])
    return ModelConfig(
        name=c["name"], attention_backend=att["kind"], la=la,
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
        param_dtype=c["precision"]["params"],
        compute_dtype=c["precision"]["compute"],
        remat=c.get("remat", True), **fields)
