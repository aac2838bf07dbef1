"""Cells at smoke size, for the benchmark's CPU tests."""
from __future__ import annotations

import copy

from bench.reference.model import arch


def tiny(cfg: dict) -> dict:
    """A configuration at smoke widths: every key that the reference and
    the program read, shrunk alike, with those that its architecture's
    `smoke_keys(cfg)` adds."""
    cfg = copy.deepcopy(cfg)
    small = {"hidden_size": 64, "intermediate_size": 128,
             "num_attention_heads": 4, "num_hidden_layers": 2,
             "vocab_size": 256}
    if "num_key_value_heads" in cfg:
        small["num_key_value_heads"] = 2
    a = arch(cfg)
    if hasattr(a, "smoke_keys"):
        small.update(a.smoke_keys(cfg))
    cfg.update(small)
    cfg["attention"] = dict(cfg["attention"], chunk=16, kernel="xla")
    cfg["precision"] = dict(cfg["precision"], compute="float32")
    cfg["remat"] = False
    return cfg


def shrink(cfg: dict, traffic: dict) -> tuple:
    """A cell's configuration and traffic at smoke size."""
    t = copy.deepcopy(traffic)
    if t["mode"] == "train":
        t.update(seq_len=32, trace_steps=2)
        return tiny(cfg), t
    t["engine"] = dict(t["engine"], slots=4, prefill_chunk=32, max_len=256)
    if t["loop"] == "open":
        t["rate_per_s"] = 20.0
        t["prompt"] = dict(t["prompt"], median=24, min=16, max=32,
                           multiple=16)
        t["output"] = dict(t["output"], median=6, min=2, max=12)
    else:
        t["prompt"] = dict(t["prompt"], min=32, max=64, multiple=32)
        t["clients"] = 4
    t.update(preroll_s=0.5, trace_seconds=0.5)
    return tiny(cfg), t


SEED = 2 ** 33 + 17


def run_cell(cell: str, fault, capsys) -> dict:
    """A whole run of the cell at smoke size on the CPU, with `fault`
    planted; checks the shape of what it printed and returns its result."""
    import jax

    from bench import run
    out = run.main(["--workload", cell, "--seed", str(SEED),
                    "--seconds", "0.5"],
                   devices=jax.devices(), fault=fault, shrink=shrink)
    printed = capsys.readouterr()
    assert printed.out.strip().splitlines()[-1].startswith('{"correct": ')
    assert printed.err.strip().splitlines()[-1].startswith("check ")
    return out
