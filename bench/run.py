"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in BENCHMARK.json, sets it up (weights from the
seed, every program of the window compiled), measures for `--seconds`,
checks what the timed path produced against the plain reference, and
prints one JSON object as the last line of standard output.  With
`--trace 0` its metrics are the cell's end-to-end metrics; with
`--trace 1` a part of the window runs under the profiler and the
metrics are the cell's per-layer ones.  Each number compared for
`correct` is printed beside its limit, last on standard error and last
in the result line.  There is no CPU fallback: without an accelerator,
or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import compare, devtrace, harness, spec  # noqa: E402


@dataclasses.dataclass
class RunContext:
    cell: str
    seed: int
    seconds: float
    trace: bool
    cfg: dict
    traffic: dict
    limits: dict
    devices: list
    counter: object


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric reader gets."""
    cfg: dict
    traffic: dict
    peak: dict
    result: dict
    trace: dict          # devtrace.load(): ops, programs, host spans
    reduced: dict        # devtrace.reduce()


def _log(**kw):
    print(json.dumps(kw), file=sys.stderr, flush=True)


def main(argv=None, devices=None, fault=None, shrink=None) -> dict:
    """`devices`, `fault` and `shrink` are for the benchmark's own tests:
    given devices skip the look for a chip, `fault` plants a fault in
    the timed path, `shrink(cfg, traffic)` returns a smaller cell."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    if shrink is not None:
        cfg, traffic = shrink(cfg, traffic)
    if devices is None:
        devices = harness.require_chips(cell["chips"])
        harness.enable_compile_cache()
    kind = devices[0].device_kind
    peak = spec.peaks(kind) if devices[0].platform != "cpu" else None
    ctx = RunContext(cell=cell["name"], seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace), cfg=cfg,
                     traffic=traffic, limits=compare.limits(cell["name"]),
                     devices=devices, counter=harness.CompileCounter())
    if traffic["mode"] == "train":
        from bench import train_cell as mode
    else:
        from bench import serve_cell as mode
    res = mode.run(ctx, fault=fault)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": compare.correct(res["checks"]),
           "attempted": res["attempted"], "failed": res["failed"]}
    if ctx.trace:
        trace = devtrace.load(res["profile"].path) \
            if res["profile"] and res["profile"].path else \
            {"ops": [], "programs": [], "host": []}
        reduced = devtrace.reduce(trace)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = res["traced"]["window_s"]
        rctx = ReadContext(cfg=cfg, traffic=traffic, peak=peak, result=res,
                           trace=trace, reduced=reduced)
        metrics = {}
        for m in spec.metrics_of(cell["name"], "per_layer"):
            value = spec.reader(m["name"])(rctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = reduced["breakdown"]
        _log(trace_programs={k: v for k, v in sorted(
            reduced["programs"].items(), key=lambda kv: -kv[1][0])[:10]},
            idle_gaps=reduced["idle_gaps"])
    else:
        units = {m["name"]: m["unit"]
                 for m in spec.metrics_of(cell["name"], "end_to_end")}
        values = dict(res["e2e"], setup_s=res["setup_s"])
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in values.items() if k in units}
        out["device"] = device
    _log(cell=cell["name"], seed=args.seed, setup_s=res["setup_s"],
         window_s=res["window_s"],
         compiles_in_window=dict(zip(("lowered", "compiled"),
                                     res["compiles_in_window"])),
         peak_bytes_in_use=res["memory_peak_bytes"], **res["side"])
    out["checks"] = res["checks"]
    for name, c in res["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except Exception:                     # noqa: BLE001 — no result line
        import traceback
        traceback.print_exc()
        sys.exit(1)
