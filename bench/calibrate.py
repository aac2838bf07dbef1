"""Readings that the limits of `bench/limits/<cell>.json` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--seconds S] [--first-seed N] [--out FILE]

In one process (set-up is long): for each seed, the program's numbers
as a benchmark run computes them (sound runs: the lower reading); for
the first control seeds, the control in the program's place: the
reference computed with fp8 matmuls (the upper reading); for a training
cell also the planted faults of `train_cell.Feed` (half the batch, an
altered token); for a serving cell that samples, the reference drawing
the sampled requests' tokens in the program's place, with the mix's
sampler and with the nucleus or the temperature wrong (`PLANTED`).  A
state left unchanged reads 1 by construction and is not run.  Prints
one JSON line per reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from bench import compare, harness, spec  # noqa: E402


def _emit(out, **kw):
    line = json.dumps(kw)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def train(cfg, traffic, seeds, n_control, out):
    from bench import train_cell as tc
    for i, seed in enumerate(seeds):
        faults = [None] + (["half_batch", "token"] if i < n_control else [])
        ref = tc.reference_steps(cfg, traffic, seed)
        for fault in faults:
            trainer, losses, grads, change = tc.build(cfg, traffic, seed,
                                                      fault)
            del trainer
            gc.collect()
            checks = compare.train_checks(losses, grads, change, *ref, {})
            _emit(out, seed=seed, kind=fault or "program",
                  **{k: v["value"] for k, v in checks.items()})
        if i < n_control:
            low = tc.reference_steps(cfg, traffic, seed, precision="fp8")
            checks = compare.train_checks(*low, *ref, {})
            _emit(out, seed=seed, kind="control_fp8",
                  **{k: v["value"] for k, v in checks.items()})


# samplers planted in the reference in the program's place: (temperature,
# top_p) in place of the mix's; "reference" is the mix's own, which
# shows what the sampled numbers read with no error in the logits
PLANTED = {"reference": None, "top_p": (None, 1.0), "temperature": (1.0, None)}


def serve(cell, cfg, traffic, seeds, n_control, seconds, out, devices):
    from bench import serve_cell as sc
    from bench.run import RunContext
    stated = sc._stated(traffic)
    for i, seed in enumerate(seeds):
        ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=False,
                         cfg=cfg, traffic=traffic, limits={},
                         devices=devices, counter=harness.CompileCounter())
        res = sc.run(ctx)
        _emit(out, seed=seed, kind="program",
              **{k: v["value"] for k, v in res["checks"].items()},
              tokens=res["side"]["tokens_compared"],
              reference_s=res["side"]["reference_s"])
        sample = res["sample"]
        if i < n_control and sample["greedy"]:
            gaps = sc.control_gaps(cfg, traffic, seed, res["book"],
                                   sample["greedy"])
            _emit(out, seed=seed, kind="control_fp8", logit_gap=max(gaps),
                  tokens=len(gaps))
        if i < n_control and sample["sampled"]:
            for name, planted in PLANTED.items():
                sampler = tuple(s if p is None else p for s, p in
                                zip(stated, planted or (None, None)))
                key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
                readings = sc.reference_readings(
                    cfg, traffic, seed, res["book"],
                    {"sampled": sample["sampled"]}, draw=(sampler, key))
                checks = compare.serve_checks(readings, traffic, {})
                _emit(out, seed=seed, kind="planted_" + name,
                      nucleus_excess=checks["nucleus_excess"]["value"],
                      sampled_logit_z=checks["sampled_logit_z"]["value"],
                      tokens=len(readings["sampled"]["excess"]))
        del res
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_017)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    devices = harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if traffic["mode"] == "train":
        train(cfg, traffic, seeds, args.control_seeds, args.out)
    else:
        seconds = args.seconds or spec.benchmark()["run_seconds"]
        serve(cell["name"], cfg, traffic, seeds, args.control_seeds,
              seconds, args.out, devices)


if __name__ == "__main__":
    main()
