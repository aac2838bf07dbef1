"""Find the highest arrival rate an open-loop serving cell sustains.

    python3 bench/sweep.py --workload <cell> --rates 4,8,12 --seconds 20

Runs the cell's traffic at each rate in one process (the chip belongs
to one process) and prints, per rate, the tokens served per second, the
p95 time to first token and between tokens, and the queue left at the
window's close: where the queue grows through the window, the rate is
above what the system sustains.  The cell's own rate is then fixed in
its traffic file at about four fifths of the highest sustained one.
The benchmark's own runs never run this.
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

from bench import harness, spec  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5_000_000_029)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    devices = harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    from bench import serve_cell
    from bench.run import RunContext
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(spec.traffic(cell["traffic"]), rate_per_s=rate)
        ctx = RunContext(cell=cell["name"], seed=args.seed,
                         seconds=args.seconds, trace=False, cfg=cfg,
                         traffic=traffic, limits={}, devices=devices,
                         counter=harness.CompileCounter())
        res = serve_cell.run(ctx)
        print(json.dumps({"rate_per_s": rate, **res["e2e"],
                          "failed": res["failed"], **res["side"],
                          "checks": res["checks"]}), flush=True)
        del res
        gc.collect()


if __name__ == "__main__":
    main()
