"""Training: whole benchmark runs at smoke size on the CPU, with
the look for a chip skipped and the timed path broken underneath:
`correct` has to come out false for each fault the cell can have,
against the cell's real limits.  The control (the reference in fp8 in
the program's place) has to fail the same limits."""
from __future__ import annotations

import pytest

from bench import compare, spec
from bench.tests.tiny import SEED, run_cell, shrink

TRAIN = "train-pythia-1.4b-n8192"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "token"])
def test_train_fault_is_not_correct(fault, capsys):
    out = run_cell(TRAIN, fault, capsys)
    assert out["correct"] is False, out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
    assert list(out)[-1] == "checks"


def test_train_control_is_not_correct():
    from bench import train_cell as tc
    cfg, traffic = shrink(spec.config("pythia-1.4b-la-6l"),
                          spec.traffic("train-n8192"))
    ref = tc.reference_steps(cfg, traffic, SEED)
    low = tc.reference_steps(cfg, traffic, SEED, precision="fp8")
    checks = compare.train_checks(*low, *ref, compare.limits(TRAIN))
    assert not compare.correct(checks), checks
