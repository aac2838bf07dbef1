"""Serving: whole benchmark runs at smoke size on the CPU, with
the look for a chip skipped and the timed path broken underneath:
`correct` has to come out false for each fault the cell can have,
against the cell's real limits.  The control (the reference in fp8 in
the program's place) has to fail the same limits."""
from __future__ import annotations

import pytest

from bench import compare, spec
from bench.tests.tiny import SEED, run_cell, shrink

CHAT = "serve-qwen2.5-3b-chat"


@pytest.mark.parametrize("fault", ["token", "top_p"])
def test_chat_fault_is_not_correct(fault, capsys):
    """An altered token fails logit_gap (and lands outside the nucleus
    too); a sampler that leaves out the nucleus fails nucleus_excess."""
    out = run_cell(CHAT, fault, capsys)
    assert out["correct"] is False, out["checks"]
    failed = {k for k, c in out["checks"].items()
              if c["value"] is not None and c["value"] > c["limit"]}
    assert {"token": "logit_gap", "top_p": "nucleus_excess"}[fault] \
        in failed, out["checks"]
    if fault == "top_p":                  # greedy requests are untouched
        assert "logit_gap" not in failed, out["checks"]
    assert list(out)[-1] == "checks"


def test_chat_control_is_not_correct():
    """The control (the reference in fp8) at each position of given
    prompts and tokens reads the gap of its own first token.  At the
    cell's widths, cut to 2 layers and a 2048-token vocabulary so that
    the CPU holds it, it has to exceed the cell's limit."""
    import copy
    import types

    import numpy as np

    from bench import serve_cell
    cfg = copy.deepcopy(spec.config("qwen2.5-3b-la"))
    cfg.update(num_hidden_layers=2, vocab_size=2048)
    traffic = shrink(cfg, spec.traffic("chat"))[1]
    rng = np.random.default_rng(SEED)
    book = types.SimpleNamespace(
        prompt={i: rng.integers(0, 2048, 32).tolist() for i in range(4)},
        tokens={i: rng.integers(0, 2048, 12).tolist() for i in range(4)})
    gaps = serve_cell.control_gaps(cfg, traffic, SEED, book, [0, 1, 2, 3])
    assert len(gaps) == 4 * 12
    assert max(gaps) > compare.limits(CHAT)["logit_gap"], max(gaps)
