"""The reduction from a profiler trace to busy time, per-op time and
idle gaps: by hand on a made-up trace, and on a small recorded one."""
from __future__ import annotations

import json
import os

import pytest

from bench import devtrace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "chat_trace.json")
DEV = "/device:TPU:0"


FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
KERNEL = ("%closed_call.2 = (f32[4,2]{1,0}, bf16[4]{0}) custom-call(f32[4,2]"
          "{1,0} %a), custom_call_target=\"tpu_custom_call\"")
LOOP = "%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b"


def test_reduce_by_hand():
    # ops (ns): [0, 10) and [5, 21) overlap -> busy 21; [30, 35) -> 5;
    # [50, 60) -> 10.  Busy 36 ns; the gap [21, 30) falls in Engine.step
    # [15, 40), the innermost span there; [35, 50) in Trainer.run only.
    # The loop around [0, 21) counts for busy time, not as an op.
    trace = {
        "ops": [[DEV, 0, 21, LOOP, "", LOOP],
                [DEV, 0, 10, FUSION, "", FUSION],
                [DEV, 5, 16, KERNEL, "", KERNEL],
                [DEV, 30, 5, FUSION, "", FUSION],
                [DEV, 50, 10, "%copy.2 = f32[8]{0} copy(f32[8]{0} %x)", "",
                 ""]],
        "programs": [[DEV, 0, 21, "jit_decode_fn(1)"],
                     [DEV, 30, 5, "jit_decode_fn(1)"],
                     [DEV, 50, 10, "jit_fn(2)"]],
        "host": [[15, 25, "Engine.step"], [0, 100, "Trainer.run"]],
    }
    r = devtrace.reduce(trace)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(36e-9)
    assert r["device_span_s"] == pytest.approx(60e-9)
    assert r["ops"]["fusion.1 fusion"] == pytest.approx((15e-9, 2))
    assert "while.3 while" not in r["ops"]
    assert r["idle_gaps"] == pytest.approx({"Engine.step": 9e-9,
                                            "Trainer.run": 15e-9})
    assert r["breakdown"]["device_ops"][0] == [
        "closed_call.2 tpu_custom_call", pytest.approx(16e-9)]
    kern = devtrace.kernel_events(trace, "(f32[4,2], bf16[4])")
    assert kern == [(DEV, pytest.approx(16e-9))]
    assert not devtrace.kernel_events(trace, "f32[8]")
    prog = devtrace.program_events(trace, r"decode_fn")
    assert sum(d for _, d in prog) == pytest.approx(26e-9)


def test_reduce_recorded_trace():
    """A slice of a chat-cell trace recorded on one TPU v5e: the decode
    program, its fused decode kernels and the host spans around it.
    Busy time is checked against a nanosecond timeline of the same ops,
    and the idle gaps must fill the rest of the device's span."""
    import numpy as np
    with open(FIXTURE) as f:
        fx = json.load(f)
    trace = fx["trace"]
    r = devtrace.reduce(trace)
    t0 = min(op[1] for op in trace["ops"])
    t1 = max(op[1] + op[2] for op in trace["ops"])
    covered = np.zeros(int(t1 - t0) + 1, bool)
    for _, start, dur, *_ in trace["ops"]:
        covered[int(start - t0):int(start - t0 + dur)] = True
    assert r["busy_s"] == pytest.approx(covered.sum() * 1e-9, rel=1e-6)
    assert sum(r["idle_gaps"].values()) == pytest.approx(
        r["device_span_s"] - r["busy_s"], rel=1e-6)
    assert set(r["idle_gaps"]) <= set(devtrace.HOST_SPANS) | {
        "outside spans"}
    assert len(devtrace.kernel_events(trace, fx["kernel_signature"])) == 6
    assert devtrace.program_events(trace, fx["program_pattern"])


def test_kernel_roofline_needs_every_part():
    """A kernel of several parts reads no number when one part has no
    call in the trace: the share of the parts left is not the kernel's."""
    import types

    from bench import readers, spec
    with open(FIXTURE) as f:
        fx = json.load(f)
    ctx = types.SimpleNamespace(trace=fx["trace"],
                                peak=spec.peaks("TPU v5 lite"))
    part = (fx["kernel_signature"], (1e9, 1e7))
    share = readers.kernel_roofline(ctx, [part])
    assert share is not None and 0 < share < 100
    gone = ("(bf16[1,2,3])", (1e9, 1e7))
    assert readers.kernel_roofline(ctx, [part, gone]) is None
