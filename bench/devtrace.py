"""From a profiler trace to the device's busy time, per-operation and
per-program device time, and the host spans that idle gaps fall in.

`load` reads the `.xplane.pb` the profiler wrote with JAX alone
(`jax.profiler.ProfileData`).  Device planes are those named
`/device:TPU:<n>`; their "XLA Ops" line holds one event per operation
run, named by the operation's whole HLO text, and their "XLA Modules"
line one per program run.  A loop or conditional is an event of its own
around the events of its body, so per-op sums leave those out.  A
Pallas kernel is a `tpu_custom_call` whose text names no kernel; it is
known by the shapes of its outputs.  Host spans are the
`TraceAnnotation`s the benchmark puts around its calls into the
program.  `reduce` works on the plain lists that `load` returns, so it
is tested on a small recorded trace (tests/fixtures).
"""
from __future__ import annotations

import bisect
import collections
import re

DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_SPANS = ("Engine.step", "Engine.submit", "Trainer.run",
              "wait_arrival")
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"^%?(\S+) = .*? ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """"fusion.3 fusion" from an HLO instruction's text; a custom call
    adds its target."""
    m = _OP.match(text)
    if not m:
        return text[:80]
    t = _TARGET.search(text)
    return f"{m.group(1)} {t.group(1) if t else m.group(2)}"


def opcode(text: str) -> str:
    m = _OP.match(text)
    return m.group(2) if m else ""


def outputs(text: str) -> str:
    """The output type of an HLO instruction's text, layouts dropped:
    "(f32[128,2,128,129], bf16[128,2,8,128])"."""
    head = text.split(" = ", 1)[1] if " = " in text else text
    depth, end = 0, 0
    for i, ch in enumerate(head):
        depth += ch in "({[" and 1 or (ch in ")}]" and -1 or 0)
        if depth == 0 and ch == " ":
            end = i
            break
    return re.sub(r"\{[^{}]*\}", "", head[:end] if end else head)


def _label(event) -> str:
    """The event's name with its string stats, for matching kernels."""
    parts = [event.name]
    for key, value in event.stats:
        if isinstance(value, str) and key in ("long_name", "hlo_op",
                                              "tf_op", "name",
                                              "hlo_module", "kernel_details"):
            parts.append(value)
    return " ".join(parts)


def _module(event) -> str:
    for key, value in event.stats:
        if key == "hlo_module":
            return str(value)
    return ""


def load(path: str) -> dict:
    """{"ops": [[device, start_ns, dur_ns, name, module, label]],
        "programs": [[device, start_ns, dur_ns, name]],
        "host": [[start_ns, dur_ns, name]]}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, programs, host = [], [], []
    for plane in data.planes:
        if DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        ops.append([plane.name, e.start_ns, e.duration_ns,
                                    e.name, _module(e), _label(e)])
                elif line.name == "XLA Modules":
                    for e in line.events:
                        programs.append([plane.name, e.start_ns,
                                         e.duration_ns, e.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append([e.start_ns, e.duration_ns, e.name])
    return {"ops": ops, "programs": programs, "host": host}


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s (mean over devices of the union of op intervals), the
    device span of the trace, per-op and per-program seconds, and the
    idle gaps between ops grouped by the host span they fall in."""
    by_dev = collections.defaultdict(list)
    for dev, start, dur, *_ in trace["ops"]:
        by_dev[dev].append([start, start + dur])
    busy, gaps = 0.0, collections.Counter()
    host = sorted(trace["host"])
    first = last = None
    for dev, ivs in by_dev.items():
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged)
        first = merged[0][0] if first is None else min(first, merged[0][0])
        last = merged[-1][1] if last is None else max(last, merged[-1][1])
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps[_host_at(host, (e0 + s1) / 2)] += (s1 - e0) / len(by_dev)
    n = max(len(by_dev), 1)
    op_s = collections.Counter()
    op_n = collections.Counter()
    for _, _, dur, name, _, _ in trace["ops"]:
        if opcode(name) in CONTAINERS:
            continue
        key = short_name(name)
        op_s[key] += dur / n
        op_n[key] += 1
    prog_s = collections.Counter()
    prog_n = collections.Counter()
    for _, _, dur, name in trace["programs"]:
        prog_s[name] += dur / n
        prog_n[name] += 1
    return {
        "devices": len(by_dev),
        "busy_s": busy / n * 1e-9,
        "device_span_s": 0.0 if first is None else (last - first) * 1e-9,
        "ops": {k: (v * 1e-9, op_n[k]) for k, v in op_s.items()},
        "programs": {k: (v * 1e-9, prog_n[k]) for k, v in prog_s.items()},
        "idle_gaps": {k: v * 1e-9 for k, v in gaps.items()},
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in op_s.most_common(top)],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps.most_common(top)],
        },
    }


def _host_at(host: list, t: float, depth: int = 8) -> str:
    """The innermost of the benchmark's spans open at time t (spans nest
    at most a few deep, so only the last few that started before t can
    hold it), or "outside spans"."""
    best = None
    i = bisect.bisect_right(host, [t, float("inf")])
    for start, dur, name in host[max(0, i - depth):i]:
        if t <= start + dur and (best is None or dur < best[0]):
            best = (dur, name)
    return best[1] if best else "outside spans"


def kernel_events(trace: dict, signature: str) -> list:
    """[(device, duration_s)] of the Pallas calls (`tpu_custom_call`)
    whose output type is `signature`, as `outputs` writes it."""
    return [(dev, dur * 1e-9) for dev, _, dur, name, _, _ in trace["ops"]
            if 'custom_call_target="tpu_custom_call"' in name
            and outputs(name) == signature]


def program_events(trace: dict, pattern: str) -> list:
    """[(device, duration_s)] of the program runs whose name matches."""
    rx = re.compile(pattern)
    return [(dev, dur * 1e-9) for dev, _, dur, name in trace["programs"]
            if rx.search(name)]

