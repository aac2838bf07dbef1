"""The program's own spans and named scopes, read from a profiler trace.

The serving engine and the trainer open host spans in the profiler's
trace (`repro.obs.spans`): `engine.*` and `trainer.*`, with their
counts (`slots`, `tokens`, `step_num`) as arguments, and a name ending
in `.wait` wherever the host blocks on the device.  The model names its
parts with `jax.named_scope` (`embed`, `attention`, `mlp`, `unembed`,
`sample`, `adamw`), which reaches an HLO instruction's op name as one
component of a path ("jit(decode_fn)/jit(main)/while/body/mlp/dot").

A TPU trace's operation events carry no op name: only the instruction's
text, which starts with its name ("%fusion.163 = ...").  The op names
are in the HLO of each program, which the profiler stores in the trace's
`/host:metadata` plane ("Hlo Proto" stats, one per program, named as the
program's runs are on the "XLA Modules" line).  `hlo_op_names` decodes
them from the file's protobuf wire format, needing no generated
message classes; an operation takes the op name of its instruction in
the program whose run holds it.

`load` reads the `.xplane.pb`: the program's host spans with their
arguments (`devtrace.load` keeps only the benchmark's own spans), and
every device operation labelled with its instruction's op name, in
`devtrace.load`'s layout.  An operation whose op name cannot be told
(the trace holds no HLO for its program, or programs of its name
disagree) is labelled None, and a scope's device time is then None
too: part of it may be missing.  `of(ctx)` loads the run's trace once
per path.  The rest works on the plain lists, so it is tested on a
trace written by hand.  A program without these spans or scopes (an
older commit) gives no spans and empty op names, and the readers then
return None.

    python3 bench/progtrace.py <.xplane.pb or the directory holding it>

prints where the device's idle time went: each gap between device
operations is put down to the innermost span open at its middle, the
program's or the benchmark's; and the device time of each scope.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import statistics
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import devtrace  # noqa: E402

PREFIXES = ("engine.", "trainer.")
SCOPES = ("embed", "attention", "mlp", "unembed", "sample", "adamw")
WAIT = ".wait"

_loaded: dict = {}


def _varint(buf, i: int):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an
    int for a varint, a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _one(buf, number: int, default=None):
    for f, v in _fields(buf):
        if f == number:
            return v
    return default


def hlo_op_names(path: str) -> dict:
    """{program name: {instruction name: op name}} from the HLO the
    profiler stored in the trace.  Field numbers: XSpace.planes 1;
    XPlane.name 2, event_metadata 4 (map: key 1, value 2), stat_metadata
    5; XEventMetadata.name 2, stats 5; XStat.metadata_id 1, bytes_value
    6; HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    metadata 7; OpMetadata.op_name 2.  An instruction with no op name
    maps to ""."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1 or _text(_one(plane, 2, b"")) != "/host:metadata":
            continue
        stat_names = {}
        for g, entry in _fields(plane):
            if g == 5:
                meta = _one(entry, 2, b"")
                stat_names[_one(meta, 1, 0)] = _text(_one(meta, 2, b""))
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = _one(entry, 2, b"")
            names = {}
            for h, stat in _fields(meta):
                if h != 5 or stat_names.get(_one(stat, 1, 0)) != "Hlo Proto":
                    continue
                module = _one(_one(stat, 6, b""), 1, b"")
                for k, comp in _fields(module):
                    if k != 3:
                        continue
                    for m, ins in _fields(comp):
                        if m != 2:
                            continue
                        names[_text(_one(ins, 1, b""))] = \
                            _text(_one(_one(ins, 7, b""), 2, b""))
            if names:
                out[_text(_one(meta, 2, b""))] = names
    return out


def _candidates(hlo: dict, program: str) -> list:
    """The {instruction: op name} maps that may be the program called
    `program` ("jit_fn(123)"): its own, else those of every program
    stored under the same bare name."""
    if program in hlo:
        return [hlo[program]]
    bare = program.split("(")[0]
    return [v for k, v in hlo.items() if k.split("(")[0] == bare]


def _op_name(candidates: list, text: str):
    """The op name of the instruction whose text is an event's name,
    among a program's `_candidates`: "" when it has none, None when it
    cannot be told (no candidate, or candidates that disagree)."""
    m = devtrace._OP.match(text)
    if not candidates or m is None:
        return None if not candidates else ""
    found = {names[m.group(1)] for names in candidates
             if m.group(1) in names}
    if len(found) > 1:
        return None
    return found.pop() if found else ""


def load(path: str) -> dict:
    """{"spans": [[start_ns, dur_ns, name, args, thread]],
        "ops": [[device, start_ns, dur_ns, name, program, op name]]}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    hlo = hlo_op_names(path)
    spans, ops = [], []
    for plane in data.planes:
        if devtrace.DEVICE.match(plane.name):
            runs, events = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events]
                elif line.name == "XLA Ops":
                    events += [(e.start_ns, e.duration_ns, e.name)
                               for e in line.events]
            runs.sort()
            starts = [r[0] for r in runs]
            programs = {}
            for start, dur, name in events:
                i = bisect.bisect_right(starts, start) - 1
                program = runs[i][2] if i >= 0 and start < runs[i][1] else ""
                if program not in programs:
                    programs[program] = _candidates(hlo, program)
                ops.append([plane.name, start, dur, name, program,
                            _op_name(programs[program], name)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        spans.append([e.start_ns, e.duration_ns, e.name,
                                      dict(e.stats), line.name])
    spans.sort(key=lambda s: (s[0], -s[1]))
    return {"spans": spans, "ops": ops}


def of(ctx) -> dict:
    """The program's spans and the device ops with their op names, of
    the run's trace (empty lists when the run wrote none)."""
    path = getattr((ctx.result or {}).get("profile"), "path", None)
    if not path:
        return {"spans": [], "ops": []}
    if path not in _loaded:
        _loaded[path] = load(path)
    return _loaded[path]


def in_scope(label: str, scope: str) -> bool:
    """Whether `scope` is a component of the op-name path in the label:
    between "/" or the ends of the path, or inside the parentheses a
    transformation writes around it ("transpose(jvp(mlp))")."""
    return re.search(r'(?:^|[\s/("=])' + re.escape(scope)
                     + r'(?=$|[\s/)"])', label) is not None


def _timed(ops: list) -> list:
    """The operations that take device time of their own: loops and
    conditionals left out, since their bodies are events of their own."""
    return [op for op in ops
            if devtrace.opcode(op[3]) not in devtrace.CONTAINERS]


def unresolved_seconds(ops: list) -> dict:
    """{program: device seconds of its operations whose op name cannot
    be told}, averaged over the devices."""
    devices = max(len({op[0] for op in ops}), 1)
    out = collections.Counter()
    for op in _timed(ops):
        if op[5] is None:
            out[op[4]] += op[2] * 1e-9 / devices
    return dict(out)


def scope_seconds(ops: list, scope: str):
    """Device seconds of the operations in `scope`, averaged over the
    devices; None when no operation is in it, or when any operation's
    op name cannot be told (it might have been in it)."""
    timed = _timed(ops)
    if any(op[5] is None for op in timed):
        return None
    took = [op[2] for op in timed if in_scope(op[5], scope)]
    if not took:
        return None
    return sum(took) * 1e-9 / max(len({op[0] for op in ops}), 1)


def named(spans: list, *names: str) -> list:
    return [s for s in spans if s[2] in names]


def count(spans: list, names: tuple, arg: str) -> int:
    """The sum of argument `arg` over the spans called one of `names`."""
    return sum(int(s[3].get(arg, 0)) for s in named(spans, *names))


def self_ms(spans: list, step: str) -> list:
    """Per `step` span, in ms: its duration less the part of it that
    the `.wait` spans inside it (on its thread) cover."""
    waits = collections.defaultdict(list)
    for s in spans:
        if s[2].endswith(WAIT):
            waits[s[4]].append((s[0], s[0] + s[1]))
    out = []
    for start, dur, _, _, thread in named(spans, step):
        end = start + dur
        covered = sum(e - s for s, e in devtrace._union(
            [[max(a, start), min(b, end)] for a, b in waits[thread]
             if a < end and b > start]))
        out.append((dur - covered) * 1e-6)
    return out


def median_self_ms(spans: list, step: str):
    xs = self_ms(spans, step)
    return statistics.median(xs) if xs else None


def idle_by_span(ops: list, spans: list) -> dict:
    """Seconds of device idle time (gaps between operations, averaged
    over the devices) by the innermost span open at each gap's middle:
    `spans` is any list of [start_ns, dur_ns, name, ...]."""
    by_dev = collections.defaultdict(list)
    for op in ops:
        by_dev[op[0]].append([op[1], op[1] + op[2]])
    host = sorted([s[0], s[1], s[2]] for s in spans)
    longest = max((s[1] for s in host), default=0)
    gaps = collections.Counter()
    for ivs in by_dev.values():
        merged = devtrace._union(ivs)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps[_innermost(host, longest, (e0 + s1) / 2)] += \
                (s1 - e0) * 1e-9 / len(by_dev)
    return dict(gaps)


def _innermost(host: list, longest: float, t: float) -> str:
    best = None
    lo = bisect.bisect_left(host, [t - longest])
    hi = bisect.bisect_right(host, [t, float("inf")])
    for start, dur, name in host[lo:hi]:
        if t <= start + dur and (best is None or dur < best[0]):
            best = (dur, name)
    return best[1] if best else "outside spans"


def top_ops(ops: list, top: int = 12) -> list:
    """[short name, device seconds, op name] of the operations that took
    the most device time (loops and conditionals left out)."""
    took = collections.Counter()
    op_name = {}
    for op in _timed(ops):
        key = devtrace.short_name(op[3])
        took[key] += op[2] * 1e-9
        op_name.setdefault(key, op[5])
    devices = max(len({op[0] for op in ops}), 1)
    return [[k, v / devices, op_name[k]] for k, v in took.most_common(top)]


def summary(path: str) -> dict:
    """Idle time by span, span totals and self times, device time by
    scope and of the longest operations, of one trace."""
    prog = load(path)
    bench = devtrace.load(path)
    spans = prog["spans"]
    by_name = collections.defaultdict(list)
    for s in spans:
        by_name[s[2]].append(s[1] * 1e-6)
    device = devtrace.reduce(bench)
    steps = [n for n in ("engine.step", "trainer.step") if n in by_name]
    return {
        "busy_s": device["busy_s"],
        "device_span_s": device["device_span_s"],
        "idle_by_span_s": idle_by_span(
            bench["ops"], spans + [[a, b, n] for a, b, n in bench["host"]]),
        "spans": {n: {"count": len(v), "total_ms": sum(v),
                      "p50_ms": statistics.median(v)}
                  for n, v in sorted(by_name.items())},
        "step_self_ms_p50": {n: median_self_ms(spans, n) for n in steps},
        "scope_s": {sc: scope_seconds(prog["ops"], sc) for sc in SCOPES},
        "unresolved_s": unresolved_seconds(prog["ops"]),
        "top_ops": top_ops(prog["ops"]),
    }


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target, = glob.glob(os.path.join(target, "**", "*.xplane.pb"),
                            recursive=True)
    print(json.dumps(summary(target), indent=1, default=str))
