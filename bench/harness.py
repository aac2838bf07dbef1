"""What every run needs around the system under test: the chip check,
the compile cache, a count of compilations, the device's memory, host
spans and the profiler window."""
from __future__ import annotations

import gc
import glob
import os
import resource
import shutil
import time

from bench.spec import ROOT

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def now() -> float:
    return time.perf_counter()


class HostWatch:
    """What the host did over a window, for telling a stalled run from
    a slow one: CPU seconds of the process, context switches it was
    forced into (another process took its core), and the time and the
    longest pause of Python's garbage collector."""

    def __init__(self):
        self.gc_s = 0.0
        self.gc_max_s = 0.0
        self._t = None

    def _on_gc(self, phase, _info):
        if phase == "start":
            self._t = now()
        elif self._t is not None:
            d = now() - self._t
            self.gc_s += d
            self.gc_max_s = max(self.gc_max_s, d)
            self._t = None

    def start(self):
        self._r0 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._on_gc)

    def stop(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        r0, r1 = self._r0, resource.getrusage(resource.RUSAGE_SELF)
        return {"cpu_user_s": r1.ru_utime - r0.ru_utime,
                "cpu_sys_s": r1.ru_stime - r0.ru_stime,
                "forced_switches": r1.ru_nivcsw - r0.ru_nivcsw,
                "gc_s": self.gc_s, "gc_max_ms": self.gc_max_s * 1e3}


def timeline(steps, t_start: float, slice_s: float = 5.0) -> dict:
    """Tokens served in each `slice_s` of the window (by the end of the
    step that served them), the longest steps, and the longest host gap
    between two steps: where a slow run lost its time.  `steps` holds
    (start, end, prefill tokens, decode tokens, tokens emitted)
    per step."""
    if not steps:
        return {}
    n = int((steps[-1][1] - t_start) // slice_s) + 1
    per = [0] * n
    for _, b, p, _, e in steps:
        per[int((b - t_start) // slice_s)] += p + e
    longest = sorted(steps, key=lambda s: s[0] - s[1])[:5]
    gaps = [b2 - b1 for (_, b1, *_), (b2, *_) in zip(steps, steps[1:])]
    return {f"tokens_per_{slice_s:g}s": per,
            "longest_steps": [[round(a - t_start, 3), round((b - a) * 1e3, 1),
                               p, d] for a, b, p, d, _ in longest],
            "host_gap_max_ms": max(gaps) * 1e3 if gaps else 0.0}


def require_chips(n: int):
    """The devices of the run; raises when JAX finds no accelerator or
    fewer chips than the cell asks for.  There is no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit("no accelerator found: this benchmark measures "
                         "the chip and has no CPU fallback")
    if len(devices) < n:
        raise SystemExit(f"the cell asks for {n} chips, JAX finds "
                         f"{len(devices)}")
    return devices[:n]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache, always at `.jax_cache/` in
    the checkout (a fixed path: the path is part of what an entry is
    found by), for every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs lowered (each new jit signature, whether or not
    the persistent cache then has it) and backend compiles."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == self.LOWER:
            self.lowered += 1
        elif name == self.COMPILE:
            self.compiled += 1

    def snapshot(self) -> tuple:
        return self.lowered, self.compiled


def memory_peak(devices) -> int:
    """The highest peak_bytes_in_use over the devices of the run."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Profile:
    """The profiler over a part of the window; `path` is the .xplane.pb
    it wrote.  The directory is fixed and emptied first, so a run leaves
    one trace behind at most."""

    def __init__(self, cell: str):
        self.dir = os.path.join(TRACE_DIR, cell)
        self.path = None
        self.t0 = self.t1 = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.t0 = now()

    def stop(self):
        """Ends the traced window now; writing the trace out comes after
        and is not part of it."""
        import jax
        self.t1 = now()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        self.path = found[0] if found else None

