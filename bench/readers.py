"""Shared arithmetic of the per-layer metric readers in bench/metrics/.

A reader returns None where its run gave it nothing to read (no traced
step, no matching event), and the harness then leaves the metric out of
the result line; it never returns 0 for a share of a roofline or peak.
"""
from __future__ import annotations

import statistics

from bench import devtrace, flops


def idle_share(ctx):
    """Per cent of the traced window in which no operation ran on the
    device (busy time is averaged over the chips of the run)."""
    window = (ctx.result.get("traced") or {}).get("window_s")
    if not window or not ctx.reduced["devices"]:
        return None
    return 100.0 * (1.0 - ctx.reduced["busy_s"] / window)


def median_span(ctx, key: str):
    xs = (ctx.result.get("spans") or {}).get(key) or []
    return statistics.median(xs) if xs else None


def kernel_roofline(ctx, parts: list):
    """Per cent of the least time the chip could take for every call of
    the kernel in the trace, over the time those calls took.  `parts`
    is [(output signature, (flops, bytes) of one call)].  Where any part
    has no call in the trace (its outputs changed, or it left the path),
    there is no number: a share of the parts that remain would read as
    the whole kernel."""
    if ctx.peak is None:
        return None
    least = took = 0.0
    for signature, (fl, nb) in parts:
        events = devtrace.kernel_events(ctx.trace, signature)
        if not events:
            return None
        least += len(events) * flops.roofline_seconds(fl, nb, ctx.peak)
        took += sum(d for _, d in events)
    return 100.0 * least / took if took > 0 else None


def program_share(ctx, pattern: str, flops_per_run: float,
                  bytes_per_run: float = 0.0):
    """Per cent of the least time (FLOPs at peak, or bytes at the HBM
    bandwidth where given) over the device time of the program's runs."""
    if ctx.peak is None:
        return None
    events = devtrace.program_events(ctx.trace, pattern)
    took = sum(d for _, d in events)
    if not events or took <= 0:
        return None
    least = len(events) * flops.roofline_seconds(
        flops_per_run, bytes_per_run, ctx.peak)
    return 100.0 * least / took
