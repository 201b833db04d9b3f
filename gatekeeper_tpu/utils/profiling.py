"""Profiling + compiler introspection.

The reference exposes pprof endpoints through controller-runtime; the
TPU-native analogs are (a) the XLA program itself — dump the HLO of any
compiled template to see exactly what the device executes — and (b)
jax.profiler traces viewable in TensorBoard/Perfetto for device
timelines. Host-side audit phases get a lightweight timer registry that
feeds the metrics exposition; while a profiler session records, each
phase is also a host span `gk/<phase>` in the same trace as the device
operations, on the same clock.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Optional

# host spans of the program in a profiler trace: "gk/<phase>", and the
# root "gk/audit" around Client.audit()
SPAN_PREFIX = "gk/"

_NO_SPAN = contextlib.nullcontext()


def span(name: str, **args):
    """The host span `gk/<name>` (with `args` as its arguments) while a
    profiler session records; a no-op context otherwise. Never imports
    jax: a process that has not (the HTTP frontends) gets the no-op."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    annotation = jax.profiler.TraceAnnotation
    if not annotation.is_enabled():
        return _NO_SPAN
    return annotation(SPAN_PREFIX + name, **args)


def compiled_hlo(ct, feats, params, table, derived=None,
                 stage: str = "hlo") -> str:
    """The compiled device program for one template's dense sweep.
    stage: "jaxpr" | "hlo" (StableHLO text) | "optimized" (post-XLA)."""
    import jax

    args = (feats, params, table, derived or {})
    if stage == "jaxpr":
        return str(jax.make_jaxpr(ct._eval)(*args))
    # lower through the template's own jit wrapper when it has one (an
    # AotJit — ir/aot.py — exposes .lower), so the rendered program is
    # the exact one the AOT store persists/serves; plain jax.jit is the
    # fallback for bare evaluators
    fn = getattr(ct, "_fn", None)
    lowered = (fn.lower(*args) if fn is not None and hasattr(fn, "lower")
               else jax.jit(ct._eval).lower(*args))
    if stage == "optimized":
        return lowered.compile().as_text()
    return lowered.as_text()


@contextlib.contextmanager
def device_trace(log_dir: str):
    """jax.profiler trace (TensorBoard/Perfetto) around a block:

        with device_trace("/tmp/gk-trace"):
            client.audit()

    The Python tracer stays off (on a v5e it tripled an audit's host
    time); host spans at level 1 are the TraceAnnotations, so the
    program's `gk/` phase spans are recorded. `python -m
    tools.trace_gaps <log_dir>` reduces the result."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class PhaseTimers:
    """Named wall-clock phase accumulators (audit: encode/device_sweep/
    materialize/...), exposed via control.metrics + the trace layer.

    The driver's audit internals time their phases into the process-
    global timers() instance; the audit manager snapshots before/after a
    sweep and diffs, turning the per-sweep phase durations into trace
    spans and per-stage histograms — the attribution PAPER.md's
    per-package stats reporters provide in the reference line.

    Phases do not nest on one thread: a phase opened inside another
    suspends it until it closes, so each instant of a thread is charged
    to at most one phase, and the phases of one audit sum to at most its
    wall time."""

    def __init__(self):
        self._lock = threading.Lock()
        # per thread: [name, start of its current stretch] of the phase
        # open on that thread, or None
        self._open = threading.local()
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}

    @contextlib.contextmanager
    def phase(self, name: str, **args):
        """Time a block as phase `name`; while a profiler records, the
        block is also the span `gk/<name>` with `args` as arguments."""
        outer = getattr(self._open, "top", None)
        t0 = time.monotonic()
        if outer is not None:
            self.add(outer[0], t0 - outer[1], n=0)
        me = [name, t0]
        self._open.top = me
        try:
            with span(name, **args):
                yield
        finally:
            t1 = time.monotonic()
            self.add(name, t1 - me[1])
            self._open.top = outer
            if outer is not None:
                outer[1] = t1

    def add(self, name: str, seconds: float, n: int = 1) -> None:
        """Accumulate an externally-timed interval (the status writer
        times each write on its own thread)."""
        with self._lock:
            self.totals[name] = self.totals.get(name, 0.0) + seconds
            self.counts[name] = self.counts.get(name, 0) + n

    def snapshot(self) -> dict[str, tuple[float, int]]:
        with self._lock:
            return {k: (self.totals[k], self.counts[k])
                    for k in self.totals}

    @staticmethod
    def diff(before: dict, after: dict) -> dict[str, float]:
        """Per-phase seconds accumulated between two snapshots."""
        out = {}
        for name, (total, _n) in after.items():
            delta = total - before.get(name, (0.0, 0))[0]
            if delta > 1e-9:
                out[name] = delta
        return out


_timers: Optional[PhaseTimers] = None


def timers() -> PhaseTimers:
    global _timers
    if _timers is None:
        _timers = PhaseTimers()
    return _timers
