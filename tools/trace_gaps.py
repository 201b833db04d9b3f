#!/usr/bin/env python
"""Where the device waited, and what it ran, in a profiler trace.

Reduces one `.xplane.pb` (a `/debug/profile` window, or
`utils.profiling.device_trace` around a few audits) to two tables:

* the device's idle gaps, each put down to the innermost program span
  (`gk/<phase>`, utils/profiling.PhaseTimers; `gk/audit` is the root
  around Client.audit()) that holds the gap's middle on the trace's
  clock, else to "outside gk/ spans";
* the device's time by program: its XLA modules are named `jit_<tag>`
  after their AotJit tag (ir/aot.py), so `jit_slabp`, `jit_join`, ...

The window is the trace's extent: the first to the last of the device
operations and `gk/` spans. With several devices, times are averaged
over them.

    python -m tools.trace_gaps <log_dir or file.xplane.pb>
"""

from __future__ import annotations

import glob
import os
import re
import sys

from gatekeeper_tpu.utils.profiling import SPAN_PREFIX

OUTSIDE = "outside gk/ spans"
# an accelerator's plane (not the runtime's CUSTOM planes beside it), and
# its lines of executed operations and programs
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(devices: dict, spans: list) -> dict:
    """devices: {device: {"ops": [(start_ns, end_ns)], "programs":
    [(name, start_ns, end_ns)]}}; spans: [(name, start_ns, end_ns)] of
    the `gk/` host spans. -> window_s, busy_s, idle_gaps and programs
    ({name: seconds}, per device on average)."""
    if not devices:
        raise ValueError("the trace holds no device plane (/device:TPU:n)")
    edges = [t for d in devices.values() for s, e in d["ops"]
             for t in (s, e)] + [t for _, s, e in spans for t in (s, e)]
    if not edges:
        raise ValueError("the trace holds no device operation and no "
                         "gk/ span")
    w0, w1 = min(edges), max(edges)
    n_dev = len(devices)
    busy = 0.0
    gaps: dict = {}
    programs: dict = {}
    for dev in devices.values():
        merged = merge(dev["ops"])
        busy += sum(e - s for s, e in merged)
        bounds = [w0] + [t for m in merged for t in m] + [w1]
        for g0, g1 in zip(bounds[::2], bounds[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            inside = [(e - s, n) for n, s, e in spans if s <= mid < e]
            what = min(inside)[1] if inside else OUTSIDE
            gaps[what] = gaps.get(what, 0.0) + (g1 - g0) / 1e9 / n_dev
        for name, s, e in dev["programs"]:
            programs[name] = programs.get(name, 0.0) + (e - s) / 1e9 / n_dev
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy / 1e9 / n_dev,
            "idle_gaps": gaps, "programs": programs}


def program_name(event_name: str) -> str:
    """'jit_slabp' from a module event's name ('jit_slabp(1234...)')."""
    return event_name.split("(")[0]


def read_xplane(path: str) -> tuple:
    """(devices, spans) of one .xplane.pb, as reduce_events takes them."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "programs": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] += [(e.start_ns, e.end_ns)
                                   for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["programs"] += [(program_name(e.name), e.start_ns,
                                         e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return devices, spans


def find_xplane(path: str) -> str:
    """The newest .xplane.pb at or under `path`."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def table(r: dict) -> str:
    """The reduction as two Markdown tables."""
    w = r["window_s"]
    idle = w - r["busy_s"]
    out = [f"window {w:.6f} s, device busy {r['busy_s']:.6f} s, "
           f"idle {idle:.6f} s ({100 * idle / w:.1f}%)" if w > 0 else
           "empty window", "",
           "| idle gap under | s | share of idle |", "| --- | --- | --- |"]
    for name, s in sorted(r["idle_gaps"].items(), key=lambda kv: -kv[1]):
        share = 100 * s / idle if idle > 0 else 0.0
        out.append(f"| `{name}` | {s:.6f} | {share:.1f}% |")
    out += ["", "| program | device s | share of busy |",
            "| --- | --- | --- |"]
    for name, s in sorted(r["programs"].items(), key=lambda kv: -kv[1]):
        share = 100 * s / r["busy_s"] if r["busy_s"] > 0 else 0.0
        out.append(f"| `{name}` | {s:.6f} | {share:.1f}% |")
    return "\n".join(out)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    print(table(reduce_events(*read_xplane(find_xplane(argv[0])))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
