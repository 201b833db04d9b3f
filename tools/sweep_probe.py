#!/usr/bin/env python
"""Time each kind's device sweep on its own, and dump its compiled program.

    python tools/sweep_probe.py --workload audit.psp-thresholds --seed 1 \
        [--reps 5] [--hlo K8sPSPSeccomp,K8sPSPHostFilesystem] \
        [--out chiprun_out/probe] [--root <benchmark root>]

Builds the benchmark cell's library from the seed, ingests it through the
public client on a `TpuDriver`, audits until every program is warm, and
records the arguments of each kind's sweep dispatch during one more audit.
Then it dispatches each kind's sweep alone, --reps times (after one
untimed call), and prints one `kind: {json}` line per kind: the median and
minimum wall of dispatch plus fetch of the pairs, the rows and the
constraints. The audit's other kinds are idle meanwhile, so on a chip the
wall is that kind's device time plus one fetch. --hlo writes the optimized
HLO of every program those kinds ran in the recorded audit to
<out>/<kind>.<tag>.hlo.txt.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

CHECKOUT = os.getcwd()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--hlo", default="")
    p.add_argument("--force-layout", default="",
                   help="kinds to time again with every clause laid out "
                        "as given by --layout")
    p.add_argument("--layout", default="constraints")
    p.add_argument("--out", default=os.path.join("chiprun_out", "probe"))
    p.add_argument("--root", default="",
                   help="a benchmark root other than the checkout's")
    args = p.parse_args(argv)
    if CHECKOUT not in sys.path:
        sys.path.insert(0, CHECKOUT)
    from benchmark import harness, spec
    from benchmark.gen import library
    from gatekeeper_tpu.client import Backend
    from gatekeeper_tpu.ir import TpuDriver
    from gatekeeper_tpu.ir.aot import AotJit
    from gatekeeper_tpu.ir import evaljax
    from gatekeeper_tpu.ir.evaljax import CompiledTemplate
    from gatekeeper_tpu.target import K8sValidationTarget

    cell = spec.resolve(args.workload, args.root or CHECKOUT)
    harness.device(cell.chips, require_chip=False)
    lib = library.build(cell.config, args.seed, root=cell.root)
    driver = TpuDriver()
    client = Backend(driver).new_client([K8sValidationTarget()])
    for t in lib["templates"]:
        client.add_template(t)
    for c in lib["constraints"]:
        client.add_constraint(c)
    for o in lib["inventory"]:
        client.add_data(o)
    t0 = time.monotonic()
    client.audit().results()
    harness.wait_compiles(driver)
    harness.say("setup", seconds=time.monotonic() - t0,
                objects=len(lib["inventory"]))

    seen, programs = {}, {}
    orig, orig_call = CompiledTemplate.fires_pairs_dispatch, AotJit.__call__

    def record(self, *a, **kw):
        seen[self.kind] = (self, a, kw)
        return orig(self, *a, **kw)

    def record_call(self, *a):
        programs[(self._kind, self._tag)] = (self, a)
        return orig_call(self, *a)

    CompiledTemplate.fires_pairs_dispatch = record
    AotJit.__call__ = record_call
    try:
        driver.drop_audit_results()
        t0 = time.monotonic()
        client.audit().results()
        audit_s = time.monotonic() - t0
    finally:
        CompiledTemplate.fires_pairs_dispatch = orig
        AotJit.__call__ = orig_call
    harness.say("audit", seconds=audit_s, kinds=sorted(seen))

    hlo = {k for k in args.hlo.split(",") if k}
    forced = {k for k in args.force_layout.split(",") if k}
    if hlo:
        os.makedirs(args.out, exist_ok=True)
    for kind in sorted(seen):
        ct, a, kw = seen[kind]
        feats, params = a[0], a[1]
        leaf = next(iter(next(iter(feats.values())).values())) \
            if feats else None
        n = int(leaf.shape[0]) if leaf is not None else 0
        c = next((int(x.shape[0]) for arrs in params.values()
                  for x in arrs.values()), 1)

        def sweep():
            pairs = 0
            for _shard, rows, _cols in ct.fires_pairs_dispatch(
                    *a, **kw).pairs():
                pairs += len(rows)
            return pairs

        def timed():
            pairs = sweep()
            walls = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                sweep()
                walls.append(time.perf_counter() - t0)
            return dict(rows=n, constraints=c, pairs=pairs,
                        median_s=statistics.median(walls),
                        min_s=min(walls), walls_s=walls)

        harness.say("kind", kind=kind, **timed())
        if kind in forced and hasattr(evaljax, "_sweep_layout"):
            rule = evaljax._sweep_layout
            evaljax._sweep_layout = lambda n, c: args.layout
            ct._sweep_cache.clear()
            fn, ct._fn = ct._fn, ct._ajit("eval", (), ct._eval)
            try:
                harness.say("forced", kind=kind, layout=args.layout,
                            **timed())
            finally:
                evaljax._sweep_layout = rule
                ct._fn = fn
                ct._sweep_cache.clear()
    for (kind, tag), (fn, a) in sorted(programs.items()):
        if kind in hlo:
            with open(os.path.join(args.out, f"{kind}.{tag}.hlo.txt"),
                      "w") as f:
                f.write(fn.lower(*a).compile().as_text())
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
