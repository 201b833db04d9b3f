"""The device sweep's clause layout (ir/evaljax.py `_sweep_layout`).

A traced sweep lays its clause tensors out rows-minor, [C, axes..., N],
when it has more rows than constraints, else constraints-minor,
[N, axes..., C]. The layout is chosen from the traced shapes alone, so
the differential below drives both layouts through the same rule: one
sweep of the whole inventory against a chunked sweep whose chunk is
smaller than the constraint count. Every shipped template with a clause
program must give identical verdicts under both, with one constraint and
with MANY.
"""

from __future__ import annotations

import numpy as np
import pytest

from gatekeeper_tpu import policies
from gatekeeper_tpu.client import Backend
from gatekeeper_tpu.control.metrics import REGISTRY
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.ir.evaljax import _param_c, _sweep_layout
from gatekeeper_tpu.ir.features import extract_batch
from gatekeeper_tpu.ir.params import encode_params
from gatekeeper_tpu.parallel.workload import (
    build_eval_setup, synth_mixed_objects)
from gatekeeper_tpu.target import K8sValidationTarget

# the two inventory joins: evaluated by ir/join.py, no clause program
JOINS = {"general/uniqueingresshost", "general/uniqueserviceselector"}
SWEPT = [n for n in policies.names() if n not in JOINS]
# constraints of the constraints-minor case: more than CHUNK, fewer
# than the inventory's rows
MANY = 200
CHUNK = 64
LAYOUT_METRIC = "gatekeeper_tpu_sweep_layout_total"


@pytest.fixture(scope="module")
def lib(tmp_path_factory):
    """The benchmark configurations' constraints and inventories at a
    two-hundredth of their size, plus mixed Pods, Deployments, Ingresses
    and Services for the general templates the inventories hold no
    object of; every shipped template installed on one TpuDriver."""
    from benchmark import spec
    from benchmark.gen import library
    from benchmark.tests.tiny import make_root

    root = make_root(str(tmp_path_factory.mktemp("bench")))
    objects, constraints = synth_mixed_objects(200, seed=5), {}
    for cell in ("audit.general-thresholds", "audit.psp-thresholds"):
        built = library.build(spec.resolve(cell, root).config, 11,
                              root=spec.resolve(cell, root).root)
        objects += built["inventory"]
        for c in built["constraints"]:
            constraints.setdefault(c["kind"], c)
    target = K8sValidationTarget()
    reviews = [target.handle_review(o)[1] for o in objects]
    driver = TpuDriver()
    client = Backend(driver).new_client([target])
    for name in policies.names():
        client.add_template(policies.load(name))
    return driver, reviews, constraints


def _variants(params: dict, k: int) -> list[dict]:
    """k parameter sets: the constraint's own first, then that set with
    one top-level key dropped, or one list parameter cut to a prefix or
    rotated, in turn, so neighbouring constraint columns differ."""
    keys = sorted(params)
    out = [params]
    for i in range(k - 1):
        v = dict(params)
        if keys:
            key, step = keys[i % len(keys)], i // len(keys)
            val = params[key]
            if not isinstance(val, list) or not val or step % 3 == 0:
                v.pop(key)
            elif step % 3 == 1:
                v[key] = val[:step % len(val)]
            else:
                r = step % len(val)
                v[key] = val[r:] + val[:r]
        out.append(v)
    return out


def _layout_counts() -> dict:
    ent = REGISTRY.snapshot((LAYOUT_METRIC,)).get(LAYOUT_METRIC, {})
    vals = {tuple(k): v for k, v in ent.get("values", [])}
    return {layout: vals.get((layout,), 0.0)
            for layout in ("rows", "constraints")}


def test_every_shipped_template_but_the_joins_has_a_clause_program(lib):
    driver, _, _ = lib
    for name in policies.names():
        compiled = driver.compiled_for(policies.kind_of(name))
        assert (compiled is None) == (name in JOINS), name


@pytest.mark.parametrize("n_cons", [1, MANY], ids=["one", "many"])
@pytest.mark.parametrize("name", SWEPT)
def test_both_layouts_give_identical_fires(lib, name, n_cons):
    driver, reviews, constraints = lib
    kind = policies.kind_of(name)
    ct = driver.compiled_for(kind)
    spec = constraints[kind].get("spec") or {}
    feats, _, _ = extract_batch(ct.program, driver.strtab, reviews)
    params = encode_params(ct.program,
                           _variants(spec.get("parameters") or {}, n_cons),
                           driver.strtab, driver.match_tables)
    derived = driver._derived_arrays(kind, ct)
    table = driver.match_tables.materialize_packed()
    n = len(reviews)
    n_feat = next(iter(next(iter(feats.values())).values())).shape[0]
    # a program that reads no parameter sweeps one column for them all
    c = _param_c(params)
    assert c == (n_cons if ct.program.param_slots else 1)
    # whole inventory in one sweep: rows outnumber constraints
    assert _sweep_layout(n_feat, c) == "rows"
    rows_minor = ct.fires(feats, params, table, derived)[:n]
    # chunks of CHUNK rows under MANY constraints, else of 1 row:
    # constraints fill the lanes at least as well
    chunk = CHUNK if c > CHUNK else 1
    assert _sweep_layout(chunk, c) == "constraints"
    cons_minor = ct.fires_chunked(feats, params, table, derived,
                                  chunk=chunk)[:n]
    assert rows_minor.shape == cons_minor.shape == (n, c)
    np.testing.assert_array_equal(rows_minor, cons_minor)


def test_layout_rule_puts_the_larger_axis_on_the_lanes():
    # an audit chunk under one constraint: rows
    assert _sweep_layout(8192, 1) == "rows"
    # an admission batch under 256 constraints: constraints
    assert _sweep_layout(64, 256) == "constraints"
    # a tie keeps constraints minor-most
    assert _sweep_layout(128, 128) == "constraints"


def test_layout_counter_counts_each_traced_program_once():
    _, ct, feats, params, table, derived, _, _ = build_eval_setup(
        300, 1, seed=3)
    before = _layout_counts()
    ct.fires_chunked(feats, params, table, derived, chunk=256)
    ct.fires_chunked(feats, params, table, derived, chunk=256)
    mid = _layout_counts()
    # one scan program over 256-row chunks under 1 constraint
    assert mid["rows"] - before["rows"] == 1
    assert mid["constraints"] == before["constraints"]

    _, ct, feats, params, table, derived, _, _ = build_eval_setup(
        64, 256, seed=4)
    ct.fires(feats, params, table, derived)
    ct.fires(feats, params, table, derived)
    after = _layout_counts()
    # one program of a 64-row batch under 256 constraints
    assert after["constraints"] - mid["constraints"] == 1
    assert after["rows"] == mid["rows"]
