"""JAX evaluation of compiled Programs.

One traced function per template answers `fires[N, C]` — whether any
violation clause fires for each (object, constraint) pair. Everything is
static-shape, elementwise + reduce over small iteration axes, so XLA fuses
the whole clause into a handful of kernels; the N axis is the data-parallel
dimension sharded across the device mesh (parallel/), and the C axis rides
along broadcast.

Tri-state semantics (undefined vs false) are carried as (value, defined)
pairs collapsed into literal "success" exactly where Rego collapses them
(body-literal boundaries); `!=`/comparison definedness mirrors OPA topdown.
The filter may over-fire — unknown-comparable kinds (arrays/objects)
compare as "maybe" — because the host re-check of firing pairs is
authoritative; it must never under-fire.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.strtab import MatchTables, StringTable
from ..parallel.mesh import shard_map_wrap as _shard_map_wrap
from .prog import (
    And,
    Arith,
    Cmp,
    Const,
    DerivedVal,
    Expr,
    K_ABSENT,
    K_FALSE,
    K_NUM,
    K_STR,
    KindIs,
    MatchLookup,
    Not,
    Or,
    OrReduce,
    OVal,
    Program,
    PVal,
    SumReduce,
    Truthy,
    Exists,
)


class Cell(NamedTuple):
    sid: Any  # int32 string ids
    num: Any  # f32 (approximate; ordering comparisons only)
    nid: Any  # int32 interned canonical-number ids (exact equality)
    kind: Any  # int8


class BPair(NamedTuple):
    """Three-valued literal result: lo = certainly succeeds, hi = possibly
    succeeds (lo implies hi). Uncertainty enters at leaves that only
    approximate Rego semantics (f32 ordering ties, composite equality) and
    must survive arbitrary negation — Not(lo, hi) = (~hi, ~lo) — so the
    final filter verdict (hi) over-fires and never under-fires; the host
    re-check of firing pairs is authoritative. Exact subtrees keep
    lo `is` hi, so XLA sees a single computation for them."""

    lo: Any
    hi: Any

    @staticmethod
    def exact(v) -> "BPair":
        return BPair(v, v)

    @property
    def is_exact(self) -> bool:
        return self.lo is self.hi


def _band(a: BPair, b: BPair) -> BPair:
    lo = jnp.logical_and(a.lo, b.lo)
    hi = lo if (a.is_exact and b.is_exact) else jnp.logical_and(a.hi, b.hi)
    return BPair(lo, hi)


def _bor(a: BPair, b: BPair) -> BPair:
    lo = jnp.logical_or(a.lo, b.lo)
    hi = lo if (a.is_exact and b.is_exact) else jnp.logical_or(a.hi, b.hi)
    return BPair(lo, hi)


def _bnot(a: BPair) -> BPair:
    hi = jnp.logical_not(a.lo)
    lo = hi if a.is_exact else jnp.logical_not(a.hi)
    return BPair(lo, hi)


def _bany(a: BPair, mask, axis: int) -> BPair:
    lo = jnp.any(jnp.logical_and(a.lo, mask), axis=axis, keepdims=True)
    hi = lo if a.is_exact else jnp.any(jnp.logical_and(a.hi, mask),
                                       axis=axis, keepdims=True)
    return BPair(lo, hi)


class EvalError(Exception):
    pass


def resolve_consts(program: Program, table: StringTable,
                   match: MatchTables) -> Program:
    """Replace string/pattern constants by interned ids / match rows.
    Must run before the match table is materialized."""
    from dataclasses import replace as dc_replace

    from ..ops.strtab import canon_num

    def fix(e):
        if isinstance(e, Const):
            if e.kind == "str":
                return Const("id", table.intern(e.value))
            if e.kind == "row":
                op, pattern = e.value
                return Const("rowidx", match.row(op, pattern))
            if e.kind == "num":
                return Const("numc",
                             (float(e.value), table.intern(canon_num(e.value))))
            return e
        if isinstance(e, Cmp):
            return Cmp(e.op, fix(e.lhs), fix(e.rhs), e.dtype)
        if isinstance(e, Arith):
            return Arith(e.op, fix(e.lhs), fix(e.rhs))
        if isinstance(e, MatchLookup):
            return MatchLookup(fix(e.row), fix(e.sid))
        if isinstance(e, Truthy):
            return Truthy(fix(e.e))
        if isinstance(e, Exists):
            return Exists(fix(e.e))
        if isinstance(e, And):
            return And(tuple(fix(x) for x in e.items))
        if isinstance(e, Or):
            return Or(tuple(fix(x) for x in e.items))
        if isinstance(e, Not):
            return Not(fix(e.e), e.local_axes)
        if isinstance(e, OrReduce):
            return OrReduce(e.axis, fix(e.e))
        if isinstance(e, SumReduce):
            return SumReduce(e.axis, fix(e.e))
        if isinstance(e, DerivedVal):
            return DerivedVal(e.col, fix(e.base))
        if isinstance(e, KindIs):
            return KindIs(fix(e.e), e.kinds)
        return e

    clauses = tuple(
        dc_replace(c, guards=tuple(
            dc_replace(g, expr=fix(g.expr)) for g in c.guards))
        for c in program.clauses
    )
    return dc_replace(program, clauses=clauses)


def _collect_axes(e: Expr, out: set) -> None:
    if isinstance(e, (OVal, PVal)):
        if e.axis:
            out.add(e.axis)
    elif isinstance(e, (Cmp, Arith)):
        _collect_axes(e.lhs, out)
        _collect_axes(e.rhs, out)
    elif isinstance(e, MatchLookup):
        _collect_axes(e.row, out)
        _collect_axes(e.sid, out)
    elif isinstance(e, (Truthy, Exists, KindIs)):
        _collect_axes(e.e, out)
    elif isinstance(e, DerivedVal):
        _collect_axes(e.base, out)
    elif isinstance(e, (And, Or)):
        for x in e.items:
            _collect_axes(x, out)
    elif isinstance(e, Not):
        _collect_axes(e.e, out)
        out.update(e.local_axes)
    elif isinstance(e, (OrReduce, SumReduce)):
        _collect_axes(e.e, out)
        out.add(e.axis)


def _sweep_layout(n: int, c: int) -> str:
    """The clause tensors' layout for a traced sweep of n rows under c
    constraints: the larger of the two takes the minor-most dim (the 128
    TPU lanes; ties keep C there). "rows" lays a clause out as
    [C, axes..., N], "constraints" as [N, axes..., C]."""
    return "rows" if n > c else "constraints"


class _ClausePlan:
    """Static layout for one clause: [N, ax0, ax1, ..., C] when `layout`
    is "constraints", [C, ax0, ax1, ..., N] when it is "rows".

    TPU tiles the two minor-most dims (8, 128): the minor-most maps to
    the 128 lanes, so whichever of N and C is larger sits there
    (_sweep_layout) and the small iteration axes live in the middle,
    where padding is cheap. An audit chunk has thousands of rows and a
    template usually one or a few constraints: with C = 1 on the lanes a
    [..., a, 1] tile fills `a` of a vreg's 1,024 slots. An admission
    batch of tens of rows under hundreds of constraints keeps C there.
    XLA's layout assignment already moves rows minor-most in most of a
    C = 1 sweep; laying them there in the program still took 4-17% off
    a PSP kind's sweep on a v5e. Putting axes minor-most instead costs
    up to 32x in both memory and VPU lanes."""

    def __init__(self, program: Program, clause, layout: str):
        axes: set = set(a.name for a in clause.axes)
        for g in clause.guards:
            _collect_axes(g.expr, axes)
        self.axis_order = sorted(axes)
        self.axpos = {a: 1 + i for i, a in enumerate(self.axis_order)}
        self.rank = 2 + len(self.axis_order)
        self.layout = layout
        if layout == "rows":
            self.cpos, self.npos = 0, self.rank - 1
        else:
            self.npos, self.cpos = 0, self.rank - 1
        self.clause = clause
        self.program = program
        self.axis_table = program.axis_table()
        self.slot_specs = {s.slot: s for s in program.obj_slots}
        self.pslot_specs = {s.slot: s for s in program.param_slots}

    # ---------------------------------------------------------- placement

    def _slot_axes(self, slot: int, is_param: bool, leaf_axis) -> list[str]:
        spec = self.pslot_specs[slot] if is_param else self.slot_specs[slot]
        seg_axes = [s.axis for s in spec.segs if s.kind == "iter"]
        if leaf_axis and (not seg_axes or seg_axes[-1] != leaf_axis):
            if len(seg_axes) > 1:
                raise EvalError("axis remap on multi-axis slot")
            seg_axes = [leaf_axis]
        return seg_axes

    def _place(self, arr, lead_pos: int, seg_axes: list) -> Any:
        """arr [L, K...] -> the clause rank: L at lead_pos, each K at
        its iteration axis' position, size 1 elsewhere."""
        pos_list = [lead_pos]
        for ax in seg_axes[:arr.ndim - 1]:
            pos = self.axpos.get(ax)
            if pos is None:
                raise EvalError(f"axis {ax} not in clause layout")
            pos_list.append(pos)
        shape = [1] * self.rank
        for pos, k in zip(pos_list, arr.shape):
            shape[pos] = k
        # the target positions must ascend for a plain reshape: N or C
        # leads the source, and seg order need not be sorted axis order
        order = [int(i) for i in np.argsort(pos_list)]
        if order != list(range(len(order))):
            arr = jnp.transpose(arr, axes=order)
        return jnp.reshape(arr, shape)

    def place_obj(self, arr, slot: int, leaf_axis) -> Any:
        """arr [N, K...] -> broadcastable, N at npos."""
        return self._place(arr, self.npos,
                           self._slot_axes(slot, False, leaf_axis))

    def place_param(self, arr, slot: int, leaf_axis) -> Any:
        """arr [C] or [C, P] -> broadcastable, C at cpos."""
        seg_axes = []
        if arr.ndim == 2:
            seg_axes = self._slot_axes(slot, True, leaf_axis)
            if not seg_axes:
                raise EvalError("param array has P dim but no axis")
            seg_axes = seg_axes[-1:]
        return self._place(arr, self.cpos, seg_axes)

    def presence(self, axis: str, feats: dict, params: dict) -> Any:
        ax = self.axis_table[axis]
        if ax.kind == "param":
            kinds = params[ax.slot]["kind"]
            return self.place_param(kinds, ax.slot, axis) != K_ABSENT
        kinds = feats[ax.slot]["kind"]
        return self.place_obj(kinds, ax.slot, axis) != K_ABSENT


def _eval_cell(plan: _ClausePlan, e: Expr, feats, params, derived) -> Cell:
    if isinstance(e, OVal):
        arrs = feats[e.slot]
        if e.f == "key":
            sid = plan.place_obj(arrs["key_id"], e.slot, e.axis)
            num = plan.place_obj(arrs["key_num"], e.slot, e.axis)
            kind = jnp.where(sid > 0, K_STR,
                             jnp.where(jnp.isnan(num), K_ABSENT, K_NUM)
                             ).astype(jnp.int8)
            return Cell(sid, num, plan.place_obj(arrs["key_nid"], e.slot,
                                                 e.axis), kind)
        return Cell(
            plan.place_obj(arrs["id"], e.slot, e.axis),
            plan.place_obj(arrs["num"], e.slot, e.axis),
            plan.place_obj(arrs["nid"], e.slot, e.axis),
            plan.place_obj(arrs["kind"], e.slot, e.axis),
        )
    if isinstance(e, PVal):
        arrs = params[e.slot]
        if e.f.startswith("row:"):
            return Cell(plan.place_param(arrs[e.f], e.slot, e.axis),
                        jnp.float32(0), jnp.int32(0), jnp.int8(0))
        if e.f == "key":
            sid = plan.place_param(arrs["key_id"], e.slot, e.axis)
            num = plan.place_param(arrs["key_num"], e.slot, e.axis)
            kind = jnp.where(sid > 0, K_STR,
                             jnp.where(jnp.isnan(num), K_ABSENT, K_NUM)
                             ).astype(jnp.int8)
            return Cell(sid, num, plan.place_param(arrs["key_nid"], e.slot,
                                                   e.axis), kind)
        return Cell(
            plan.place_param(arrs["id"], e.slot, e.axis),
            plan.place_param(arrs["num"], e.slot, e.axis),
            plan.place_param(arrs["nid"], e.slot, e.axis),
            plan.place_param(arrs["kind"], e.slot, e.axis),
        )
    if isinstance(e, Const):
        if e.kind == "id":
            return Cell(jnp.int32(e.value), jnp.float32(jnp.nan),
                        jnp.int32(0), jnp.int8(K_STR))
        if e.kind == "numc":
            num, nid = e.value
            return Cell(jnp.int32(0), jnp.float32(num), jnp.int32(nid),
                        jnp.int8(K_NUM))
        if e.kind == "bool":
            from .prog import K_TRUE
            return Cell(jnp.int32(0), jnp.float32(1.0 if e.value else 0.0),
                        jnp.int32(0),
                        jnp.int8(K_TRUE if e.value else K_FALSE))
        if e.kind == "rowidx":
            return Cell(jnp.int32(e.value), jnp.float32(0), jnp.int32(0),
                        jnp.int8(0))
        raise EvalError(f"unresolved const {e.kind}")
    if isinstance(e, DerivedVal):
        # one gather per cell: the unary function's image over the vocab,
        # indexed by the base cell's intern id (sid for strings, nid for
        # numbers; other kinds have no image -> absent)
        base = _eval_cell(plan, e.base, feats, params, derived)
        col = derived[e.col]
        is_str = base.kind == K_STR
        is_num = base.kind == K_NUM
        ix = jnp.where(is_str, base.sid, jnp.where(is_num, base.nid, 0))
        V = col["kind"].shape[0]
        ix = jnp.clip(ix, 0, V - 1)
        kind = jnp.where(jnp.logical_or(is_str, is_num),
                         col["kind"][ix], K_ABSENT).astype(jnp.int8)
        return Cell(col["sid"][ix], col["num"][ix], col["nid"][ix], kind)
    raise EvalError(f"not a value expr: {type(e).__name__}")


def _eval_num(plan: _ClausePlan, e: Expr, feats, params, table, derived):
    """-> (vlo, vhi, defined, nid-or-None): an interval [vlo, vhi]
    containing the true value.

    Cell values are points (vlo is vhi) carrying nid, the interned
    canonical-number id (exact-equality witness for f32 ties). Counts over
    uncertain inner literals widen to [sum(lo), sum(hi)]; plain counts are
    exact small ints (exact in f32)."""
    if isinstance(e, SumReduce):
        inner = _eval_bool(plan, e.e, feats, params, table, derived)
        pres = plan.presence(e.axis, feats, params)
        pos = plan.axpos[e.axis]
        slo = jnp.sum(jnp.where(jnp.logical_and(inner.lo, pres), 1.0, 0.0),
                      axis=pos, keepdims=True)
        shi = slo if inner.is_exact else jnp.sum(
            jnp.where(jnp.logical_and(inner.hi, pres), 1.0, 0.0),
            axis=pos, keepdims=True)
        return slo, shi, jnp.bool_(True), None
    if isinstance(e, OVal) and e.f in ("count", "countz"):
        arrs = feats[e.slot]
        val = plan.place_obj(arrs["count"], e.slot, None)
        if e.f == "countz":
            return val, val, jnp.bool_(True), None
        kinds = plan.place_obj(arrs["kind"], e.slot, None)
        return val, val, kinds != K_ABSENT, None
    if isinstance(e, PVal) and e.f == "count":
        arrs = params[e.slot]
        val = plan.place_param(arrs["count"], e.slot, None)
        return val, val, jnp.bool_(True), None
    if isinstance(e, Arith):
        llo, lhi, ld, _ = _eval_num(plan, e.lhs, feats, params, table, derived)
        rlo, rhi, rd, _ = _eval_num(plan, e.rhs, feats, params, table, derived)
        defined = jnp.logical_and(ld, rd)
        if e.op == "add":
            lo, hi = llo + rlo, lhi + rhi
        elif e.op == "sub":
            lo, hi = llo - rhi, lhi - rlo
        elif e.op == "mul":
            # interval product: extremes are among the endpoint products
            a, b, c, d = llo * rlo, llo * rhi, lhi * rlo, lhi * rhi
            lo = jnp.minimum(jnp.minimum(a, b), jnp.minimum(c, d))
            hi = jnp.maximum(jnp.maximum(a, b), jnp.maximum(c, d))
        else:
            raise EvalError(f"arith op {e.op}")
        # widen by the f32 rounding slack (each op contributes <=2^-24
        # relative error; 1e-5 covers deep expression chains) plus a tiny
        # absolute term so exact-zero results still straddle the true
        # value — threshold comparisons then over-fire, never under-fire
        # (the Arith docstring's contract in prog.py; host re-check exact)
        eps = jnp.float32(1e-5)
        tiny = jnp.float32(1e-30)
        lo = lo - (jnp.abs(lo) * eps + tiny)
        hi = hi + (jnp.abs(hi) * eps + tiny)
        # f32 overflow in chained ops can yield nan (inf - inf, 0 * inf),
        # which compares False on BOTH bounds — an under-fire. Scrub nan to
        # the unbounded interval; bare ±inf endpoints are already
        # conservative (lo=-inf claims nothing, hi=+inf over-fires).
        lo = jnp.where(jnp.isnan(lo), -jnp.inf, lo)
        hi = jnp.where(jnp.isnan(hi), jnp.inf, hi)
        return lo, hi, defined, None
    cell = _eval_cell(plan, e, feats, params, derived)
    return cell.num, cell.num, cell.kind == K_NUM, cell.nid


def _cell_eq(l: Cell, r: Cell):
    """(eq-ish value, defined). Arrays/objects compare as 'maybe' (True) —
    over-fire bias; host re-check is authoritative."""
    from .prog import K_ARR, K_OBJ

    defined = jnp.logical_and(l.kind != K_ABSENT, r.kind != K_ABSENT)
    same_kind = l.kind == r.kind
    str_eq = jnp.logical_and(l.kind == K_STR, l.sid == r.sid)
    num_eq = jnp.logical_and(l.kind == K_NUM, l.nid == r.nid)
    lit_eq = jnp.logical_and(same_kind,
                             jnp.logical_or(
                                 jnp.logical_or(str_eq, num_eq),
                                 jnp.logical_and(l.kind != K_STR,
                                                 l.kind != K_NUM)))
    maybe = jnp.logical_and(
        same_kind, jnp.logical_or(l.kind == K_ARR, l.kind == K_OBJ))
    return jnp.logical_or(lit_eq, maybe), defined, maybe


def _eval_bool(plan: _ClausePlan, e: Expr, feats, params, table, derived) -> BPair:
    """-> literal success BPair (bool arrays broadcastable to the clause
    rank). hi is the over-approximation the filter fires on; lo feeds
    negation so Not() can't turn over-fire into under-fire."""
    if isinstance(e, Cmp):
        if e.dtype == "auto":
            l = _eval_cell(plan, e.lhs, feats, params, derived)
            r = _eval_cell(plan, e.rhs, feats, params, derived)
            eq, defined, maybe = _cell_eq(l, r)
            if e.op == "eq":
                # eq includes maybe-equal composites; certain only without
                return BPair(
                    jnp.logical_and(defined,
                                    jnp.logical_and(eq, ~maybe)),
                    jnp.logical_and(defined, eq))
            if e.op == "ne":
                return BPair(
                    jnp.logical_and(defined, ~eq),
                    jnp.logical_and(defined, jnp.logical_or(~eq, maybe)))
            raise EvalError(f"auto cmp op {e.op}")
        lvlo, lvhi, ld, lnid = _eval_num(plan, e.lhs, feats, params, table, derived)
        rvlo, rvhi, rd, rnid = _eval_num(plan, e.rhs, feats, params, table, derived)
        defined = jnp.logical_and(ld, rd)
        # f32 carries ~24 bits of mantissa: values that differ beyond that
        # (e.g. 16777217 vs 16777216) compare equal, hiding the true
        # ordering. A "tie" = f32-equal point values whose exact canonical
        # number ids differ — the comparison outcome is then unknown, so
        # it lands in hi but not lo. nid 0 / None = computed value, exact.
        if lnid is not None and rnid is not None:
            tie = jnp.logical_and(
                lvlo == rvlo, jnp.logical_and(lnid != rnid,
                                              jnp.logical_and(lnid != 0,
                                                              rnid != 0)))
            exact = False
        else:
            tie = jnp.bool_(False)
            exact = (lvlo is lvhi) and (rvlo is rvhi)
        point = (lvlo is lvhi) and (rvlo is rvhi)
        # interval comparison: lo = certain for all values in the
        # intervals, hi = possible for some values (plus tie uncertainty)
        if e.op == "lt":
            lo, hi = lvhi < rvlo, jnp.logical_or(lvlo < rvhi, tie)
        elif e.op == "gt":
            lo, hi = lvlo > rvhi, jnp.logical_or(lvhi > rvlo, tie)
        elif e.op == "le":
            lo = jnp.logical_and(lvhi <= rvlo, ~tie)
            hi = lvlo <= rvhi
        elif e.op == "ge":
            lo = jnp.logical_and(lvlo >= rvhi, ~tie)
            hi = lvhi >= rvlo
        elif e.op == "eq":
            pts = (lvlo == rvlo) if point else jnp.logical_and(
                lvlo == lvhi, jnp.logical_and(rvlo == rvhi, lvlo == rvlo))
            lo = jnp.logical_and(pts, ~tie)
            hi = jnp.logical_and(lvlo <= rvhi, rvlo <= lvhi)  # overlap
        elif e.op == "ne":
            pts = (lvlo == rvlo) if point else jnp.logical_and(
                lvlo == lvhi, jnp.logical_and(rvlo == rvhi, lvlo == rvlo))
            lo = jnp.logical_or(lvhi < rvlo, rvhi < lvlo)  # disjoint
            hi = jnp.logical_not(jnp.logical_and(pts, ~tie))
        else:
            raise EvalError(f"cmp op {e.op}")
        lo = jnp.logical_and(defined, lo)
        hi = lo if exact else jnp.logical_and(defined, hi)
        return BPair(lo, hi)
    if isinstance(e, MatchLookup):
        # table is bit-packed [V, W] uint32 (strtab.materialize_packed):
        # gather the string's row-bitmask words (1-D gather) and test the
        # pattern row's bit — a single fused int32 AND per (obj, constraint)
        # cell, no extra broadcast dim and no 2-D fancy-index tuples.
        row = _eval_cell(plan, e.row, feats, params, derived).sid
        sv = _eval_cell(plan, e.sid, feats, params, derived)
        defined = jnp.logical_and(row >= 0, sv.kind == K_STR)
        V, W = table.shape
        r = jnp.clip(row, 0, W * 32 - 1)
        s = jnp.clip(sv.sid, 0, V - 1)
        per_string = jnp.take(table, s, axis=0)  # [..., W]
        if W == 1:
            word = per_string[..., 0]
        else:
            word_idx = (r >> 5)[..., None]
            sel = word_idx == jnp.arange(W)
            word = jnp.sum(jnp.where(sel, per_string, 0), axis=-1,
                           dtype=jnp.uint32)
        rbit = (jnp.uint32(1) << (r & 31).astype(jnp.uint32))
        hit = (word & rbit) != 0
        return BPair.exact(jnp.logical_and(defined, hit))
    if isinstance(e, Truthy):
        c = _eval_cell(plan, e.e, feats, params, derived)
        return BPair.exact(jnp.logical_and(c.kind != K_ABSENT,
                                           c.kind != K_FALSE))
    if isinstance(e, Exists):
        c = _eval_cell(plan, e.e, feats, params, derived)
        return BPair.exact(c.kind != K_ABSENT)
    if isinstance(e, KindIs):
        c = _eval_cell(plan, e.e, feats, params, derived)
        hit = None
        for k in e.kinds:
            t = c.kind == k
            hit = t if hit is None else jnp.logical_or(hit, t)
        return BPair.exact(hit if hit is not None else jnp.bool_(False))
    if isinstance(e, And):
        out = None
        for x in e.items:
            v = _eval_bool(plan, x, feats, params, table, derived)
            out = v if out is None else _band(out, v)
        return out if out is not None else BPair.exact(jnp.bool_(True))
    if isinstance(e, Or):
        out = None
        for x in e.items:
            v = _eval_bool(plan, x, feats, params, table, derived)
            out = v if out is None else _bor(out, v)
        return out if out is not None else BPair.exact(jnp.bool_(False))
    if isinstance(e, Not):
        inner = _eval_bool(plan, e.e, feats, params, table, derived)
        for ax in e.local_axes:
            pres = plan.presence(ax, feats, params)
            inner = _bany(inner, pres, plan.axpos[ax])
        return _bnot(inner)
    if isinstance(e, OrReduce):
        inner = _eval_bool(plan, e.e, feats, params, table, derived)
        pres = plan.presence(e.axis, feats, params)
        return _bany(inner, pres, plan.axpos[e.axis])
    if isinstance(e, SumReduce):
        slo, shi, _, _ = _eval_num(plan, e, feats, params, table, derived)
        lo = slo != 0
        hi = lo if shi is slo else shi != 0
        return BPair(lo, hi)
    if isinstance(e, Const):
        if e.kind == "bool":
            return BPair.exact(jnp.bool_(bool(e.value)))
        # any non-false scalar literal succeeds
        return BPair.exact(jnp.bool_(True))
    raise EvalError(f"unsupported expr {type(e).__name__}")


def _eval_clause(plan: _ClausePlan, feats, params, table, derived,
                 n: int, c: int):
    """-> bool [n, c], whatever the plan's layout."""
    pair = None
    for g in plan.clause.guards:
        v = _eval_bool(plan, g.expr, feats, params, table, derived)
        if g.negated:  # guards are pre-wrapped in Not by the compiler
            v = _bnot(v)
        pair = v if pair is None else _band(pair, v)
    if pair is None:
        pair = BPair.exact(jnp.bool_(True))
    # the filter verdict is the over-approximation: possibly-fires
    success = pair.hi
    for a in plan.clause.axes:
        success = jnp.logical_and(success,
                                  plan.presence(a.name, feats, params))
    # reduce FIRST, broadcast last: materializing the full-rank success
    # tensor would carry tiny minor dims that TPU layouts pad to (8,128)
    # tiles — reducing lets XLA fuse the whole clause into the reduction.
    # The iteration axes are the middle dims in either layout.
    if success.ndim > 2:
        success = jnp.any(success, axis=tuple(range(1, success.ndim - 1)))
    if plan.layout == "rows":
        # [C, N] is small next to the clause: transposed here, the bit
        # pack and pair decode downstream see [n, c] on every path
        return jnp.broadcast_to(success, (c, n)).T
    return jnp.broadcast_to(success, (n, c))


def _sweep_shape(feats: dict, params: dict) -> tuple[int, int]:
    """(rows, constraints) of a traced sweep: the leading dims of the
    feature and parameter leaves (1 where there are none)."""
    n = max((a.shape[0] for arrs in feats.values() for a in arrs.values()),
            default=1)
    c = max((a.shape[0] for arrs in params.values()
             for a in arrs.values()), default=1)
    return n, c


def _param_c(params: dict) -> int:
    """Leading C dim of the first param array (1 for parameterless
    programs, whose device verdicts are constraint-independent)."""
    for arrs in params.values():
        for a in arrs.values():
            return a.shape[0]
    return 1


class _EagerPairs:
    """Dispatch handle for workloads below the slab threshold: the
    monolithic packed sweep + row gather dispatch at CONSTRUCTION (so a
    multi-template audit overlaps every kind's device work); only the
    dense-small / parameter-only paths stay lazy (they are
    latency-trivial)."""

    def __init__(self, ct, feats, params, table, derived, chunk, n_true,
                 n_cons=None):
        self._ct = ct
        self._args = (feats, params, table, derived, chunk, n_true,
                      n_cons)
        self._st = None
        self._out = None
        if feats:
            n_feat = next(iter(next(iter(
                feats.values())).values())).shape[0]
            n = n_feat if n_true is None else min(n_feat, n_true)
            if n_feat > chunk:
                c = _param_c(params)
                if n_cons is not None:
                    c = min(c, n_cons)
                self._st = ct._pairs_dispatch_mono(
                    feats, params, table, derived, chunk, n, c)

    def force(self) -> None:
        """Run a lazy sweep now: its jit call (and any compile) then
        happens in the caller's scope, e.g. a no_inline_compile guard,
        not at consume time."""
        if self._st is None and self._out is None:
            feats, params, table, derived, chunk, n_true, n_cons = \
                self._args
            self._out = self._ct.fires_pairs(feats, params, table,
                                             derived, chunk=chunk,
                                             n_true=n_true, n_cons=n_cons)

    def pairs(self):
        if self._st is not None:
            yield self._ct._pairs_consume_mono(self._st)
            return
        self.force()
        yield self._out


class _SlabPairs:
    """Pending slab kernels; .pairs() syncs in dispatch order with the
    capacity-retry loop."""

    def __init__(self, ct, pend, feats, params, table, derived, chunk,
                 slab, n, c):
        self._ct = ct
        self._pend = pend
        self._args = (feats, params, table, derived, chunk, slab, n, c)

    def pairs(self):
        ct = self._ct
        feats, params, table, derived, chunk, slab, n, c = self._args
        for k, (used_pcap, dev_arr) in enumerate(self._pend):
            arr = np.asarray(dev_arr)  # sync point + single fetch
            pcount = int(arr[0, 0])
            while pcount > used_pcap:
                used_pcap = max(used_pcap,
                                1 << (pcount - 1).bit_length())
                fn2 = ct._slab_pairs_jit(chunk, slab, used_pcap)
                arr = np.asarray(fn2(feats, params, table, derived,
                                     np.int32(k * slab), np.int32(n),
                                     np.int32(c)))
                pcount = int(arr[0, 0])
            ct._rows_cap = max(ct._rows_cap,
                               (1 << (pcount - 1).bit_length())
                               if pcount > 1 else 256)
            yield _decode_pair_blocks(arr, pcount)


def _decode_pair_blocks(arr: np.ndarray, pcount: int):
    """(rows, cols) from one device pair block: the kernels decode the
    bit-packed verdicts to dense (row, constraint) index pairs ON
    DEVICE (row-major, invalid columns already masked), so the host
    does no bitmask unpacking at all — two int64 casts and a slice."""
    if pcount == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z.copy()
    body = arr[1:1 + pcount]
    return body[:, 0].astype(np.int64), body[:, 1].astype(np.int64)


def _pair_expand(packed, valid_rows, row0, c, pcap):
    """Shared device tail for every pair kernel: masked bit-packed
    verdicts [R, W] -> one [pcap+1, 2] uint32 block — header row
    carrying the true pair count, then (global row, constraint) index
    pairs in row-major order. `valid_rows` masks extraction padding /
    slab overlap; `row0` is the block's global row offset; `c` (traced)
    masks the C-bucket padding columns so library edits inside a bucket
    still hit this program.

    No scatter: a fixed-capacity nonzero over the unpacked [R, W*32]
    bit matrix (jnp.nonzero's bincount) is a scatter-add of R*W*32
    updates into pcap bins, which the TPU serializes: ~25 ms per
    81,920-row slab on a v5e, whatever the clause. Instead each row's
    popcount is prefix-summed, pair k's row is a binary search of k in
    those sums, and its column the (rank+1)-th set bit of that row's
    words."""
    r, w = packed.shape
    nbits = jnp.clip(c - 32 * jnp.arange(w, dtype=jnp.int32), 0, 32)
    wmask = jnp.where(nbits >= 32, jnp.uint32(0xFFFFFFFF),
                      (jnp.uint32(1) << nbits.astype(jnp.uint32))
                      - jnp.uint32(1))
    packed = jnp.where(valid_rows[:, None], packed & wmask[None, :],
                       jnp.uint32(0))
    per_row = jnp.sum(jax.lax.population_count(packed), axis=1,
                      dtype=jnp.int32)
    ends = jnp.cumsum(per_row)  # pairs in rows [0, i]
    pcount = ends[-1]
    k = jnp.arange(pcap, dtype=jnp.int32)
    ok = k < pcount
    lrow = jnp.minimum(jnp.searchsorted(ends, k, side="right",
                                        method="scan_unrolled"), r - 1)
    rank = k - (ends[lrow] - per_row[lrow])  # pair k's place in its row
    bits = (packed[lrow][:, :, None] >> jnp.arange(32, dtype=jnp.uint32)
            ) & jnp.uint32(1)
    seen = jnp.cumsum(bits.reshape(pcap, w * 32).astype(jnp.int32),
                      axis=1)
    pcol = jnp.sum(seen <= rank[:, None], axis=1, dtype=jnp.int32)
    prow = jnp.where(ok, row0 + lrow, 0).astype(jnp.uint32)
    pcol = jnp.where(ok, pcol, 0).astype(jnp.uint32)
    body = jnp.stack([prow, pcol], axis=1)  # [pcap, 2]
    header = jnp.zeros((1, 2), jnp.uint32)
    header = header.at[0, 0].set(pcount.astype(jnp.uint32))
    return jnp.concatenate([header, body], axis=0)


class _MeshPairs:
    """Pending mesh sweep; .pairs() syncs the single all-shard fetch and
    decodes shard row blocks in mesh order (global row-major, since the
    data axis shards the N axis into contiguous ordered blocks)."""

    def __init__(self, ct, mesh, dev, rcap, chunk, args):
        self._ct = ct
        self._mesh = mesh
        self._dev = dev
        self._rcap = rcap
        self._chunk = chunk
        self._args = args  # (feats, params, table, derived, n_valid, c)

    def pairs(self):
        for _shard, rows, cols in self.pairs_labeled():
            yield rows, cols

    def pairs_labeled(self):
        """(shard, rows, cols) per data shard — the shard index feeds
        the per-shard audit stage histograms."""
        ct = self._ct
        feats, params, table, derived, n_valid, c = self._args
        rcap = self._rcap
        arr = np.asarray(self._dev)  # sync point + single fetch
        n_shards = arr.shape[0] // (rcap + 1)
        counts = arr[:: rcap + 1, 0].astype(np.int64)
        while counts.max(initial=0) > rcap:
            # some shard overflowed its gather capacity: re-run the whole
            # sweep at the next power of two (rare; remembered below)
            rcap = max(rcap, 1 << (int(counts.max()) - 1).bit_length())
            fn = ct._mesh_pairs_jit(self._mesh, self._chunk, rcap)
            arr = np.asarray(fn(feats, params, table, derived, n_valid,
                                np.int32(c)))
            counts = arr[:: rcap + 1, 0].astype(np.int64)
        # RATCHET, like _SlabPairs does for _rows_cap: resetting to this
        # sweep's count made alternating small/large mesh sweeps re-trip
        # the overflow re-run (and its jit recompile) on every grow
        ct._rows_cap_mesh = max(ct._rows_cap_mesh, 256,
                                (1 << (int(counts.max()) - 1).bit_length())
                                if counts.max(initial=0) > 1 else 256)
        for k in range(n_shards):
            block = arr[k * (rcap + 1): (k + 1) * (rcap + 1)]
            rows, cols = _decode_pair_blocks(block, int(block[0, 0]))
            yield k, rows, cols


class _MeshSlabPairs:
    """Pending DOUBLE-BUFFERED mesh slab sweeps.

    The monolithic mesh dispatch (_MeshPairs) syncs the whole sweep in
    one fetch, so at 1M+ objects the host sits idle through the entire
    device pass and the mesh sits idle through the entire
    materialization tail. Slabbing the LOCAL row axis fixes both: each
    slab is one SPMD dispatch covering every shard's next `lslab` local
    rows, at most WINDOW slabs are in flight, and the only
    jax.block_until_ready sits at the slab boundary — while the host
    materializes slab k's firing pairs, the mesh is already sweeping
    slab k+1. Yield order is (slab, shard): blocks are NOT globally
    row-major (shard d's slab-s rows are d*n_loc + [s*lslab, ...)), so
    order-sensitive consumers reassemble by each block's first global
    row (the driver's audit consume does)."""

    WINDOW = 2  # double-buffered: one slab syncing, one in flight

    def __init__(self, ct, mesh, chunk, lslab, n_slabs, rcap, args):
        self._ct = ct
        self._mesh = mesh
        self._chunk = chunk
        self._lslab = lslab
        self._n_slabs = n_slabs
        # (feats, params, table, derived, n_valid, c)
        self._args = args
        fn = ct._mesh_slab_pairs_jit(mesh, chunk, lslab, rcap)
        # prime the pipeline NOW (dispatch is async): the audit's
        # cross-kind window consumes handles long after construction
        self._pend = [
            (s, rcap, fn(args[0], args[1], args[2], args[3],
                         np.int32(s * lslab), args[4],
                         np.int32(args[5])))
            for s in range(min(self.WINDOW, n_slabs))]
        self._next = len(self._pend)

    def pairs(self):
        for _shard, rows, cols in self.pairs_labeled():
            yield rows, cols

    def pairs_labeled(self):
        ct = self._ct
        feats, params, table, derived, n_valid, c = self._args
        lslab = self._lslab
        while self._pend:
            s, rcap, dev = self._pend.pop(0)
            if self._next < self._n_slabs:
                # keep the window full BEFORE blocking: the refill slab
                # overlaps this slab's fetch + materialization
                fn = ct._mesh_slab_pairs_jit(self._mesh, self._chunk,
                                             lslab, ct._rows_cap_mesh)
                self._pend.append(
                    (self._next, ct._rows_cap_mesh,
                     fn(feats, params, table, derived,
                        np.int32(self._next * lslab), n_valid,
                        np.int32(c))))
                self._next += 1
            jax.block_until_ready(dev)  # the slab boundary: the ONLY
            # sync point in the loop
            arr = np.asarray(dev)
            n_shards = arr.shape[0] // (rcap + 1)
            counts = arr[:: rcap + 1, 0].astype(np.int64)
            while counts.max(initial=0) > rcap:
                # a shard overflowed its gather capacity: re-run THIS
                # slab at the next power of two (rare; ratcheted below)
                rcap = max(rcap,
                           1 << (int(counts.max()) - 1).bit_length())
                fn = ct._mesh_slab_pairs_jit(self._mesh, self._chunk,
                                             lslab, rcap)
                arr = np.asarray(fn(feats, params, table, derived,
                                    np.int32(s * lslab), n_valid,
                                    np.int32(c)))
                counts = arr[:: rcap + 1, 0].astype(np.int64)
            ct._rows_cap_mesh = max(
                ct._rows_cap_mesh, 256,
                (1 << (int(counts.max()) - 1).bit_length())
                if counts.max(initial=0) > 1 else 256)
            for k in range(n_shards):
                block = arr[k * (rcap + 1): (k + 1) * (rcap + 1)]
                rows, cols = _decode_pair_blocks(block, int(block[0, 0]))
                yield k, rows, cols


class CompiledTemplate:
    """Device-evaluable filter for one template."""

    def __init__(self, program: Program, table: StringTable,
                 match: MatchTables, aot=None, kind: str = ""):
        self.table = table
        self.match = match
        self.program = resolve_consts(program, table, match)
        # one plan per clause and layout; _eval picks the layout from
        # the traced shapes (_sweep_layout)
        self.plans = {layout: [_ClausePlan(self.program, c, layout)
                               for c in self.program.clauses]
                      for layout in ("rows", "constraints")}
        # AOT program store (ir/aot.py): every jit below is wrapped so
        # compiled executables persist across processes and a warm boot
        # deserializes instead of recompiling. The fingerprint is over
        # the RESOLVED program — interned ids are embedded in the
        # constants, so vocab skew changes it and safely misses.
        from .aot import AotStore, program_fingerprint

        self.kind = kind
        self.aot = aot if aot is not None else AotStore()
        self.fingerprint = program_fingerprint(self.program, kind)
        self._fn = self._ajit("eval", (), self._eval)
        self._scan_cache: dict[int, Any] = {}
        self._pairs_cache: dict[tuple, Any] = {}
        # remembered firing-pair gather capacity (see _gather_pairs)
        self._rows_cap = 256
        # per-shard capacity for the mesh sweep (fires_pairs_mesh_dispatch)
        self._rows_cap_mesh = 256

    def _ajit(self, tag: str, static: tuple, fn):
        from .aot import AotJit

        return AotJit(fn, store=self.aot, fingerprint=self.fingerprint,
                      tag=tag, static=static, kind=self.kind)

    def preload_aot(self, mesh=None) -> dict:
        """Ingest-time background prewarm: deserialize every stored
        executable recorded for this program's fingerprint into the
        live jit wrappers, so the first sweep/batch at a remembered
        shape dispatches with ZERO lowering or compilation on-path.
        Mesh-program entries need the live mesh (skipped without one,
        or when the topology drifted). Returns programs loaded, by
        tag."""
        loaded: dict[str, int] = {}
        if not self.aot.enabled:
            return loaded
        for ent in self.aot.entries_for(self.fingerprint):
            tag, static = ent["tag"], ent["static"]
            try:
                if tag == "eval":
                    w = self._fn
                elif tag == "scan":
                    w = self._scan_jit(*static)
                elif tag == "slabp":
                    w = self._slab_pairs_jit(*static)
                elif tag == "pairsg":
                    w = self._pairs_jit(*static)
                elif tag in ("meshp", "mesh-slabp"):
                    if mesh is None or \
                            tuple(sorted(mesh.shape.items())) != static[-1]:
                        continue
                    if tag == "meshp":
                        w = self._mesh_pairs_jit(mesh, *static[:-1])
                    else:
                        w = self._mesh_slab_pairs_jit(mesh, *static[:-1])
                else:
                    # pre-pair-decode tags ("slab"/"rows"/"mesh"/
                    # "mesh-slab") produced row-bitmask blocks; their
                    # stored executables are format-incompatible — skip
                    continue
                key = self.aot.entry_key(self.fingerprint, tag, static,
                                         ent["asig"])
                if w.preload(ent["asig"], key):
                    loaded[tag] = loaded.get(tag, 0) + 1
            except Exception:  # pragma: no cover - prewarm best-effort
                continue
        return loaded

    def _eval(self, feats, params, table, derived):
        """-> bool [n, c]; runs at trace time, once per compiled
        program."""
        from ..control.metrics import report_sweep_layout

        n, c = _sweep_shape(feats, params)
        layout = _sweep_layout(n, c)
        report_sweep_layout(layout)
        out = None
        for plan in self.plans[layout]:
            v = _eval_clause(plan, feats, params, table, derived, n, c)
            out = v if out is None else jnp.logical_or(out, v)
        return out

    def fires(self, feats: dict, params: dict, match_table: np.ndarray,
              derived: Optional[dict] = None) -> np.ndarray:
        """-> bool [N, C]."""
        return np.asarray(self._fn(feats, params, match_table,
                                   derived or {}))

    def fires_chunked(self, feats: dict, params: dict,
                      match_table: np.ndarray,
                      derived: Optional[dict] = None,
                      chunk: int = 8192,
                      n_cons: Optional[int] = None) -> np.ndarray:
        """Chunk the N axis so [N, C, K...] intermediates stay bounded.

        Single dispatch: inputs live on device whole, the chunk loop is a
        lax.map inside the jitted fn (no per-chunk host→device
        transfers).

        n_cons bounds the valid constraint columns: the C axis may carry
        power-of-two bucket padding (driver._prepare_eval) so constraint
        add/remove inside a bucket re-hits the cached program; padded
        columns replicate the last real constraint and are sliced off
        here."""
        derived = derived or {}
        c = _param_c(params)
        if n_cons is not None:
            c = min(c, n_cons)
        if not feats:
            # parameter-only program: no object slots to chunk over
            return self.fires(feats, params, match_table, derived)[:, :c]
        n = next(iter(next(iter(feats.values())).values())).shape[0]
        if n <= chunk:
            return self.fires(feats, params, match_table, derived)[:, :c]
        if n % chunk:
            pad_n = ((n + chunk - 1) // chunk) * chunk
            feats = jax.tree_util.tree_map(
                lambda a: jnp.pad(a, [(0, pad_n - n)] + [(0, 0)] *
                                  (a.ndim - 1)), feats)
        out = self._fn_scan(feats, params, match_table, derived, chunk)
        # slice the bit-unpack padding back to the true C
        return np.asarray(out)[:n, :c]

    def _fn_scan(self, feats, params, match_table, derived, chunk: int):
        """Verdicts return bit-packed over C (32x smaller device→host
        transfer)."""
        packed = np.asarray(self._packed_device(feats, params, match_table,
                                                derived, chunk))
        # unpack on host (vectorized)
        bits = (packed[..., None] >> np.arange(32, dtype=np.uint32)) & 1
        return bits.reshape(packed.shape[0], -1).astype(bool)

    def _packed_device(self, feats, params, match_table, derived,
                       chunk: int):
        """Bit-packed verdicts [Npad, W] uint32, left on device."""
        return self._scan_jit(chunk)(feats, params, match_table, derived)

    def _scan_jit(self, chunk: int):
        fn = self._scan_cache.get(chunk)
        if fn is None:
            def run(feats, params, table, derived):
                def reshape(a):
                    return a.reshape((-1, chunk) + a.shape[1:])
                chunked = jax.tree_util.tree_map(reshape, feats)

                def body(ch):
                    fires = self._eval(ch, params, table, derived)  # [chunk, C]
                    c = fires.shape[-1]
                    w = (c + 31) // 32
                    pad = w * 32 - c
                    if pad:
                        fires = jnp.pad(fires, ((0, 0), (0, pad)))
                    bits = fires.reshape(fires.shape[0], w, 32)
                    weights = (jnp.uint32(1) << jnp.arange(32,
                                                           dtype=jnp.uint32))
                    return jnp.sum(
                        jnp.where(bits, weights, jnp.uint32(0)), axis=-1,
                        dtype=jnp.uint32)
                outs = jax.lax.map(body, chunked)
                return outs.reshape((-1,) + outs.shape[2:])
            fn = self._ajit("scan", (chunk,), run)
            self._scan_cache[chunk] = fn
        return fn

    # ------------------------------------------------------ sparse verdicts

    def fires_pairs(self, feats: dict, params: dict,
                    match_table: np.ndarray,
                    derived: Optional[dict] = None,
                    chunk: int = 8192,
                    n_true: Optional[int] = None,
                    n_cons: Optional[int] = None
                    ) -> tuple[np.ndarray, np.ndarray]:
        """-> (rows, cols): row-major-ordered firing (object, constraint)
        index pairs.

        Audits are ~99% rejects, so the dense [N, C] verdict tensor is
        nearly all False; extracting the firing pairs ON DEVICE
        (population count + fixed-capacity nonzero) and transferring only
        those indices moves ~10x fewer bytes than even the bit-packed
        tensor. The nonzero capacity is
        remembered from the previous sweep (steady-state audits transfer
        once); a capacity miss re-gathers at the exact count.

        n_true bounds the valid rows (feats may carry extraction bucket
        padding — empty padding objects can legitimately fire absence
        clauses, so they are masked out ON DEVICE before the count, or
        they would flood the gather capacity)."""
        derived = derived or {}
        c = _param_c(params)
        if n_cons is not None:
            c = min(c, n_cons)
        if not feats:
            fires = self.fires(feats, params, match_table, derived)
            rows, cols = np.nonzero(fires[:, :c])
            return rows.astype(np.int64), cols.astype(np.int64)
        n = next(iter(next(iter(feats.values())).values())).shape[0]
        if n_true is not None:
            n = min(n, n_true)
        if next(iter(next(iter(feats.values())).values())).shape[0] <= chunk:
            fires = self.fires(feats, params, match_table, derived)
            rows, cols = np.nonzero(fires[:n, :c])
            return rows.astype(np.int64), cols.astype(np.int64)
        st = self._pairs_dispatch_mono(feats, params, match_table, derived,
                                       chunk, n, c)
        return self._pairs_consume_mono(st)

    def _pairs_dispatch_mono(self, feats, params, match_table, derived,
                             chunk: int, n: int,
                             c: Optional[int] = None):
        """ASYNC dispatch of the monolithic packed sweep + device pair
        decode; _pairs_consume_mono syncs (with the capacity-retry
        loop)."""
        n_feat = next(iter(next(iter(feats.values())).values())).shape[0]
        if n_feat % chunk:
            pad_n = ((n_feat + chunk - 1) // chunk) * chunk
            feats = jax.tree_util.tree_map(
                lambda a: jnp.pad(a, [(0, pad_n - n_feat)] + [(0, 0)] *
                                  (a.ndim - 1)), feats)
        packed = self._packed_device(feats, params, match_table, derived,
                                     chunk)
        if c is None:
            c = _param_c(params)
        rcap = self._rows_cap
        dev = self._gather_pairs(packed, n, c, rcap)
        return (packed, n, rcap, dev, c)

    def _pairs_consume_mono(self, st):
        packed, n, rcap, dev, c = st
        arr = np.asarray(dev)  # sync
        pcount = int(arr[0, 0])
        while pcount > rcap:
            rcap = max(rcap, 1 << (pcount - 1).bit_length())
            arr = np.asarray(self._gather_pairs(packed, n, c, rcap))
            pcount = int(arr[0, 0])
        self._rows_cap = max(256, (1 << (pcount - 1).bit_length())
                             if pcount > 1 else 256)
        return _decode_pair_blocks(arr, pcount)

    def _slab_pairs_jit(self, chunk: int, slab: int, pcap: int):
        """One fused jit per (chunk, slab, pcap): clamped dynamic-slice
        of the FULL device-resident feature tree at a traced `start`,
        chunked sweep, bit-pack, and dense pair decode (_pair_expand),
        returning one [pcap+1, 2] pair block. One device dispatch + one
        fetch per slab — no per-leaf host pad/slice op storms, no scalar
        count fetches, each of which costs a device roundtrip — and the
        host receives (row, constraint) INDEX arrays, no
        bitmask unpacking."""
        key = ("slabp", chunk, slab, pcap)
        fn = self._pairs_cache.get(key)
        if fn is not None:
            return fn

        def run(feats, params, table, derived, start, n_valid, c):
            leaf = next(iter(next(iter(feats.values())).values()))
            n_feat = leaf.shape[0]  # static
            cs = jnp.minimum(start, n_feat - slab)
            sl = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, cs, slab, axis=0),
                feats)
            chunked = jax.tree_util.tree_map(
                lambda a: a.reshape((-1, chunk) + a.shape[1:]), sl)

            def body(ch):
                fires = self._eval(ch, params, table, derived)  # [chunk, C]
                cc = fires.shape[-1]
                w = (cc + 31) // 32
                pad = w * 32 - cc
                if pad:
                    fires = jnp.pad(fires, ((0, 0), (0, pad)))
                bits = fires.reshape(fires.shape[0], w, 32)
                weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
                return jnp.sum(jnp.where(bits, weights, jnp.uint32(0)),
                               axis=-1, dtype=jnp.uint32)

            packed = jax.lax.map(body, chunked)
            packed = packed.reshape((slab,) + packed.shape[2:])
            rows_global = cs + jnp.arange(slab, dtype=jnp.int32)
            # mask extraction padding (>= n_valid) AND the clamp overlap
            # (< start): overlap rows were already emitted by the
            # previous slab
            valid = (rows_global < n_valid) & (rows_global >= start)
            return _pair_expand(packed, valid, cs, c, pcap)

        fn = self._ajit("slabp", (chunk, slab, pcap), run)
        self._pairs_cache[key] = fn
        return fn

    def fires_pairs_dispatch(self, feats: dict, params: dict,
                             match_table: np.ndarray,
                             derived: Optional[dict] = None,
                             chunk: int = 8192,
                             slab: int = 32768,
                             n_true: Optional[int] = None,
                             n_cons: Optional[int] = None):
        """Dispatch every slab kernel NOW (async); the returned handle's
        .pairs() iterator syncs and decodes slab-by-slab. Callers can
        dispatch MANY templates' sweeps before consuming any — the audit
        overlaps every kind's device work with every kind's host
        materialization."""
        derived = derived or {}
        n_feat = (next(iter(next(iter(feats.values())).values())).shape[0]
                  if feats else 0)
        n = n_feat
        if n_true is not None:
            n = min(n, n_true)
        if not feats or n <= slab or n_feat < slab:
            return _EagerPairs(self, feats, params, match_table, derived,
                               chunk, n_true, n_cons)
        c = _param_c(params)
        if n_cons is not None:
            c = min(c, n_cons)
        n_slabs = (n + slab - 1) // slab
        rcap = self._rows_cap
        fn = self._slab_pairs_jit(chunk, slab, rcap)
        pend = [(rcap, fn(feats, params, match_table, derived,
                          np.int32(k * slab), np.int32(n), np.int32(c)))
                for k in range(n_slabs)]
        return _SlabPairs(self, pend, feats, params, match_table, derived,
                          chunk, slab, n, c)

    def _mesh_pairs_jit(self, mesh, chunk: int, pcap: int):
        """One fused SPMD program per (mesh, chunk, per-shard pcap):
        shard_map over the mesh's "data" axis — each device sweeps its
        contiguous N/D row block (chunked lax.map, same eval body as the
        single-device sweep), bit-packs verdicts over C, masks padding
        rows by GLOBAL row index, and decodes its local firing pairs to
        dense (row, constraint) indices at capacity pcap (_pair_expand).
        Output spec P("data") concatenates the per-shard [pcap+1, 2]
        pair blocks, so the host pays ONE fetch for the whole mesh and
        does no bit unpacking. No cross-device collective during
        evaluation: the object axis is pure data parallelism;
        aggregation happens on host from per-shard blocks (counts ride
        in each block header)."""
        key = ("meshp", id(mesh), chunk, pcap)
        fn = self._pairs_cache.get(key)
        if fn is not None:
            return fn
        from jax.sharding import PartitionSpec as P

        def local(feats_l, params, table, derived, n_valid, c):
            leaf = next(iter(next(iter(feats_l.values())).values()))
            n_loc = leaf.shape[0]  # static: N // data axis size
            chunked = jax.tree_util.tree_map(
                lambda a: a.reshape((-1, chunk) + a.shape[1:]), feats_l)

            def body(ch):
                fires = self._eval(ch, params, table, derived)  # [chunk, C]
                cc = fires.shape[-1]
                w = (cc + 31) // 32
                pad = w * 32 - cc
                if pad:
                    fires = jnp.pad(fires, ((0, 0), (0, pad)))
                bits = fires.reshape(fires.shape[0], w, 32)
                weights = (jnp.uint32(1) << jnp.arange(32,
                                                       dtype=jnp.uint32))
                return jnp.sum(jnp.where(bits, weights, jnp.uint32(0)),
                               axis=-1, dtype=jnp.uint32)

            packed = jax.lax.map(body, chunked)
            packed = packed.reshape((n_loc,) + packed.shape[2:])
            idx = jax.lax.axis_index("data")
            row0 = idx * n_loc
            rows_global = row0 + jnp.arange(n_loc, dtype=jnp.int32)
            return _pair_expand(packed, rows_global < n_valid, row0, c,
                                pcap)

        def run(feats, params, table, derived, n_valid, c):
            fspec = jax.tree_util.tree_map(
                lambda a: P("data", *([None] * (a.ndim - 1))), feats)
            rep = lambda tree: jax.tree_util.tree_map(
                lambda a: P(*([None] * a.ndim)), tree)
            return _shard_map_wrap(
                local, mesh=mesh,
                in_specs=(fspec, rep(params), rep(table), rep(derived),
                          P(), P()),
                out_specs=P("data", None),
            )(feats, params, table, derived, n_valid, c)

        fn = self._ajit(
            "meshp", (chunk, pcap, tuple(sorted(mesh.shape.items()))), run)
        self._pairs_cache[key] = fn
        return fn

    def _mesh_slab_pairs_jit(self, mesh, chunk: int, lslab: int,
                             pcap: int):
        """One fused SPMD program per (mesh, chunk, lslab, pcap): the
        slab twin of _mesh_pairs_jit — each device dynamic-slices its
        next `lslab` LOCAL rows at a traced `start` (so every slab of
        a sweep reuses ONE compiled program), sweeps/bit-packs them,
        and decodes its local firing pairs to dense (row, constraint)
        indices at capacity pcap, with global row indices stamped from
        axis_index. Out spec P("data") concatenates per-shard
        [pcap+1, 2] blocks: one dispatch + one fetch per slab for the
        whole mesh, nothing to unpack on host."""
        key = ("mesh-slabp", id(mesh), chunk, lslab, pcap)
        fn = self._pairs_cache.get(key)
        if fn is not None:
            return fn
        from jax.sharding import PartitionSpec as P

        def local(feats_l, params, table, derived, start, n_valid, c):
            leaf = next(iter(next(iter(feats_l.values())).values()))
            n_loc = leaf.shape[0]  # static: N // data axis size
            cs = jnp.minimum(start, n_loc - lslab)
            sl = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, cs, lslab,
                                                       axis=0),
                feats_l)
            chunked = jax.tree_util.tree_map(
                lambda a: a.reshape((-1, chunk) + a.shape[1:]), sl)

            def body(ch):
                fires = self._eval(ch, params, table, derived)
                cc = fires.shape[-1]
                w = (cc + 31) // 32
                pad = w * 32 - cc
                if pad:
                    fires = jnp.pad(fires, ((0, 0), (0, pad)))
                bits = fires.reshape(fires.shape[0], w, 32)
                weights = (jnp.uint32(1) << jnp.arange(32,
                                                       dtype=jnp.uint32))
                return jnp.sum(jnp.where(bits, weights, jnp.uint32(0)),
                               axis=-1, dtype=jnp.uint32)

            packed = jax.lax.map(body, chunked)
            packed = packed.reshape((lslab,) + packed.shape[2:])
            idx = jax.lax.axis_index("data")
            row0 = idx * n_loc
            loc_rows = cs + jnp.arange(lslab, dtype=jnp.int32)
            rows_global = row0 + loc_rows
            # mask extraction padding (>= n_valid) AND the clamp
            # overlap (< start): overlap rows were already emitted by
            # the previous slab
            valid = (rows_global < n_valid) & (loc_rows >= start)
            return _pair_expand(packed, valid, row0 + cs, c, pcap)

        def run(feats, params, table, derived, start, n_valid, c):
            fspec = jax.tree_util.tree_map(
                lambda a: P("data", *([None] * (a.ndim - 1))), feats)
            rep = lambda tree: jax.tree_util.tree_map(
                lambda a: P(*([None] * a.ndim)), tree)
            return _shard_map_wrap(
                local, mesh=mesh,
                in_specs=(fspec, rep(params), rep(table), rep(derived),
                          P(), P(), P()),
                out_specs=P("data", None),
            )(feats, params, table, derived, start, n_valid, c)

        fn = self._ajit(
            "mesh-slabp",
            (chunk, lslab, pcap, tuple(sorted(mesh.shape.items()))), run)
        self._pairs_cache[key] = fn
        return fn

    # the mesh slab loop engages once each shard holds at least this
    # many multiples of the chunk (below it, one dispatch is cheaper
    # than the per-slab fetch round-trips); slabs aim for ~MESH_SLABS
    # per sweep
    MESH_SLAB_MIN_CHUNKS = 8
    MESH_SLABS = 8

    def fires_pairs_mesh_dispatch(self, feats: dict, params: dict,
                                  match_table: np.ndarray, mesh,
                                  derived: Optional[dict] = None,
                                  chunk: int = 8192,
                                  n_true: Optional[int] = None,
                                  slab: Optional[int] = None,
                                  n_cons: Optional[int] = None):
        """Mesh-sharded form of fires_pairs_dispatch: dispatch the SPMD
        sweep NOW (async), return a handle whose .pairs() syncs and
        yields per-shard (rows, cols). Requires the feature N axis
        divisible by the mesh's "data" axis size (callers pad to a
        power-of-two bucket and gate on divisibility).

        Large sweeps take the double-buffered SLAB loop (_MeshSlabPairs:
        per-shard materialization overlaps the device sweep of the next
        slab, jax.block_until_ready only at slab boundaries); small
        sweeps keep the single monolithic dispatch. `slab` overrides the
        LOCAL (per-shard) slab size — must divide the per-shard row
        count and be a multiple of the chunk."""
        derived = derived or {}
        n_feat = (next(iter(next(iter(feats.values())).values())).shape[0]
                  if feats else 0)
        n_data = mesh.shape["data"]
        if not feats or n_feat % n_data:
            raise ValueError(f"N={n_feat} not shardable over data={n_data}")
        n = n_feat if n_true is None else min(n_feat, n_true)
        n_loc = n_feat // n_data
        chunk_eff = min(chunk, n_loc)
        if n_loc % chunk_eff:
            raise ValueError(f"n_loc={n_loc} not divisible by "
                             f"chunk={chunk_eff}")
        c = _param_c(params)
        if n_cons is not None:
            c = min(c, n_cons)
        lslab = slab
        if lslab is None and \
                n_loc >= self.MESH_SLAB_MIN_CHUNKS * chunk_eff:
            # power-of-two extraction buckets make this exact: aim for
            # MESH_SLABS slabs, never below one chunk each
            lslab = max(chunk_eff, n_loc // self.MESH_SLABS)
        if lslab is not None and lslab < n_loc:
            if n_loc % lslab or lslab % chunk_eff:
                raise ValueError(
                    f"slab={lslab} must divide n_loc={n_loc} and be a "
                    f"multiple of chunk={chunk_eff}")
            return _MeshSlabPairs(
                self, mesh, chunk_eff, lslab, n_loc // lslab,
                self._rows_cap_mesh,
                (feats, params, match_table, derived, np.int32(n), c))
        rcap = self._rows_cap_mesh
        fn = self._mesh_pairs_jit(mesh, chunk_eff, rcap)
        dev = fn(feats, params, match_table, derived, np.int32(n),
                 np.int32(c))
        return _MeshPairs(self, mesh, dev, rcap, chunk_eff,
                          (feats, params, match_table, derived,
                           np.int32(n), c))

    def fires_pairs_slabbed(self, feats: dict, params: dict,
                            match_table: np.ndarray,
                            derived: Optional[dict] = None,
                            chunk: int = 8192,
                            slab: int = 32768,
                            n_true: Optional[int] = None,
                            n_cons: Optional[int] = None):
        """Yield row-major (rows, cols) firing pairs per N-axis slab.
        See fires_pairs_dispatch; this is dispatch + immediate consume."""
        yield from self.fires_pairs_dispatch(
            feats, params, match_table, derived, chunk=chunk, slab=slab,
            n_true=n_true, n_cons=n_cons).pairs()

    def _gather_pairs(self, packed, n: int, c: int, pcap: int):
        """Device firing-PAIR gather: one [pcap+1, 2] uint32 block —
        header row carrying the pair count, then the (row, constraint)
        index pairs row-major (see _pair_expand).

        Audits are ~99.99% rejects, so the dense index pairs are tiny,
        the whole result is ONE device->host fetch (scalar-count-then-
        data would pay two roundtrips), and the host does no bit
        unpacking at all.
        Rows >= n are extraction padding, masked before counting; cols
        >= c are C-bucket padding, masked on device too."""
        return self._pairs_jit(pcap)(packed, np.int32(n), np.int32(c))

    def _pairs_jit(self, pcap: int):
        fn = self._pairs_cache.get(("pairsg", pcap))
        if fn is None:
            def run(packed, n, c):
                npad = packed.shape[0]
                valid = jnp.arange(npad, dtype=jnp.int32) < n
                return _pair_expand(packed, valid, jnp.int32(0), c, pcap)
            fn = self._ajit("pairsg", (pcap,), run)
            self._pairs_cache[("pairsg", pcap)] = fn
        return fn
