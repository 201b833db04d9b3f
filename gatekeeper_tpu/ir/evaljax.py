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


# rows per lax.map step of a sweep: bounds the [chunk, axes..., C]
# clause intermediates
CHUNK = 8192
# a mesh shard of at least this many chunks sweeps in this many slabs
MESH_SLABS = 8
# a slab of one chunk gets room for every (row, constraint) pair its
# verdicts can hold while those pairs times its verdict words per row
# (the pair decode's [pcap, 32 W] bit matrix) stay within this: it then
# never overflows, so more firing pairs (a library edit, inventory
# churn) cost no second program, as the dense verdicts such a sweep
# returned before did not. The decode's device time grows with its
# capacity: ~2.8 ms at 16,384 pairs, ~9 ms at 65,536 on a v5e
ALL_PAIRS = 1 << 15


def _rows(feats: dict) -> int:
    """Leading (row) dim of a feature tree; 0 for a program that reads
    no object field."""
    for arrs in feats.values():
        for a in arrs.values():
            return a.shape[0]
    return 0


def _pad_rows(feats: dict, rows: int) -> dict:
    """Zero-pad every feature leaf's row axis to `rows`."""
    return jax.tree_util.tree_map(
        lambda a: jnp.pad(a, [(0, rows - a.shape[0])]
                          + [(0, 0)] * (a.ndim - 1)), feats)


def _slab_plan(n_feat: int, n_true: int, n_shards: int = 1
               ) -> tuple[int, int]:
    """(chunk, slab) of a sweep over n_feat feature rows, n_true of them
    real, split over n_shards devices (the slab then counts one shard's
    rows). The one place a sweep's slab is decided.

    One device: up to four chunks of true rows sweep as one slab of
    every padded row; above that, two slabs, so the second sweeps under
    the host materialization of the first (each further slab adds a
    fetch round-trip). A mesh shard of at least MESH_SLABS chunks sweeps
    in MESH_SLABS slabs, two in flight, so the host materializes one
    while the mesh sweeps the next; a smaller shard in one."""
    n = n_feat // n_shards
    if n <= CHUNK:
        return n, n
    if n_shards > 1:
        return CHUNK, (n // MESH_SLABS if n >= MESH_SLABS * CHUNK else n)
    if n_true <= 4 * CHUNK:
        return CHUNK, -(-n // CHUNK) * CHUNK
    half = -(-n_true // 2)
    return CHUNK, max(4 * CHUNK, -(-half // CHUNK) * CHUNK)


def _pack_chunk(fires):
    """bool [R, C] -> uint32 [R, W]: bit j of word w is column 32w + j."""
    r, c = fires.shape
    w = (c + 31) // 32
    if w * 32 > c:
        fires = jnp.pad(fires, ((0, 0), (0, w * 32 - c)))
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(jnp.where(fires.reshape(r, w, 32), weights,
                             jnp.uint32(0)), axis=-1, dtype=jnp.uint32)


def _decode_pair_blocks(arr: np.ndarray, pcount: int):
    """(rows, cols) from one device pair block: the sweep decodes the
    bit-packed verdicts to dense (row, constraint) index pairs ON
    DEVICE (row-major, invalid columns already masked), so the host
    does no bitmask unpacking at all — two int64 casts and a slice."""
    if pcount == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z.copy()
    body = arr[1:1 + pcount]
    return body[:, 0].astype(np.int64), body[:, 1].astype(np.int64)


def _pair_expand(packed, valid_rows, row0, c, pcap):
    """The sweep's device tail: masked bit-packed verdicts [R, W] -> one
    [pcap+1, 2] uint32 block — header row carrying the true pair count,
    then (global row, constraint) index pairs in row-major order.
    `valid_rows` masks extraction padding / slab overlap; `row0` is the
    block's global row offset; `c` (traced) masks the C-bucket padding
    columns so library edits inside a bucket still hit this program.

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


class _PairSweep:
    """A dispatched sweep's firing pairs, slab by slab.

    The first WINDOW slabs dispatch at construction (async), so an audit
    dispatches every kind's sweep before it consumes any; .pairs() then
    refills the window before each slab's fetch, so the host
    materializes slab k while the device sweeps slab k+1. A slab of one
    chunk small enough (ALL_PAIRS) has room for every pair it can hold.
    Otherwise a block whose pairs overflow their capacity re-runs its
    slab at the next power of two, and the template keeps the grown
    capacity (one for one device, one for a mesh), so steady-state
    sweeps fetch once per slab."""

    WINDOW = 2

    def __init__(self, ct, mesh, args, chunk, slab, n_slabs, done=()):
        self._ct, self._mesh, self._args = ct, mesh, args
        self._chunk, self._slab, self._n_slabs = chunk, slab, n_slabs
        c_pad = _param_c(args[1]) if args else 1
        most = slab * c_pad
        self._all = most if chunk == slab and \
            most * -(-c_pad // 32) <= ALL_PAIRS else 0
        self._done = list(done)
        self._pend: list = []
        self._next = 0
        while self._next < min(self.WINDOW, n_slabs):
            self._dispatch()

    def _run(self, s: int, pcap: int):
        feats, params, table, derived, n_valid, c = self._args
        fn = self._ct._sweep_jit(self._mesh, self._chunk, self._slab, pcap)
        return fn(feats, params, table, derived, np.int32(s * self._slab),
                  n_valid, c)

    def _dispatch(self) -> None:
        pcap = self._all or self._ct._pcap[self._mesh is not None]
        self._pend.append((self._next, pcap, self._run(self._next, pcap)))
        self._next += 1

    def pairs(self):
        """(shard, rows, cols) per block: firing (row, constraint) index
        pairs, row-major within the block. On a mesh, shard is the data
        shard's index and blocks come in (slab, shard) order, which is
        not globally row-major: an order-sensitive consumer reassembles
        them by first row. On one device shard is None and the blocks
        are in row order."""
        yield from self._done
        ct = self._ct
        on_mesh = self._mesh is not None
        while self._pend:
            s, pcap, dev = self._pend.pop(0)
            if self._next < self._n_slabs:
                self._dispatch()
            arr = np.asarray(dev)  # the slab boundary: sync + one fetch
            top = int(arr[:: pcap + 1, 0].max())
            while top > pcap:
                pcap = 1 << (top - 1).bit_length()
                arr = np.asarray(self._run(s, pcap))
                top = int(arr[:: pcap + 1, 0].max())
            if not self._all:
                ct._pcap[on_mesh] = max(ct._pcap[on_mesh], 256,
                                        1 << max(top - 1, 0).bit_length())
            for k in range(arr.shape[0] // (pcap + 1)):
                block = arr[k * (pcap + 1):(k + 1) * (pcap + 1)]
                rows, cols = _decode_pair_blocks(block, int(block[0, 0]))
                yield (k if on_mesh else None), rows, cols


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
        self._sweep_cache: dict[tuple, Any] = {}
        # pair capacity of a sweep block, by "on a mesh" (per shard
        # there); grown by _PairSweep, never shrunk
        self._pcap = {False: 256, True: 256}

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
                    w = self._sweep_jit(None, *static)
                elif tag == "mesh-slabp":
                    if mesh is None or \
                            tuple(sorted(mesh.shape.items())) != static[-1]:
                        continue
                    w = self._sweep_jit(mesh, *static[:-1])
                else:
                    # tags of retired programs: nothing here runs them
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

    def _packed(self, feats, params, table, derived, chunk: int):
        """Traced: bit-packed verdicts [rows, W] of a feature tree whose
        rows are a multiple of chunk, one chunk per lax.map step."""
        chunked = jax.tree_util.tree_map(
            lambda a: a.reshape((-1, chunk) + a.shape[1:]), feats)
        packed = jax.lax.map(
            lambda ch: _pack_chunk(self._eval(ch, params, table, derived)),
            chunked)
        return packed.reshape((-1,) + packed.shape[2:])

    def fires(self, feats: dict, params: dict, match_table: np.ndarray,
              derived: Optional[dict] = None) -> np.ndarray:
        """-> bool [N, C]."""
        return np.asarray(self._fn(feats, params, match_table,
                                   derived or {}))

    def fires_chunked(self, feats: dict, params: dict,
                      match_table: np.ndarray,
                      derived: Optional[dict] = None,
                      chunk: Optional[int] = None,
                      n_cons: Optional[int] = None) -> np.ndarray:
        """Dense bool [N, C] verdicts, the N axis chunked (CHUNK rows by
        default) so [N, C, K...] intermediates stay bounded.

        Single dispatch: inputs live on device whole, the chunk loop is a
        lax.map inside the jitted fn (no per-chunk host→device
        transfers), and the verdicts return bit-packed over C (32x
        smaller device→host transfer).

        n_cons bounds the valid constraint columns: the C axis may carry
        power-of-two bucket padding (driver._prepare_eval) so constraint
        add/remove inside a bucket re-hits the cached program; padded
        columns replicate the last real constraint and are sliced off
        here."""
        derived = derived or {}
        chunk = chunk or CHUNK
        c = _param_c(params)
        if n_cons is not None:
            c = min(c, n_cons)
        n = _rows(feats)
        if n <= chunk:
            # one chunk, or a parameter-only program with no rows
            return self.fires(feats, params, match_table, derived)[:, :c]
        if n % chunk:
            feats = _pad_rows(feats, -(-n // chunk) * chunk)
        packed = np.asarray(self._scan_jit(chunk)(feats, params,
                                                  match_table, derived))
        bits = (packed[..., None] >> np.arange(32, dtype=np.uint32)) & 1
        return bits.reshape(packed.shape[0], -1).astype(bool)[:n, :c]

    def _scan_jit(self, chunk: int):
        fn = self._scan_cache.get(chunk)
        if fn is None:
            def run(feats, params, table, derived):
                return self._packed(feats, params, table, derived, chunk)
            fn = self._ajit("scan", (chunk,), run)
            self._scan_cache[chunk] = fn
        return fn

    # ------------------------------------------------------ sparse verdicts

    def _sweep_jit(self, mesh, chunk: int, slab: int, pcap: int):
        """The sweep program, one per (mesh, chunk, slab, pcap): clamped
        dynamic-slice of `slab` rows of the FULL device-resident feature
        tree at a traced `start`, chunked sweep and bit-pack, masking of
        padding rows (>= n_valid) and of the clamp overlap (< start,
        already emitted by the previous slab), and the device pair decode
        (_pair_expand) into one [pcap+1, 2] block. Every slab of a sweep
        reuses the one program.

        With a mesh, the same function runs under shard_map over the
        "data" axis: each device slices its own rows, stamps global row
        indices from its axis_index, and the out spec P("data")
        concatenates the per-shard blocks, so one dispatch and one fetch
        serve a slab of the whole mesh. No collective runs: the object
        axis is pure data parallelism."""
        key = (None if mesh is None else id(mesh), chunk, slab, pcap)
        fn = self._sweep_cache.get(key)
        if fn is not None:
            return fn

        def sweep(feats, params, table, derived, start, n_valid, c, shard):
            n_rows = _rows(feats)  # static: one shard's rows on a mesh
            cs = jnp.minimum(start, n_rows - slab)
            sl = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, cs, slab, axis=0),
                feats)
            packed = self._packed(sl, params, table, derived, chunk)
            local = cs + jnp.arange(slab, dtype=jnp.int32)
            row0 = shard * n_rows
            valid = (row0 + local < n_valid) & (local >= start)
            return _pair_expand(packed, valid, row0 + cs, c, pcap)

        if mesh is None:
            def run(feats, params, table, derived, start, n_valid, c):
                return sweep(feats, params, table, derived, start, n_valid,
                             c, 0)
            fn = self._ajit("slabp", (chunk, slab, pcap), run)
        else:
            from jax.sharding import PartitionSpec as P

            def local(feats, params, table, derived, start, n_valid, c):
                return sweep(feats, params, table, derived, start, n_valid,
                             c, jax.lax.axis_index("data"))

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
                (chunk, slab, pcap, tuple(sorted(mesh.shape.items()))), run)
        self._sweep_cache[key] = fn
        return fn

    def fires_pairs_dispatch(self, feats: dict, params: dict,
                             match_table: np.ndarray,
                             derived: Optional[dict] = None,
                             n_true: Optional[int] = None,
                             n_cons: Optional[int] = None,
                             mesh=None) -> _PairSweep:
        """Dispatch a sweep's firing-pair extraction NOW (async) and
        return its handle, whose .pairs() syncs and decodes slab by slab
        (_PairSweep). Callers can dispatch MANY templates' sweeps before
        consuming any: the audit overlaps every kind's device work with
        every kind's host materialization.

        Audits are ~99% rejects, so the dense [N, C] verdicts are nearly
        all False; each slab's verdicts are decoded to (row, constraint)
        index pairs ON DEVICE and one fetch moves only those. n_true
        bounds the valid rows: feats may carry extraction bucket padding,
        and empty padding objects can fire absence clauses, so they are
        masked on device before the count. n_cons bounds the valid
        columns (C-bucket padding). With `mesh`, the feature rows split
        over its "data" axis, whose size must divide them."""
        derived = derived or {}
        c = _param_c(params)
        if n_cons is not None:
            c = min(c, n_cons)
        if not feats:
            # a program that reads no object field: one verdict row
            rows, cols = np.nonzero(
                self.fires(feats, params, match_table, derived)[:, :c])
            return _PairSweep(self, None, None, 0, 0, 0, done=[
                (None, rows.astype(np.int64), cols.astype(np.int64))])
        n_feat = _rows(feats)
        n = n_feat if n_true is None else min(n_feat, n_true)
        shards = 1 if mesh is None else mesh.shape["data"]
        if n_feat % shards:
            raise ValueError(f"N={n_feat} not shardable over data={shards}")
        chunk, slab = _slab_plan(n_feat, n, shards)
        n_loc = n_feat // shards
        if mesh is None:
            n_slabs = -(-n // slab)
            if slab > n_feat:
                feats = _pad_rows(feats, slab)
        else:
            n_slabs = n_loc // slab
            if n_loc % slab or slab % chunk:
                raise ValueError(f"n_loc={n_loc} does not split into slabs "
                                 f"of {slab} rows, chunks of {chunk}")
        return _PairSweep(self, mesh, (feats, params, match_table, derived,
                                       np.int32(n), np.int32(c)),
                          chunk, slab, n_slabs)
