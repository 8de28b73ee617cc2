"""Zone-tiled clustered warm evaluator — the device aggregation fast path.

The generic warm path (jax_eval's scan over stacked blocks) spends its time in
per-row masked reductions: XLA-CPU devectorizes a reduction whose input is a
select or a widening cast, and TPU scatter is off the table entirely.  This
module removes per-row masking from the hot loop with a classic columnar
storage layout (the reference has no equivalent inside a region scan — TiKV's
coprocessor filters row-by-row, `src/coprocessor/endpoint.rs`; the layout here
plays the role TiFlash's rough index / Parquet page statistics play in the
columnar siblings):

* rows are PERMUTED so each group-by slot's rows are contiguous (cluster by
  the stable dictionary codes), padded per run to a tile multiple, and
  secondary-sorted inside each run by a range-predicate column;
* referenced columns are pinned NARROWED (int8/int16/int32 chosen from the
  actual value range) with per-tile min/max zone statistics kept host-side;
* each query classifies every tile against its selection conjuncts using
  interval arithmetic: **full** (provably all rows pass), **empty** (provably
  none), or **partial**;
* full tiles aggregate with PURE same-dtype staged tile reductions — no mask,
  no select, no widening in the reduction, so XLA emits clean SIMD loops (and
  on TPU, clean VPU/MXU reductions with no scatter);
* partial tiles (predicate boundaries, tiles containing NULLs in referenced
  columns, pad tiles) are gathered whole — a contiguous DMA-friendly gather —
  and evaluated row-by-row through the same RPN machinery as the generic
  path, over a power-of-two tile-count bucket so shapes stay static;
* per-group results merge through tiny T-sized segment ops (T = n/TILE_ROWS).

Scope: zone layouts are built and keyed PER CACHE (one region image), so
they serve the per-request warm path and the same-region fused batch
(jax_eval.run_batch_cached probes them first).  The read scheduler's
cross-region batches (scheduler.py → jax_eval.launch_xregion_cached) and
the mesh-sharded warm launcher (parallel/mesh.py launch_xregion_sharded,
docs/mesh_serving.md) bypass zones: a cross-region/sharded program needs
one shared geometry across images whose cluster permutations and tile
statistics differ per region — batching zone-tiled execution across
regions (or tiling it per device shard) would need a shared tile
classification pass and is future work; the scheduler's padding-budget
shed keeps the bypass bounded to batches that actually profit from
stacking.

Exactness contract: REAL (f64) aggregate arguments are rejected (summation
order would differ from the CPU oracle beyond the last ulp); everything else
is int64-lane arithmetic, so responses stay byte-identical to the CPU
pipeline, including group output order (tracked as the minimum original row
index among each group's active rows — the CPU hash-agg's insertion order,
matching jax_eval's `_fused_step` semantics).  One carve-out shared with the
generic device path: var_pop's sum-of-squares accumulates in f64, exact
while Σx² < 2^53 and last-ulp-exempt beyond (the documented REAL caveat).

Layouts are built once per (group columns, sort column) signature and pinned
on the ColumnBlockCache; queries whose partial fraction exceeds
``PARTIAL_FALLBACK`` hand back to the generic path (the layout buys nothing
when most tiles straddle a predicate boundary).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..analysis.sanitizer import note_blocking
from ..util import trace
from . import observatory as _obs
from .datatypes import EvalType
from .rpn import RpnExpression, eval_rpn

TILE_ROWS = 4096
PARTIAL_FALLBACK = 0.6  # > this fraction of partial tiles → generic path
_RIDX_INF = np.int32(2**31 - 1)

_ZONE_AGG_OPS = {"count", "sum", "avg", "min", "max", "var_pop"}
# null-preserving kernels: non-null operands can never produce a NULL result,
# so an expression's null mask is exactly the OR of its operands' — which lets
# has-null tiles be forced partial instead of tracked per row on full tiles
_NULLSAFE_OPS = {
    "plus", "minus", "multiply", "unary_minus", "abs",
    "bit_and", "bit_or", "bit_xor", "bit_neg",
    "lt", "le", "gt", "ge", "eq", "ne",
    "and", "or", "not", "is_not_null",
}


def _narrow_dtype(lo: int, hi: int):
    """Smallest signed int dtype that holds [lo, hi] (and 0, the null fill)."""
    lo, hi = min(lo, 0), max(hi, 0)
    for dt in (np.int8, np.int16, np.int32):
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return dt
    return np.int64


def _stage_split(dtype, max_abs: int):
    """(inner_k, inner_dtype) for a two-stage tile sum that never overflows
    and never widens inside a vector reduction.  inner sums K elements in a
    dtype just wide enough; the outer reduce widens K× fewer elements."""
    for k in (64, 32, 16, 8):
        if TILE_ROWS % k:
            continue
        bound = k * max(max_abs, 1)
        for idt in (np.int16, np.int32):
            if np.iinfo(idt).min < -bound and bound < np.iinfo(idt).max and np.dtype(idt).itemsize >= np.dtype(dtype).itemsize:
                return k, idt
        if bound < np.iinfo(np.int64).max // 4:
            return k, np.int64
    return 1, np.int64


def _tile_sum(x2d, max_abs: int):
    """(T', L) → (T',) exact int64 tile sums, staged to keep reductions
    same-dtype (a widening reduce scalarizes on XLA-CPU)."""
    t, l = x2d.shape
    if x2d.dtype == jnp.int64:
        return x2d.sum(axis=1)
    k, idt = _stage_split(x2d.dtype.type, max_abs)
    if k == 1:
        return x2d.astype(jnp.int64).sum(axis=1)
    inner = x2d.reshape(t, l // k, k).sum(axis=-1, dtype=jnp.dtype(idt))
    return inner.sum(axis=1, dtype=jnp.int64)


# ---------------------------------------------------------------------------
# Conjunct recognition (interval arithmetic against tile zones)
# ---------------------------------------------------------------------------

def _rpn_sig(rpn: RpnExpression | None) -> tuple:
    if rpn is None:
        return ()
    return tuple(
        (n.kind, n.eval_type, n.frac, n.index, n.value, n.op, n.arity, tuple(n.scale_by or ()))
        for n in rpn.nodes
    )


def _plan_sig(ev) -> tuple:
    """Everything the partial-tile program depends on: selection RPNs (of a
    plan's shape: the literals are param nodes, read from the program's
    ``params`` argument), aggregate ops + argument RPNs, and whether grouping
    is on.  Two evaluators with equal signatures compile to identical
    programs, so they share one cached jitted fn per layout instead of
    pinning one each, and a new literal finds the one it is."""
    return (
        tuple(_rpn_sig(r) for r in ev.sel_rpns),
        _agg_sig(ev),
    )


def _agg_sig(ev) -> tuple:
    """The aggregate/grouping part of the plan signature ALONE.  The
    full-tile program never evaluates selection row-wise — selection lives
    entirely in the tile classification, which arrives as the w_full
    argument — so keying its cache on the full _plan_sig made every distinct
    selection constant recompile an identical XLA program and churn the
    32-entry per-layout cache."""
    return (
        tuple((da.op, _rpn_sig(da.rpn)) for da in ev.device_aggs),
        bool(ev.group_rpns),
    )


_ZONE_FNS_MAX = 32  # distinct plan shapes cached per layout
_DECLINED_PARAMS_MAX = 256  # literal sets a cache remembers a decline for


def _layout_fn_cache(layout) -> dict:
    return layout.__dict__.setdefault("_zone_fns", {})


def _fn_cache_put(fns: dict, key, jfn):
    fns[key] = jfn
    while len(fns) > _ZONE_FNS_MAX:
        fns.pop(next(iter(fns)))
    return jfn


def _recognize_conjunct(rpn: RpnExpression):
    """(col_index, op, col_scale, const_value_scaled) for `cmp(col, const)` /
    `cmp(const, col)` RPNs, with the comparison flipped so the column is
    always on the left and both sides pre-multiplied by the node's static
    decimal-alignment factors (positive, so interval order is preserved);
    None for anything else (those classify every tile as partial)."""
    nodes = rpn.nodes
    if len(nodes) != 3 or nodes[2].kind != "fn":
        return None
    op = nodes[2].op
    flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq", "ne": "ne"}
    if op not in flip:
        return None
    a, b = nodes[0], nodes[1]
    sb = nodes[2].scale_by
    if a.kind == "col" and b.kind == "const":
        const = None if b.value is None else b.value * sb[1]
        return (a.index, op, sb[0], const)
    if a.kind == "const" and b.kind == "col":
        const = None if a.value is None else a.value * sb[0]
        return (b.index, flip[op], sb[1], const)
    return None




# ---------------------------------------------------------------------------
# Layout
# ---------------------------------------------------------------------------

class ZoneLayout:
    """Clustered, tiled, narrowed image of a filled block cache for one
    (group_cols, sort_col) signature.  Device arrays are flat over all tiles;
    zone stats stay host-side numpy."""

    def __init__(self, blocks, group_cols, dicts, sort_col, needed_cols, schema):
        self.group_cols = list(group_cols)
        self.sort_col = sort_col
        dict_lens = [len(d) for d in dicts]
        self.n_slots = 1
        for dl in dict_lens:
            self.n_slots *= dl + 1
        self.dicts = dicts
        self.dict_lens = dict_lens
        self.schema = schema

        perm_parts = []      # (block_index, original_positions) per run chunk
        valid_parts = []
        tile_gid_parts = []
        base = 0             # global valid-row offset of each block
        for blk in blocks:
            n_valid = blk.n_valid
            if self.n_slots > 1:
                gid = np.zeros(n_valid, dtype=np.int64)
                for ci, dl in zip(group_cols, dict_lens):
                    col = blk.cols[ci]
                    codes = np.asarray(col.data[:n_valid], dtype=np.int64)
                    nulls = np.asarray(col.nulls[:n_valid])
                    gid = gid * (dl + 1) + np.where(nulls, dl, codes)
            else:
                gid = np.zeros(n_valid, dtype=np.int64)
            if sort_col is not None:
                skey = np.asarray(blk.cols[sort_col].data[:n_valid])
                order = np.lexsort((skey, gid))
            else:
                order = np.argsort(gid, kind="stable")
            gs = gid[order]
            # run boundaries per slot present in this block
            boundaries = np.flatnonzero(np.diff(gs)) + 1
            starts = np.concatenate([[0], boundaries, [n_valid]])
            for s, e in zip(starts[:-1], starts[1:]):
                if s == e:
                    continue
                run = order[s:e]
                slot = int(gs[s])
                pad = (-len(run)) % TILE_ROWS
                perm_parts.append((blk, base, run, False))
                valid_parts.append(np.ones(len(run), dtype=bool))
                if pad:
                    perm_parts.append((blk, base, np.zeros(pad, dtype=run.dtype), True))
                    valid_parts.append(np.zeros(pad, dtype=bool))
                tile_gid_parts.append(np.full((len(run) + pad) // TILE_ROWS, slot, dtype=np.int32))
            base += n_valid

        valid = np.concatenate(valid_parts)
        self.n_rows = len(valid)
        self.tile_gid = np.concatenate(tile_gid_parts)
        self.n_tiles = len(self.tile_gid)
        assert self.n_tiles * TILE_ROWS == self.n_rows

        # gather the needed columns through the permutation, block by block
        ridx = np.empty(self.n_rows, dtype=np.int32)
        pos = 0
        gathered: dict[int, list] = {i: [] for i in needed_cols}
        nullable = set()
        for i in needed_cols:
            if any(np.asarray(b.cols[i].nulls[: b.n_valid]).any() for b in blocks):
                nullable.add(i)
        null_gathered: dict[int, list] = {i: [] for i in nullable}
        for blk, bbase, run, is_pad in perm_parts:
            m = len(run)
            if not is_pad:
                ridx[pos : pos + m] = (bbase + run).astype(np.int32)
                for i in needed_cols:
                    gathered[i].append(np.asarray(blk.cols[i].data)[run])
                for i in nullable:
                    null_gathered[i].append(np.asarray(blk.cols[i].nulls)[run])
            else:
                ridx[pos : pos + m] = _RIDX_INF
                for i in needed_cols:
                    gathered[i].append(np.zeros(m, dtype=np.asarray(blk.cols[i].data).dtype))
                for i in nullable:
                    null_gathered[i].append(np.ones(m, dtype=bool))
            pos += m

        self.valid = valid
        self.ridx = ridx
        self.nullable = nullable
        T = self.n_tiles
        self.cols_np: dict[int, np.ndarray] = {}
        self.nulls_np: dict[int, np.ndarray] = {}
        self.col_ranges: dict[int, tuple] = {}
        self.zone_lo: dict[int, np.ndarray] = {}
        self.zone_hi: dict[int, np.ndarray] = {}
        self.zone_has_null: dict[int, np.ndarray] = {}
        for i in needed_cols:
            arr = np.concatenate(gathered[i])
            nl = np.concatenate(null_gathered[i]) if i in nullable else None
            et = schema[i][0]
            if et == EvalType.REAL:
                data = np.where(~valid | (nl if nl is not None else False), 0.0, arr).astype(np.float64)
            else:
                a64 = arr.astype(np.int64)
                a64 = np.where(~valid | (nl if nl is not None else False), 0, a64)
                lo, hi = (int(a64.min()), int(a64.max())) if len(a64) else (0, 0)
                data = a64.astype(_narrow_dtype(lo, hi))
            self.cols_np[i] = data
            if nl is not None:
                self.nulls_np[i] = nl
            # zone stats over live (non-pad, non-null) rows only, in the
            # column's own dtype domain (float stats on int64 would round
            # above 2^53 and could misclassify a boundary tile as full)
            live = valid & (~nl if nl is not None else True)
            if et == EvalType.REAL:
                vals, pos_id, neg_id = arr.astype(np.float64), np.inf, -np.inf
            else:
                info = np.iinfo(np.int64)
                vals, pos_id, neg_id = arr.astype(np.int64), info.max, info.min
            self.zone_lo[i] = np.where(live, vals, pos_id).reshape(T, TILE_ROWS).min(axis=1)
            self.zone_hi[i] = np.where(live, vals, neg_id).reshape(T, TILE_ROWS).max(axis=1)
            self.zone_has_null[i] = (
                nl.reshape(T, TILE_ROWS).any(axis=1) if nl is not None else np.zeros(T, dtype=bool)
            )
            if et != EvalType.REAL:
                a = self.cols_np[i].astype(np.int64)
                self.col_ranges[i] = (int(a.min()) if len(a) else 0, int(a.max()) if len(a) else 0)
            else:
                self.col_ranges[i] = (0, 0)
        self.valid_count = valid.reshape(T, TILE_ROWS).sum(axis=1).astype(np.int32)
        self.has_pad = self.valid_count < TILE_ROWS

        # device pins
        self.dev = {
            "tile_gid": jnp.asarray(self.tile_gid),
            "valid_count": jnp.asarray(self.valid_count),
            "ridx": jnp.asarray(self.ridx),
            "valid": jnp.asarray(self.valid),
            "cols": {i: jnp.asarray(a) for i, a in self.cols_np.items()},
            "nulls": {i: jnp.asarray(a) for i, a in self.nulls_np.items()},
        }
        note_blocking("device.pin:zone_layout")
        for v in jax.tree.leaves(self.dev):
            v.block_until_ready()
        # classification needs only the per-tile stats; the full-size host
        # copies just fed the device pins — at bench scale they are GBs
        del self.cols_np, self.nulls_np, self.valid, self.ridx
        # encoded-resident images (docs/compressed_columns.md): the gathers
        # above materialized their decode caches — drop them, or the image
        # holds encoded payload + full decode while the budget counts only
        # the former
        for blk in blocks:
            for c in blk.cols:
                if hasattr(c, "purge_decoded"):
                    c.purge_decoded()



def build_layout(cache, group_cols, dicts, sort_col, needed_cols, schema):
    sig = ("zone_layout", tuple(group_cols), sort_col, tuple(sorted(needed_cols)), TILE_ROWS)
    blocks = cache.blocks

    def build(_blk):
        return ZoneLayout(blocks, group_cols, dicts, sort_col, sorted(needed_cols), schema)

    return cache.device_arrays(blocks[0], sig, build)


# ---------------------------------------------------------------------------
# Evaluator
# ---------------------------------------------------------------------------

class ZoneEvaluator:
    """Zone-path runner for one JaxDagEvaluator plan.  ``try_run`` returns the
    (state_np, n_slots, key_of) finalize inputs, or None to fall back."""

    def __init__(self, ev):
        self.ev = ev  # the owning JaxDagEvaluator
        import weakref

        # caches we already declined for (partial fraction too high): skip
        # the layout work on every later query against the same cache
        self._declined = weakref.WeakSet()
        # the partial-tile fraction follows the request's literals, so that
        # decline is remembered by cache AND literals (a bounded set of them)
        self._declined_params = weakref.WeakKeyDictionary()
        self.served = 0  # queries answered by the zone path (observability)
        self.failed = 0  # zone-path crashes that fell through (observability)
        self.last_error: str | None = None

    # -- eligibility -------------------------------------------------------

    def eligible(self, blocks):
        from .tracker import count_path_fallback

        ev = self.ev
        if ev.plan.agg is None:
            return None
        stable = ev._stable_dict_group_cols(blocks)
        if stable is None:
            count_path_fallback("zone", "unstable_group_dicts")
            return None
        group_cols, dicts = stable
        for da in ev.device_aggs:
            if da.op not in _ZONE_AGG_OPS:
                count_path_fallback("zone", "agg_op")
                return None
            if da.rpn is not None:
                if da.rpn.eval_type == EvalType.REAL or da.input_type == EvalType.REAL:
                    # float sum order must match the CPU oracle — the
                    # VERDICT-weak-#6 decline that used to be invisible
                    count_path_fallback("zone", "real_arg")
                    return None
                for node in da.rpn.nodes:
                    if node.kind == "fn" and node.op not in _NULLSAFE_OPS:
                        count_path_fallback("zone", "non_nullsafe_fn")
                        return None
                    if node.kind == "const" and node.value is None:
                        # NULL literal breaks the null-safety rule
                        count_path_fallback("zone", "null_literal")
                        return None
        return group_cols, dicts

    def declined(self, cache, params) -> bool:
        return (cache in self._declined
                or params in self._declined_params.get(cache, ()))

    # -- per-query host classification -------------------------------------

    def _classify_tiles(self, layout, sel_rpns):
        """(full_mask, partial_idx) over tiles; empty tiles appear in
        neither.  Forced-partial: pad tiles and tiles with NULLs in any
        column referenced by selection or aggregate arguments.
        ``sel_rpns``: the selection with THIS request's literals
        (``bound_sel_rpns``): a tile called full by another query's date
        would be a wrong answer."""
        T = layout.n_tiles
        status_full = np.ones(T, dtype=bool)
        status_empty = np.zeros(T, dtype=bool)
        for rpn in sel_rpns:
            rec = _recognize_conjunct(rpn)
            if rec is None:
                status_full[:] = False
                continue
            ci, op, cscale, const = rec
            if ci not in layout.zone_lo:
                status_full[:] = False
                continue
            if const is None:
                status_empty[:] = True
                status_full[:] = False
                continue
            lo, hi = layout.zone_lo[ci], layout.zone_hi[ci]
            if cscale != 1:
                # exact Python-int arithmetic: int64*scale may wrap in numpy,
                # and a wrapped bound could prove a tile "full" wrongly
                lo = lo.astype(object) * int(cscale)
                hi = hi.astype(object) * int(cscale)
            c = const
            if op == "lt":
                cf, ce = hi < c, lo >= c
            elif op == "le":
                cf, ce = hi <= c, lo > c
            elif op == "gt":
                cf, ce = lo > c, hi <= c
            elif op == "ge":
                cf, ce = lo >= c, hi < c
            elif op == "eq":
                cf, ce = (lo == c) & (hi == c), (c < lo) | (c > hi)
            else:  # ne
                cf, ce = (c < lo) | (c > hi), (lo == c) & (hi == c)
            # a NULL row fails every comparison: nulls block fullness
            cf = cf & ~layout.zone_has_null[ci]
            status_full &= cf
            status_empty |= ce
        forced = layout.has_pad.copy()
        for ci in self._referenced_cols():
            if ci in layout.zone_has_null:
                forced |= layout.zone_has_null[ci]
        full = status_full & ~status_empty & ~forced
        partial = ~full & ~status_empty
        # the tile-grained twin of the block-grained zone_maps counter:
        # proved-empty tiles are pruned work, same metric family
        from .zone_maps import count_prune

        count_prune("zone", "examined", T)
        count_prune("zone", "pruned", int(status_empty.sum()))
        return full, np.flatnonzero(partial).astype(np.int32)

    def _referenced_cols(self):
        ev = self.ev
        need = set()
        for r in ev.sel_rpns:
            need |= r.referenced_columns()
        for da in ev.device_aggs:
            if da.rpn is not None:
                need |= da.rpn.referenced_columns()
        return need

    # -- device programs ---------------------------------------------------

    def _full_fn(self, layout, capacity):
        """Full-tile contributions: pure tile reductions weighted by w_full."""
        # jitted fns live ON the layout: they close over it, so storing them
        # anywhere longer-lived would pin evicted layouts (and their device
        # arrays) forever; with the cache pin gone, layout + fns + compiled
        # programs all drop together.  Plan-signature keys let equivalent
        # evaluators share one compiled program (the endpoint's evaluator
        # LRU churns instances), and the dict is bounded.
        fns = _layout_fn_cache(layout)
        key = ("full", _agg_sig(self.ev), capacity)
        if key in fns:
            return fns[key]
        ev = self.ev
        T = layout.n_tiles
        track_first = bool(ev.group_rpns)
        ranges = layout.col_ranges

        def widen_cols(dev):
            cols = {}
            for i, a in dev["cols"].items():
                d = a.astype(jnp.int64) if a.dtype != jnp.float64 else a
                nl = dev["nulls"].get(i)
                cols[i] = (d, nl if nl is not None else jnp.zeros(layout.n_rows, dtype=bool))
            return cols

        def fn(dev, w_full):
            tg = dev["tile_gid"]
            wf = w_full
            seg = lambda x: jax.ops.segment_sum(x, tg, num_segments=capacity)
            vc = jnp.where(wf, dev["valid_count"].astype(jnp.int64), 0)
            counts = seg(vc)
            carries = []
            lazy_cols = None
            for da in ev.device_aggs:
                if da.op == "count":
                    # count(*) and count(expr) agree on full tiles: forced-
                    # partial removed every tile with NULLs in referenced
                    # columns, so all valid rows are live
                    carries.append((counts,))
                    continue
                bare = len(da.rpn.nodes) == 1 and da.rpn.nodes[0].kind == "col"
                if bare:
                    ci = da.rpn.nodes[0].index
                    arr2 = dev["cols"][ci].reshape(T, TILE_ROWS)
                    max_abs = max(abs(ranges[ci][0]), abs(ranges[ci][1]))
                else:
                    if lazy_cols is None:
                        lazy_cols = widen_cols(dev)
                    d, _nl = eval_rpn(da.rpn, lazy_cols, layout.n_rows, xp=jnp)
                    arr2 = d.reshape(T, TILE_ROWS)
                    max_abs = None  # already int64: _tile_sum sums directly
                if da.op in ("sum", "avg"):
                    ts = _tile_sum(arr2, max_abs if bare else 0)
                    carries.append((counts, seg(jnp.where(wf, ts, 0))))
                elif da.op == "var_pop":
                    # sumsq rides f64 (the CPU state's own dtype), fused
                    # square + same-dtype tile sum — vectorizes like the
                    # pure passes because nothing widens inside the reduce
                    ts = _tile_sum(arr2, max_abs if bare else 0)
                    f2 = arr2.astype(jnp.float64)
                    tsq = (f2 * f2).sum(axis=1)
                    carries.append((
                        counts,
                        seg(jnp.where(wf, ts, 0)),
                        seg(jnp.where(wf, tsq, 0.0)),
                    ))
                else:  # min / max — same-dtype tile reduce, then widen T-wise
                    red = (arr2.min(axis=1) if da.op == "min" else arr2.max(axis=1)).astype(jnp.int64)
                    info = np.iinfo(np.int64)
                    ident = info.max if da.op == "min" else info.min
                    red = jnp.where(wf, red, ident)
                    f = jax.ops.segment_min if da.op == "min" else jax.ops.segment_max
                    carries.append((counts, f(red, tg, num_segments=capacity)))
            if track_first:
                tmin = dev["ridx"].reshape(T, TILE_ROWS).min(axis=1)
                tmin = jnp.where(wf, tmin, _RIDX_INF)
                first = jax.ops.segment_min(tmin, tg, num_segments=capacity).astype(jnp.int64)
                first = jnp.where(first == int(_RIDX_INF), _NO_ROW_J, first)
            else:
                first = jnp.full(capacity, _NO_ROW_J, dtype=jnp.int64)
            return first, tuple(carries)

        return _fn_cache_put(
            fns, key,
            _obs.timed_jit(jax.jit(fn), "jax_zone.full", "zone",
                           self.ev.obs_sig))

    def _partial_fn(self, layout, capacity, pcap):
        """Gathered partial tiles: full row-level RPN evaluation over a
        (pcap, TILE_ROWS) bucket, padded entries weighted out."""
        fns = _layout_fn_cache(layout)
        key = ("partial", _plan_sig(self.ev), capacity, pcap)
        if key in fns:
            return fns[key]
        ev = self.ev
        T = layout.n_tiles
        track_first = bool(ev.group_rpns)
        n_sub = pcap * TILE_ROWS

        def fn(dev, pidx, pw, params):
            tg = dev["tile_gid"][pidx]
            tg = jnp.where(pw, tg, capacity - 1)  # scratch slot for padding
            cols = {}
            for i, a in dev["cols"].items():
                sub = a.reshape(T, TILE_ROWS)[pidx].reshape(n_sub)
                d = sub.astype(jnp.int64) if sub.dtype != jnp.float64 else sub
                nl = dev["nulls"].get(i)
                nl = (
                    nl.reshape(T, TILE_ROWS)[pidx].reshape(n_sub)
                    if nl is not None
                    else jnp.zeros(n_sub, dtype=bool)
                )
                cols[i] = (d, nl)
            valid = dev["valid"].reshape(T, TILE_ROWS)[pidx].reshape(n_sub)
            active = valid & jnp.broadcast_to(pw[:, None], (pcap, TILE_ROWS)).reshape(n_sub)
            for rpn in ev.sel_rpns:
                d, nl = eval_rpn(rpn, cols, n_sub, xp=jnp, params=params)
                active = active & (d != 0) & ~nl
            seg = lambda x: jax.ops.segment_sum(x, tg, num_segments=capacity)

            def tile_red(x, red):
                return red(x.reshape(pcap, TILE_ROWS), axis=1)

            carries = []
            for da in ev.device_aggs:
                if da.rpn is None:
                    live = active
                    data = None
                else:
                    data, dnl = eval_rpn(da.rpn, cols, n_sub, xp=jnp)
                    live = active & ~dnl
                cnt = seg(tile_red(live.astype(jnp.int64), jnp.sum))
                if da.op == "count":
                    carries.append((cnt,))
                elif da.op in ("sum", "avg"):
                    vals = jnp.where(live, data, 0)
                    carries.append((cnt, seg(tile_red(vals, jnp.sum))))
                elif da.op == "var_pop":
                    vals = jnp.where(live, data, 0)
                    f = jnp.where(live, data.astype(jnp.float64), 0.0)
                    carries.append((
                        cnt,
                        seg(tile_red(vals, jnp.sum)),
                        seg(tile_red(f * f, jnp.sum)),
                    ))
                else:
                    info = np.iinfo(np.int64)
                    ident = info.max if da.op == "min" else info.min
                    masked = jnp.where(live, data, ident)
                    red = tile_red(masked, jnp.min if da.op == "min" else jnp.max)
                    f = jax.ops.segment_min if da.op == "min" else jax.ops.segment_max
                    carries.append((cnt, f(red, tg, num_segments=capacity)))
            if track_first:
                ridx = dev["ridx"].reshape(T, TILE_ROWS)[pidx].reshape(n_sub)
                rm = jnp.where(active, ridx, _RIDX_INF)
                tmin = tile_red(rm, jnp.min)
                first = jax.ops.segment_min(tmin, tg, num_segments=capacity).astype(jnp.int64)
                first = jnp.where(first == int(_RIDX_INF), _NO_ROW_J, first)
            else:
                first = jnp.full(capacity, _NO_ROW_J, dtype=jnp.int64)
            return first, tuple(carries)

        return _fn_cache_put(
            fns, key,
            _obs.timed_jit(jax.jit(fn), "jax_zone.partial", "zone",
                           self.ev.obs_sig))

    # -- merge + run -------------------------------------------------------

    def try_run(self, cache, params: tuple = ()):
        """Zone-serve the plan over ``cache`` for the request's literals
        ``params``, or None to fall back.  A
        zone-path FAILURE (unexpected compiler/backend error — e.g. the
        first run on a new accelerator) is caught, recorded, and remembered
        per cache: the fast layer must never take down a query the slower
        layers can serve, and must not retry a crash on every request."""
        from .tracker import count_path_fallback

        breaker = getattr(self.ev, "breaker", None)
        if breaker is not None and not breaker.allow("zone"):
            count_path_fallback("zone", "breaker_open")
            return None
        try:
            out = self._try_run_inner(cache, params)
            if breaker is not None:
                if out is not None:
                    breaker.record_success("zone")
                else:
                    breaker.release_probe("zone")  # declined, didn't run
            return out
        except Exception as exc:  # noqa: BLE001 — generic path always serves
            self.failed += 1
            self.last_error = repr(exc)
            self._declined.add(cache)
            count_path_fallback("zone", "zone_error")
            if breaker is not None:
                breaker.record_failure("zone")
            return None

    def _try_run_inner(self, cache, params):
        from .tracker import count_path_fallback

        # host work before the dispatches: eligibility, the layout (built
        # once an image), tile classification, the masks' transfer; each
        # dispatch is a stage of its own and suspends this one
        with trace.stage("device.prepare", path="zone"):
            ev = self.ev
            blocks = cache.blocks
            if self.declined(cache, params):
                return None
            el = self.eligible(blocks)
            if el is None:
                return None
            group_cols, dicts = el
            # the literals are read from the request, every time
            sel_rpns = ev.bound_sel_rpns(params)
            if sel_rpns and all(
                _recognize_conjunct(r) is None for r in sel_rpns
            ):
                # no conjunct classifiable → 100% partial tiles: don't pay for a
                # layout the fallback check would immediately discard
                self._declined.add(cache)
                count_path_fallback("zone", "unclassifiable_selection")
                return None
            needed = self._referenced_cols()
            sort_col = None
            for rpn in sel_rpns:
                rec = _recognize_conjunct(rpn)
                if rec is not None and rec[0] not in group_cols and ev.schema[rec[0]][0] != EvalType.REAL:
                    sort_col = rec[0]
                    break
            layout = build_layout(cache, group_cols, dicts, sort_col, needed, ev.schema)
            full, partial_idx = self._classify_tiles(layout, sel_rpns)
            if layout.n_tiles and len(partial_idx) / layout.n_tiles > PARTIAL_FALLBACK:
                seen = self._declined_params.setdefault(cache, set())
                if len(seen) >= _DECLINED_PARAMS_MAX:
                    seen.clear()
                seen.add(params)
                count_path_fallback("zone", "partial_fraction")
                return None
            n_slots = layout.n_slots
            capacity = 1
            while capacity < n_slots + 1:  # +1: scratch slot for partial padding
                capacity *= 2

            have_full = bool(full.any())
            have_partial = len(partial_idx) > 0
            states = []
            if have_full:
                fn = self._full_fn(layout, capacity)
                states.append(fn(layout.dev, jnp.asarray(full)))
            if have_partial:
                pcap = 64
                while pcap < len(partial_idx):
                    pcap *= 2
                pidx = np.zeros(pcap, dtype=np.int32)
                pidx[: len(partial_idx)] = partial_idx
                pw = np.zeros(pcap, dtype=bool)
                pw[: len(partial_idx)] = True
                fn = self._partial_fn(layout, capacity, pcap)
                states.append(fn(layout.dev, jnp.asarray(pidx), jnp.asarray(pw),
                                 ev.param_vectors(params)))
            if not states:
                # every tile proved empty: zero contributions
                states.append(
                    self._full_fn(layout, capacity)(layout.dev, jnp.zeros(layout.n_tiles, dtype=bool))
                )
            merged = states[0] if len(states) == 1 else _merge_states(ev.device_aggs, states[0], states[1])
        with trace.stage("device.pull"):
            state_np = jax.tree.map(np.asarray, merged)
        with trace.stage("device.release"):
            # the states' device buffers are dropped here and not at the
            # function's return: freeing them waits on the runtime
            del states, merged

        dict_lens = layout.dict_lens
        dicts_l = layout.dicts

        def key_of(slot: int) -> tuple:
            parts = []
            rem = int(slot)
            for d, dl in zip(reversed(dicts_l), reversed(dict_lens)):
                c = rem % (dl + 1)
                rem //= dl + 1
                parts.append(None if c == dl else bytes(d[c]))
            return tuple(reversed(parts))

        self.served += 1
        return state_np, n_slots, key_of


_NO_ROW_J = 1 << 62  # matches jax_eval._NO_ROW


def _merge_states(device_aggs, a, b):
    """Combine full-tile and partial-tile (first_row, carries) states."""
    first = jnp.minimum(a[0], b[0])
    carries = []
    for da, ca, cb in zip(device_aggs, a[1], b[1]):
        cnt = ca[0] + cb[0]
        if da.op == "count":
            carries.append((cnt,))
        elif da.op in ("sum", "avg"):
            carries.append((cnt, ca[1] + cb[1]))
        elif da.op == "var_pop":
            carries.append((cnt, ca[1] + cb[1], ca[2] + cb[2]))
        else:
            merge = jnp.minimum if da.op == "min" else jnp.maximum
            carries.append((cnt, merge(ca[1], cb[1])))
    return first, tuple(carries)
