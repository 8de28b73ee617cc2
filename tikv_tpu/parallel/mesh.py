"""Mesh-sharded coprocessor evaluation.

TiKV scales horizontally by splitting the key space into regions
(``raftstore/src/coprocessor/split_check/``); the TPU-native re-expression is
a ``jax.sharding.Mesh`` with two axes:

* ``"regions"`` — row blocks sharded across devices (the data-parallel axis:
  each device scans/filters/aggregates its own region shard; partial
  aggregate states merge with ``psum``/``pmin``/``pmax`` over ICI, exactly the
  mergeable-state design the CPU pipeline uses across batches)
* ``"groups"`` — the aggregation state (group capacity) sharded across
  devices (the tensor-parallel axis: each device owns a slice of the
  group-state vector after the cross-region reduction)

The collectives ride ICI inside a pod; nothing here assumes a host count, so
the same program runs on a virtual 8-CPU-device mesh (tests / driver dryrun)
and a real TPU slice.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from ..analysis.sanitizer import note_blocking
from ..copr import observatory as _obs
from ..copr.dag import DagRequest
from ..copr.jax_eval import (
    _NO_ROW,
    JaxDagEvaluator,
    XRegionPending,
    _build_cols,
    _fused_step,
    _pack_region_leaves,
    _seg_extreme,
    _seg_sum,
    _topn_key_operands,
)
from ..copr.rpn import eval_rpn

def _smap(mesh: Mesh, in_specs, out_specs, check: bool = True):
    """``jax.shard_map`` as a decorator over this module's mesh programs."""
    return partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=check)


_KEY_SENTINEL = jnp.int64(2**62)  # empty group-dictionary slot (sorts last)


def make_mesh(devices=None, groups: int = 1) -> Mesh:
    """A (regions × groups) mesh over the given (or all) devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    assert n % groups == 0, "device count must divide into group shards"
    arr = np.array(devices).reshape(n // groups, groups)
    return Mesh(arr, axis_names=("regions", "groups"))


# per-leaf merge semantics of each aggregate's carry (leaf 0 is always count).
# bitwise ops are associative+commutative, so they merge across region shards
# like min/max; ``first`` is NOT here — its carry is a paired (value, row
# index) argmin that a leaf-wise merge cannot express, so mesh construction
# declines it (ValueError) and the endpoint memoizes the single-device route.
_MERGE = {
    "count": ("sum",),
    "sum": ("sum", "sum"),
    "avg": ("sum", "sum"),
    "var_pop": ("sum", "sum", "sum"),
    "min": ("sum", "min"),
    "max": ("sum", "max"),
    "bit_and": ("sum", "bit_and"),
    "bit_or": ("sum", "bit_or"),
    "bit_xor": ("sum", "bit_xor"),
}


def _require_mesh_mergeable(device_aggs) -> None:
    for da in device_aggs:
        if da.op not in _MERGE:
            raise ValueError(f"aggregate {da.op!r} has no mesh merge rule")


def _marshal_block(ev: JaxDagEvaluator, columns: dict, n_valid: int, total_rows: int):
    """Host-side marshalling of one super-block: THE one definition shared
    by every sharded evaluator's run_blocks."""
    col_data = tuple(np.asarray(columns[i][0]) for i in ev.device_cols)
    col_nulls = tuple(np.asarray(columns[i][1]) for i in ev.nullable_cols)
    valid = np.zeros(total_rows, dtype=bool)
    valid[:n_valid] = True
    return col_data, col_nulls, valid


def _shard_active_cols(device_cols, nullable, sel_rpns, col_data, col_nulls, valid, n_rows):
    """In-jit preamble shared by every sharded step: build the per-column
    (data, nulls) map and fold the selection predicates into the row mask."""
    no_nulls = jnp.zeros(n_rows, dtype=bool)
    nullmap = dict(zip(nullable, col_nulls))
    cols = {
        i: (col_data[j], nullmap.get(i, no_nulls))
        for j, i in enumerate(device_cols)
    }
    active = valid
    for rpn in sel_rpns:
        d, nl = eval_rpn(rpn, cols, n_rows, xp=jnp)
        active = active & (d != 0) & ~nl
    return cols, active


def _collective(kind: str, x, axis: str):
    if kind == "sum":
        return jax.lax.psum(x, axis)
    # min/max and the bitwise monoids: gather the shard partials and fold
    # them locally.  The bitwise ones have no collective at all, and over
    # 64-bit lanes (int64 and float64 alike) the TPU lowers SUM all-reduces
    # only: its compiler refuses pmin/pmax ("Supported lowering only of Sum
    # all reduce").  The fold's result is identical on every member but
    # shard_map cannot infer that statically, so a final psum (member 0
    # contributes, the others add zero) re-establishes provable replication.
    from ..copr.jax_eval import _BIT_FN, _BIT_IDENT

    g = jax.lax.all_gather(x, axis)
    if kind == "min":
        folded = jnp.min(g, axis=0)
    elif kind == "max":
        folded = jnp.max(g, axis=0)
    else:
        folded = jax.lax.reduce(g, jnp.int64(_BIT_IDENT[kind]), _BIT_FN[kind], (0,))
    first = jax.lax.axis_index(axis) == 0
    out = jax.lax.psum(jnp.where(first, folded, jnp.zeros_like(folded)), axis)
    if jnp.issubdtype(folded.dtype, jnp.floating):
        # a sum cannot carry -0.0 (x + 0.0 is +0.0); its sign rides a flag
        neg_zero = first & (folded == 0) & jnp.signbit(folded)
        out = jnp.where(jax.lax.psum(neg_zero.astype(jnp.int32), axis) > 0,
                        jnp.full_like(out, -0.0), out)
    return out


def _combine(kind: str, a, b):
    if kind == "sum":
        return a + b
    if kind == "min":
        return jnp.minimum(a, b)
    if kind == "max":
        return jnp.maximum(a, b)
    from ..copr.jax_eval import _BIT_FN

    return _BIT_FN[kind](a, b)


class ShardedDagEvaluator:
    """Multi-device DAG aggregation step for an eligible aggregation DAG.

    ``step(col_data, col_nulls, valid, gids, state)`` consumes one super-block
    whose rows are sharded over the ``regions`` axis and whose state shards
    over ``groups``; it returns the updated sharded state.  Finalization uses
    the same host code as the single-device evaluator.
    """

    def __init__(self, dag: DagRequest, mesh: Mesh, rows_per_shard: int, capacity: int = 16):
        self.ev = JaxDagEvaluator(dag, block_rows=rows_per_shard)
        if self.ev.plan.agg is None:
            raise ValueError("sharded evaluation requires an aggregation DAG")
        _require_mesh_mergeable(self.ev.device_aggs)
        self.mesh = mesh
        self.rows_per_shard = rows_per_shard
        self.n_regions = mesh.shape["regions"]
        self.n_groups = mesh.shape["groups"]
        assert capacity % self.n_groups == 0
        self.capacity = capacity
        self.total_rows = rows_per_shard * self.n_regions
        self._step = self._build_step()

    def _build_step(self):
        ev = self.ev
        capacity = self.capacity
        gshard = capacity // self.n_groups
        n_rows = self.rows_per_shard
        device_cols = ev.device_cols
        nullable = ev.nullable_cols
        sel_rpns = ev.sel_rpns
        device_aggs = ev.device_aggs

        col_specs = tuple(P("regions") for _ in device_cols)
        null_specs = tuple(P("regions") for _ in nullable)
        state_spec = (
            P("groups"),
            tuple(
                tuple(P("groups") for _ in _MERGE[da.op])
                for da in device_aggs
            ),
        )
        in_specs = (col_specs, null_specs, P("regions"), P("regions"), P(), state_spec)

        @_smap(self.mesh, in_specs, state_spec)
        def step(col_data, col_nulls, valid, gids, block_base, state):
            first_shard, carry_shards = state
            cols, active = _shard_active_cols(
                device_cols, nullable, sel_rpns, col_data, col_nulls, valid, n_rows
            )
            gidx = jax.lax.axis_index("groups")
            lo = gidx * gshard
            new_first = first_shard
            new_carries = []
            for da, carry_shard in zip(device_aggs, carry_shards):
                zero = da.init_carry(capacity)
                partial_full = da.update(zero, cols, n_rows, gids, active, capacity)
                merged = []
                for kind, leaf in zip(_MERGE[da.op], partial_full):
                    # reduce partial states across region shards, then each
                    # groups-member keeps its slice of the state vector
                    leaf = _collective(kind, leaf, "regions")
                    my = jax.lax.dynamic_slice_in_dim(leaf, lo, gshard)
                    merged.append(my)
                new_carries.append(
                    tuple(_combine(k, c, m) for k, c, m in zip(_MERGE[da.op], carry_shard, merged))
                )
            # global row index (region shards hold consecutive row ranges), so
            # group order matches the single-stream first-occurrence order
            shard_base = jax.lax.axis_index("regions").astype(jnp.int64) * n_rows
            ridx = jnp.where(
                active,
                block_base + shard_base + jnp.arange(n_rows, dtype=jnp.int64),
                _NO_ROW,
            )
            bf = _seg_extreme(ridx, gids, capacity, True, _NO_ROW)
            bf = jax.lax.pmin(bf, "regions")
            my_bf = jax.lax.dynamic_slice_in_dim(bf, lo, gshard)
            new_first = jnp.minimum(new_first, my_bf)
            return (new_first, tuple(new_carries))

        # lint: allow(jit-nocache) -- compiled ONCE per evaluator in
        # __init__ (self._step/self._fin memoize the returned callable)
        return _obs.timed_jit(jax.jit(step), "mesh.agg_step", "mesh",
                              self.ev.obs_sig)

    def init_state(self):
        gshard = self.capacity // self.n_groups
        first = jnp.full(self.capacity, _NO_ROW, dtype=jnp.int64)
        carries = tuple(da.init_carry(self.capacity) for da in self.ev.device_aggs)
        return (first, carries)

    def step(self, col_data, col_nulls, valid, gids, state, block_base: int = 0):
        return self._step(col_data, col_nulls, valid, gids, np.int64(block_base), state)

    def run_arrays(self, columns: dict, n_valid: int, gids: np.ndarray):
        """Evaluate one super-block given per-column numpy (data, nulls)."""
        return self.run_blocks([(columns, n_valid, gids)])

    def run_blocks(self, blocks):
        """Multi-block evaluation with carried state: each super-block's rows
        shard over ``regions`` while the aggregate state stays resident on
        device between blocks — the long-scan streaming shape of §2.5
        (blockwise evaluation with carry, applied across the mesh)."""
        state = self.init_state()
        for b, (columns, n_valid, gids) in enumerate(blocks):
            col_data, col_nulls, valid = _marshal_block(
                self.ev, columns, n_valid, self.total_rows
            )
            state = self.step(
                col_data, col_nulls, valid, np.asarray(gids), state,
                block_base=b * self.total_rows,
            )
        return state


class ShardedGroupedEvaluator:
    """Grouped aggregation with the group DICTIONARY built on device, sharded
    over the mesh (fast_hash_aggr_executor.rs:38 re-expressed for SPMD).

    The single-device warm path dict-codes group keys on the host; here each
    region shard packs its group-by column values into one int64 key, merges
    the keys into a bounded SORTED dictionary (static-shape union: concat →
    sort → unique-rank scatter), all-gathers the dictionaries over the
    ``regions`` axis into one global dictionary, and group ids are
    ``searchsorted`` positions in it.  Aggregate partial states then merge
    with psum/pmin/pmax exactly as in ShardedDagEvaluator.

    Output group ORDER follows first occurrence in the global row stream —
    recovered from the first-row-index state, so results are comparable to
    the CPU executor's dict-coded order.  Capacity overflow is detected
    (``overflow`` flag in the state) rather than silently dropping groups —
    the caller falls back to the host path, like every other device gate.
    """

    def __init__(
        self,
        dag: DagRequest,
        mesh: Mesh,
        rows_per_shard: int,
        capacity: int = 64,
        key_bits: int = 31,
    ):
        self.ev = JaxDagEvaluator(dag, block_rows=rows_per_shard)
        plan = self.ev.plan
        if plan.agg is None or not plan.agg.group_by:
            raise ValueError("grouped evaluation requires GROUP BY aggregation")
        _require_mesh_mergeable(self.ev.device_aggs)
        self.group_rpns = self.ev.group_rpns
        # the single-device path group-codes on the HOST, so the evaluator
        # does not ship group-by columns; here the dictionary builds on
        # device — extend the shipped set
        extra: set[int] = set()
        for g in self.group_rpns:
            extra |= g.referenced_columns()
        self.ev.ship_extra_columns(extra)
        if len(self.group_rpns) * key_bits > 62:
            raise ValueError(
                f"{len(self.group_rpns)} group keys x {key_bits} bits "
                "overflow the packed int64 key"
            )
        self.mesh = mesh
        self.rows_per_shard = rows_per_shard
        self.n_regions = mesh.shape["regions"]
        self.capacity = capacity
        self.key_bits = key_bits
        self.total_rows = rows_per_shard * self.n_regions
        self._step = self._build_step()

    def _build_step(self):
        ev = self.ev
        cap = self.capacity
        n_rows = self.rows_per_shard
        device_cols = ev.device_cols
        nullable = ev.nullable_cols
        sel_rpns = ev.sel_rpns
        device_aggs = ev.device_aggs
        group_rpns = self.group_rpns
        key_bits = self.key_bits

        col_specs = tuple(P("regions") for _ in device_cols)
        null_specs = tuple(P("regions") for _ in nullable)
        # replicated state: dict keys, first-row index, carries, overflow flag
        state_spec = (
            P(),
            P(),
            tuple(tuple(P() for _ in _MERGE[da.op]) for da in device_aggs),
            P(),
        )
        in_specs = (col_specs, null_specs, P("regions"), P(), state_spec)

        # every output IS replicated — it flows through psum/pmin/pmax or
        # all_gather before leaving — but the static varying-axis
        # inference cannot see that through the scatter/searchsorted
        # dictionary rebuild; the equality tests assert it dynamically
        @_smap(self.mesh, in_specs, state_spec, check=False)
        def step(col_data, col_nulls, valid, block_base, state):
            dict_keys, first, carries, overflow = state
            cols, active = _shard_active_cols(
                device_cols, nullable, sel_rpns, col_data, col_nulls, valid, n_rows
            )
            # pack group-by values into ONE int64 key; NULL packs as the
            # all-ones lane so it groups separately from every real value.
            # Values outside [0, 2^key_bits-1) cannot pack losslessly —
            # flag them into `overflow` (the host-fallback gate) instead of
            # silently merging distinct groups by truncation.
            key = jnp.zeros(n_rows, dtype=jnp.int64)
            lane_max = (1 << key_bits) - 1  # all-ones = NULL, so exclusive
            range_over = jnp.asarray(False)
            for rpn in group_rpns:
                d, nl = eval_rpn(rpn, cols, n_rows, xp=jnp)
                v = d.astype(jnp.int64)
                bad = active & ~nl & ((v < 0) | (v >= lane_max))
                range_over = range_over | jnp.any(bad)
                lane = jnp.where(nl, lane_max, v)
                key = (key << key_bits) | (lane & lane_max)
            key = jnp.where(active, key, _KEY_SENTINEL)
            # bounded sorted union: dict ∪ block keys (static shapes)
            combined = jnp.sort(jnp.concatenate([dict_keys, key]))
            fresh = jnp.concatenate(
                [jnp.array([True]), combined[1:] != combined[:-1]]
            ) & (combined < _KEY_SENTINEL)
            rank = jnp.cumsum(fresh) - 1
            local_dict = jnp.full(cap, _KEY_SENTINEL, dtype=jnp.int64)
            pos = jnp.where(fresh & (rank < cap), rank, cap)
            local_dict = local_dict.at[pos].set(combined, mode="drop")
            local_over = jnp.any(fresh & (rank >= cap))
            # global dictionary: union of every region shard's dictionary
            gathered = jax.lax.all_gather(local_dict, "regions", tiled=True)
            gsorted = jnp.sort(gathered)
            gfresh = jnp.concatenate(
                [jnp.array([True]), gsorted[1:] != gsorted[:-1]]
            ) & (gsorted < _KEY_SENTINEL)
            grank = jnp.cumsum(gfresh) - 1
            new_dict = jnp.full(cap, _KEY_SENTINEL, dtype=jnp.int64)
            gpos = jnp.where(gfresh & (grank < cap), grank, cap)
            new_dict = new_dict.at[gpos].set(gsorted, mode="drop")
            new_over = (
                overflow
                | (
                    jax.lax.psum(
                        (local_over | range_over).astype(jnp.int32), "regions"
                    )
                    > 0
                )
                | jnp.any(gfresh & (grank >= cap))
            )
            gids = jnp.searchsorted(new_dict, key).astype(jnp.int32)
            gids = jnp.clip(gids, 0, cap - 1)
            # REMAP carried slots: new keys can reshuffle the sorted
            # dictionary, so position i of the old dict moves to
            # searchsorted(new_dict, old_key).  Old sentinel slots hold
            # identity values and scatter-drop past the end.
            perm = jnp.where(
                dict_keys < _KEY_SENTINEL,
                jnp.searchsorted(new_dict, dict_keys),
                cap,
            )
            new_carries = []
            for da, carry in zip(device_aggs, carries):
                ident = da.init_carry(cap)
                remapped = tuple(
                    iv.at[perm].set(cv, mode="drop") for iv, cv in zip(ident, carry)
                )
                part = da.update(da.init_carry(cap), cols, n_rows, gids, active, cap)
                merged = []
                for kind, leaf, cur in zip(_MERGE[da.op], part, remapped):
                    leaf = _collective(kind, leaf, "regions")
                    merged.append(_combine(kind, cur, leaf))
                new_carries.append(tuple(merged))
            first_remap = jnp.full(cap, _NO_ROW, dtype=jnp.int64).at[perm].set(
                first, mode="drop"
            )
            shard_base = jax.lax.axis_index("regions").astype(jnp.int64) * n_rows
            ridx = jnp.where(
                active,
                block_base + shard_base + jnp.arange(n_rows, dtype=jnp.int64),
                _NO_ROW,
            )
            bf = _seg_extreme(ridx, gids, cap, True, _NO_ROW)
            bf = jax.lax.pmin(bf, "regions")
            new_first = jnp.minimum(first_remap, bf)
            return (new_dict, new_first, tuple(new_carries), new_over)

        # lint: allow(jit-nocache) -- compiled ONCE per evaluator in
        # __init__ (self._step/self._fin memoize the returned callable)
        return _obs.timed_jit(jax.jit(step), "mesh.grouped_step", "mesh",
                              self.ev.obs_sig)

    def init_state(self):
        dict_keys = jnp.full(self.capacity, _KEY_SENTINEL, dtype=jnp.int64)
        first = jnp.full(self.capacity, _NO_ROW, dtype=jnp.int64)
        carries = tuple(da.init_carry(self.capacity) for da in self.ev.device_aggs)
        return (dict_keys, first, carries, jnp.asarray(False))

    def run_blocks(self, blocks):
        """blocks: [(columns, n_valid), ...] in stream order — multi-block
        carry with the dictionary, first-row order and aggregate state all
        resident on device between blocks."""
        state = self.init_state()
        for b, (columns, n_valid) in enumerate(blocks):
            col_data, col_nulls, valid = _marshal_block(
                self.ev, columns, n_valid, self.total_rows
            )
            state = self._step(
                col_data, col_nulls, valid,
                np.int64(b * self.total_rows), state,
            )
        return state

    def finalize(self, state) -> dict:
        """Pull the state and order groups by FIRST OCCURRENCE in the row
        stream (the CPU dict-coded order): returns {"keys": [...],
        "counts": ..., "aggs": [per-agg leaves], "overflow": bool} with
        group axis in first-occurrence order."""
        dict_keys, first, carries, overflow = jax.tree.map(np.asarray, state)
        live = dict_keys < int(_KEY_SENTINEL)
        order = np.argsort(first[live], kind="stable")
        idx = np.nonzero(live)[0][order]
        return {
            "keys": dict_keys[idx],
            "first": first[idx],
            "aggs": [tuple(leaf[idx] for leaf in c) for c in carries],
            "overflow": bool(overflow),
        }


class ShardedTopNEvaluator:
    """Raw TopN (TableScan → Selection? → TopN) across the mesh: every region
    shard carries its own running top-K (the single-device _topn_step shape),
    and ``finalize`` merges the shards with one collective program —
    all_gather over ``regions`` then one more stable sort (top_n_executor.rs
    re-expressed as SPMD).

    Ties resolve in GLOBAL STREAM ORDER even across shards: a global row
    index rides as the final sort key, so the merged result is byte-
    comparable with the single-stream executor."""

    def __init__(self, dag: DagRequest, mesh: Mesh, rows_per_shard: int):
        self.ev = JaxDagEvaluator(dag, block_rows=rows_per_shard)
        plan = self.ev.plan
        if plan.topn is None or plan.agg is not None:
            raise ValueError("sharded TopN requires a raw TopN DAG")
        self.k = plan.topn.limit
        self.mesh = mesh
        self.rows_per_shard = rows_per_shard
        self.n_regions = mesh.shape["regions"]
        self.total_rows = rows_per_shard * self.n_regions
        self.payload_cols = list(range(len(self.ev.schema)))
        # leaves: rank, (null-rank, key) per order key, global row idx,
        # then (data, null) per payload column
        self.n_key_ops = 1 + 2 * len(self.ev.topn_rpns) + 1
        self._step = self._build_step()
        self._fin = self._build_finalize()

    def _leaf_specs(self):
        n_leaves = self.n_key_ops + 2 * len(self.payload_cols)
        return tuple(P("regions") for _ in range(n_leaves))

    def _build_step(self):
        ev = self.ev
        k = self.k
        n_rows = self.rows_per_shard
        device_cols = ev.device_cols
        nullable = ev.nullable_cols
        sel_rpns = ev.sel_rpns
        order_rpns = ev.topn_rpns
        payload_cols = self.payload_cols
        n_key_ops = self.n_key_ops

        col_specs = tuple(P("regions") for _ in device_cols)
        null_specs = tuple(P("regions") for _ in nullable)
        state_spec = self._leaf_specs()
        in_specs = (col_specs, null_specs, P("regions"), P(), state_spec)

        @_smap(self.mesh, in_specs, state_spec)
        def step(col_data, col_nulls, valid, block_base, state):
            cols, active = _shard_active_cols(
                device_cols, nullable, sel_rpns, col_data, col_nulls, valid, n_rows
            )
            rank_blk = jnp.where(active, jnp.int64(0), jnp.int64(1))
            operands_blk = [rank_blk]
            for rpn, desc in order_rpns:
                d, nl = eval_rpn(rpn, cols, n_rows, xp=jnp)
                operands_blk += _topn_key_operands(d, nl, desc)
            shard_base = jax.lax.axis_index("regions").astype(jnp.int64) * n_rows
            gidx = jnp.where(
                active,
                block_base + shard_base + jnp.arange(n_rows, dtype=jnp.int64),
                jnp.int64(2**62),
            )
            operands_blk.append(gidx)
            merged = [jnp.concatenate([s, b]) for s, b in zip(state, operands_blk)]
            idx = jnp.arange(k + n_rows, dtype=jnp.int64)
            sorted_ops = jax.lax.sort(
                merged + [idx], num_keys=n_key_ops, is_stable=True
            )
            top = [op[:k] for op in sorted_ops[:n_key_ops]]
            top_idx = sorted_ops[n_key_ops][:k]
            out = list(top)
            for j, ci in enumerate(payload_cols):
                bd, bn = cols[ci]
                sd = state[n_key_ops + 2 * j]
                sn = state[n_key_ops + 2 * j + 1]
                out.append(jnp.concatenate([sd, bd])[top_idx])
                out.append(jnp.concatenate([sn, bn])[top_idx])
            return tuple(out)

        # lint: allow(jit-nocache) -- compiled ONCE per evaluator in
        # __init__ (self._step/self._fin memoize the returned callable)
        return _obs.timed_jit(jax.jit(step), "mesh.topn_step", "mesh",
                              self.ev.obs_sig)

    def _build_finalize(self):
        k = self.k
        n_key_ops = self.n_key_ops
        n_payload = len(self.payload_cols)
        state_spec = self._leaf_specs()
        out_spec = tuple(P() for _ in range(n_key_ops + 2 * n_payload))

        # outputs are replicated by construction (all_gather then a
        # deterministic sort), which the static inference cannot prove
        # through the index gathers; tests assert the values
        @_smap(self.mesh, (state_spec,), out_spec, check=False)
        def fin(state):
            gathered = [
                jax.lax.all_gather(leaf, "regions", tiled=True) for leaf in state
            ]
            idx = jnp.arange(gathered[0].shape[0], dtype=jnp.int64)
            sorted_ops = jax.lax.sort(
                gathered[:n_key_ops] + [idx], num_keys=n_key_ops, is_stable=True
            )
            top = [op[:k] for op in sorted_ops[:n_key_ops]]
            top_idx = sorted_ops[n_key_ops][:k]
            out = list(top)
            for j in range(n_payload):
                out.append(gathered[n_key_ops + 2 * j][top_idx])
                out.append(gathered[n_key_ops + 2 * j + 1][top_idx])
            return tuple(out)

        # lint: allow(jit-nocache) -- compiled ONCE per evaluator in
        # __init__ (self._step/self._fin memoize the returned callable)
        return _obs.timed_jit(jax.jit(fin), "mesh.topn_fin", "mesh",
                              self.ev.obs_sig)

    def init_state(self):
        from ..copr.jax_eval import _np_dtype

        n = self.total_rows // self.rows_per_shard * self.k  # k per shard
        leaves = [np.ones(n, dtype=np.int64)]  # rank 1 = empty slot
        for _rpn, _desc in self.ev.topn_rpns:
            leaves.append(np.zeros(n, dtype=np.int64))
            leaves.append(np.zeros(n, dtype=_np_dtype(_rpn.eval_type)))
        leaves.append(np.full(n, 2**62, dtype=np.int64))  # global row idx
        for ci in self.payload_cols:
            leaves.append(np.zeros(n, dtype=_np_dtype(self.ev.schema[ci][0])))
            leaves.append(np.zeros(n, dtype=bool))
        return tuple(leaves)

    def run_blocks(self, blocks):
        """blocks: [(columns, n_valid), ...] in stream order."""
        state = self.init_state()
        for b, (columns, n_valid) in enumerate(blocks):
            col_data, col_nulls, valid = _marshal_block(
                self.ev, columns, n_valid, self.total_rows
            )
            state = self._step(
                col_data, col_nulls, valid, np.int64(b * self.total_rows), state
            )
        return state

    def finalize(self, state) -> dict:
        """Merge every shard's top-K into the global top-K; returns
        {"rows": n_live, "gidx": ..., "payload": [(data, nulls) per col]}."""
        out = jax.tree.map(np.asarray, self._fin(state))
        rank = out[0]
        live = int((rank == 0).sum())
        payload = []
        for j in range(len(self.payload_cols)):
            payload.append(
                (
                    out[self.n_key_ops + 2 * j][:live],
                    out[self.n_key_ops + 2 * j + 1][:live],
                )
            )
        return {
            "rows": live,
            "gidx": out[self.n_key_ops - 1][:live],
            "payload": payload,
        }


# ---------------------------------------------------------------------------
# Mesh-sharded warm serving: the shard_map twin of launch_xregion_cached
# ---------------------------------------------------------------------------


def mesh_mergeable(device_aggs) -> bool:
    """True when every aggregate's carry has a mesh merge rule — the gate in
    front of sharded warm serving (``first`` has none; those plans keep the
    single-device path)."""
    return all(da.op in _MERGE for da in device_aggs)


_FLAT_MESHES: dict = {}


def _flat_regions_mesh(mesh: Mesh) -> Mesh:
    """A 1-D ``regions``-axis view over every device of ``mesh``.  The warm
    sharded program has no use for the ``groups`` axis (its state is a small
    replicated (R, capacity) carry), so slabs shard over ALL chips."""
    devs = list(np.asarray(mesh.devices).reshape(-1))
    key = tuple(d.id for d in devs)
    m = _FLAT_MESHES.get(key)
    if m is None:
        m = _FLAT_MESHES[key] = Mesh(np.array(devs), axis_names=("regions",))
        while len(_FLAT_MESHES) > 8:
            _FLAT_MESHES.pop(next(iter(_FLAT_MESHES)))
    return m


_ZERO_SLABS: dict = {}


def _zero_slab(dev, pad: int, n_rows: int, dtype):
    """Cached per-device zero padding slabs (content is irrelevant — pad
    slabs carry ``n_valid == 0``, so the validity mask excludes every row)."""
    key = (dev.id, pad, n_rows, np.dtype(dtype).str)
    z = _ZERO_SLABS.get(key)
    if z is None:
        z = _ZERO_SLABS[key] = jax.device_put(
            np.zeros((pad, n_rows), dtype=dtype), dev)
        while len(_ZERO_SLABS) > 64:
            _ZERO_SLABS.pop(next(iter(_ZERO_SLABS)))
    return z


def _slab_pins(ev, cache, assign: dict, by_id: dict, ship, nullable,
               plan=None):
    """Per-owner-device pinned slab stacks for ONE region image.

    ``assign``: device id -> ascending block indices.  Returns {device_id:
    (data_tuple[(B_d, rows)] per ship col, nulls_tuple per nullable col)},
    each leaf COMMITTED to its owner device.  Pinned on the cache under a
    ``shardslab`` signature, so repeat batches pay zero transfer; a delta
    apply drops the pins (cache.scatter_update treats the kind as opaque)
    and they rebuild here from the updated host blocks.

    With an encoding ``plan`` (copr/encoding.py — every cache in the batch
    carries the same signature, RLE excluded), bitpacked/narrow-code lanes
    pin AS-IS: the devices hold the encoded HBM bytes and the shard_map
    program widens in-kernel with the per-region frame-of-reference row."""
    fp = tuple(sorted((did, tuple(bs)) for did, bs in assign.items()))
    enc = None if plan is None else plan.sig
    sig = ("shardslab", fp, tuple(ship), tuple(nullable), ev.block_rows, enc)

    def _canon(arr):
        # one dtype per lane across every cache in a batch (the global
        # sharded array needs uniform shards even from devices whose slabs
        # came from different regions): f64 stays, everything else rides
        # the int64 lanes the device step computes in anyway — except
        # encoded lanes, whose narrow dtype IS uniform by plan signature
        arr = np.asarray(arr)
        return arr.astype(np.int64, copy=False) if arr.dtype != np.float64 else arr

    from ..copr import encoding as _encoding

    def build(_blk):
        out = {}
        for did, idxs in assign.items():
            dev = by_id[did]
            blocks = [cache.blocks[i] for i in idxs]
            if plan is not None:
                # ONE stacked-payload assembly (encoding.stack_block_payloads,
                # shared with jax_eval._stacked_device); RLE is excluded on
                # this path so every leaf is a plain (B, rows) array
                data_np, nulls_np, _refs = _encoding.stack_block_payloads(
                    blocks, ship, nullable, plan, ev.block_rows)
                data = tuple(jax.device_put(a, dev) for a in data_np)
                nulls = tuple(jax.device_put(a, dev) for a in nulls_np)
            else:
                # decoded_data/nulls: a decode-ship of an encoded image must
                # not leave a full decode cached (the budget counts encoded)
                data = tuple(
                    jax.device_put(
                        np.stack([_canon(ev._pad(_encoding.decoded_data(b.cols[i])))
                                  for b in blocks]),
                        dev,
                    )
                    for i in ship
                )
                nulls = tuple(
                    jax.device_put(
                        np.stack([np.asarray(ev._pad(_encoding.decoded_nulls(b.cols[i]), True))
                                  for b in blocks]),
                        dev,
                    )
                    for i in nullable
                )
            out[did] = (data, nulls)
        note_blocking("device.pin:sharded_slabs")
        for leaf in jax.tree.leaves(out):
            leaf.block_until_ready()
        return out

    return cache.device_arrays(cache.blocks[0], sig, build)


def slab_assignment(caches, mesh) -> list[dict]:
    """Per-cache {device_id: block indices} over the flat mesh: honors the
    region cache's placement metadata (``owner_devices``, written by
    RegionColumnCache in sharded mode) and falls back to whole-region
    round-robin for caches without one (block caches, tests)."""
    devices = list(np.asarray(mesh.devices).reshape(-1))
    ids = {d.id for d in devices}
    out = []
    for r, cache in enumerate(caches):
        owners = getattr(cache, "owner_devices", None)
        if (owners is None or len(owners) != len(cache.blocks)
                or any(o not in ids for o in owners)):
            if len(caches) == 1:
                # a lone unplaced cache (plain block cache, cache_version
                # path): block-spread it — pinning a whole region on one
                # device while N-1 idle defeats the sharded program
                owners = [devices[b % len(devices)].id
                          for b in range(len(cache.blocks))]
            else:
                owners = [devices[r % len(devices)].id] * len(cache.blocks)
        assign: dict[int, list[int]] = {}
        for b, did in enumerate(owners):
            assign.setdefault(did, []).append(b)
        out.append(assign)
    return out


def device_slab_load(caches, mesh) -> dict[int, int]:
    """Slabs per device for a prospective batch, derived from
    :func:`slab_assignment` — THE one fold shared by the scheduler's
    padding-shed/occupancy metrics and the benches, so reported geometry
    can never diverge from what the launcher dispatches."""
    devices = list(np.asarray(mesh.devices).reshape(-1))
    load = {d.id: 0 for d in devices}
    for assign in slab_assignment(caches, mesh):
        for did, idxs in assign.items():
            load[did] += len(idxs)
    return load


def _xshard_program(ev: JaxDagEvaluator, flat: Mesh, R: int, capacity: int,
                    ship: tuple, nullable: tuple, group_cols, enc,
                    params: tuple = ()):
    """The jitted ``shard_map`` program of :func:`launch_xregion_sharded`
    over the 1-D ``regions`` mesh ``flat``: arguments are the per-ship-column
    slab stacks and null masks, slab metadata (region slot, n_valid, row
    offset), all sharded over ``regions``, then the replicated per-region
    dictionary radices and frame-of-reference rows.  Apart from the mesh it
    depends on shapes only, so it also compiles for a mesh of described
    devices (tests/test_tpu_compile.py).  The plan's literals (``params``)
    are baked in: this rung is one program per set of them, as before plans
    were split into shape and parameters (copr/plan_shape.py)."""
    device_aggs = ev.device_aggs
    sel_rpns = ev.bound_sel_rpns(params)
    track_first = bool(ev.group_rpns)
    n_rows = ev.block_rows
    cap_total = R * capacity
    in_specs = (
        tuple(P("regions") for _ in ship),
        tuple(P("regions") for _ in nullable),
        P("regions"), P("regions"), P("regions"), P(), P(),
    )

    @_smap(flat, in_specs, (P(), P()))
    def xfn(col_data, col_nulls, slab_region, n_valids, offsets, dl_arr,
            ref_arr):
        state = (
            jnp.full(cap_total, _NO_ROW, dtype=jnp.int64),
            tuple(da.init_carry(cap_total) for da in device_aggs),
        )

        def body(st, xs):
            cd, cn, r, nv, off = xs
            # per-slab in-kernel decode: the slab's region row of the
            # frame-of-reference matrix widens its bitpacked lanes
            cols = _build_cols(ship, nullable, cd, cn, n_rows, enc,
                               None if enc is None else ref_arr[r])
            local = jnp.zeros(n_rows, dtype=jnp.int64)
            for k, gi in enumerate(group_cols):
                codes, gnulls = cols[gi]
                dlen = dl_arr[r, k]
                local = local * (dlen + 1) + jnp.where(gnulls, dlen, codes)
            # region-slot-segmented gids: slab r's rows land in the
            # [r*capacity, (r+1)*capacity) segment window, so ONE fused
            # step accumulates every region's state side by side
            gids = r.astype(jnp.int64) * capacity + local
            return _fused_step(
                sel_rpns, device_aggs, cap_total, n_rows, cols, nv, gids,
                off, st, track_first=track_first,
            ), None

        # the body's output varies over "regions" (it folds this device's
        # slabs); the scan wants the initial carry typed the same way
        state = jax.tree.map(
            lambda l: jax.lax.pcast(l, ("regions",), to="varying"), state)
        state, _ = jax.lax.scan(
            body, state, (col_data, col_nulls, slab_region, n_valids, offsets)
        )
        first, carries = state
        # cross-device merge: a region's slabs may live on one device
        # (others contribute identity) or spread across several (a
        # block-sharded huge region) — the leaf-wise collective rules
        # cover both
        first = _collective("min", first, "regions")
        merged = tuple(
            tuple(
                _collective(kind, leaf, "regions")
                for kind, leaf in zip(_MERGE[da.op], c)
            )
            for da, c in zip(device_aggs, carries)
        )
        leaves = [first] + jax.tree.leaves(merged)
        return _pack_region_leaves(leaves, R, capacity)  # (R, L*, cap)

    # lint: allow(jit-nocache) -- compiled once per batch geometry: the one
    # caller, launch_xregion_sharded, keeps the result in ev._agg_fn_cache
    return _obs.timed_jit(jax.jit(xfn), "mesh.xshard", "mesh", ev.obs_sig)


def launch_xregion_sharded(ev: JaxDagEvaluator, caches, mesh: Mesh,
                           params: tuple = ()) -> XRegionPending:
    """ONE aggregation plan over R cached region images as ONE ``shard_map``
    program over EVERY device of ``mesh`` — the sharded twin of
    ``jax_eval.launch_xregion_cached``.

    Each (region, block) pair is a SLAB living on its owner device (the
    region column cache's placement: whole regions normally, block-spread
    for single huge regions).  Every device scans its local slabs with the
    same fused block step as the single-device path — per-slab ``n_valid``
    masks keep padding inert — accumulating partial states into a
    region-slot-segmented carry (capacity R×C).  Partial states then merge
    across devices with the ``_collective`` rules (`psum`/`pmin`/`pmax` over
    ICI; bitwise via gather+fold), the exact merge semantics the sharded
    evaluators above already use, and ONE packed pull serves every region.

    Raises ValueError on documented declines (non-aggregation plan, an
    aggregate with no mesh merge rule, unstable group dictionaries, empty
    cache); callers fall back to the single-device warm path per request.
    """
    from ..copr.jax_eval import xregion_specs

    _require_mesh_mergeable(ev.device_aggs)
    specs, group_cols, capacity = xregion_specs(ev, caches)
    flat = _flat_regions_mesh(mesh)
    devices = list(np.asarray(flat.devices).reshape(-1))
    by_id = {d.id: d for d in devices}
    N = len(devices)
    R = len(caches)
    ship = tuple(ev._ship_cols(group_cols))
    nullable = tuple(ev.nullable_cols)
    n_rows = ev.block_rows

    assigns = slab_assignment(caches, flat)
    per_dev_slabs = {d.id: 0 for d in devices}
    for assign in assigns:
        for did, idxs in assign.items():
            per_dev_slabs[did] += len(idxs)
    S = max(1, max(per_dev_slabs.values()))

    # encoded residency (copr/encoding.py): slab stacks mix blocks of
    # several regions on one device, so the whole batch must agree on one
    # encoding signature and RLE is excluded (run capacities differ per
    # image) — batch_plan decides and counts the decode-ship declines
    from ..copr import encoding as _encoding

    plans = _encoding.batch_plan(caches, list(ship), list(nullable),
                                 "mesh_sharded", allow_rle=False)
    enc = plans[0].sig if plans else None

    pins = [
        _slab_pins(ev, c, a, by_id, ship, nullable,
                   plan=plans[r] if plans else None)
        for r, (c, a) in enumerate(zip(caches, assigns))
    ]
    # zone-map pruning (docs/zone_maps.md): a pruned slab ships with
    # n_valid == 0 in the metadata, so its owner device scans it as pure
    # padding — the compile key, slab placement, and row offsets (global
    # row ids for first-row tracking) are untouched
    from ..copr import zone_maps as _zm

    region_keeps = []
    region_prunes = []
    bound_sel = ev.bound_sel_rpns(params)
    for cache in caches:
        ps = _zm.PruneStats()
        region_keeps.append(
            _zm.prune_blocks(cache, bound_sel, path="mesh", stats=ps))
        region_prunes.append((ps.examined, ps.pruned))

    region_offsets = []
    for cache in caches:
        nv = np.array([b.n_valid for b in cache.blocks], dtype=np.int64)
        region_offsets.append(np.concatenate([[0], np.cumsum(nv)[:-1]]).astype(np.int64))

    # per-device shard assembly: concat each device's pinned slab stacks in
    # region-major order (matching the metadata below), zero-pad to S slabs.
    # All inputs are committed to the device, so the concat runs THERE —
    # the host never touches row data on the warm path.
    from ..copr.datatypes import EvalType

    ship_dtypes = [
        np.float64 if ev.schema[i][0] == EvalType.REAL else np.int64 for i in ship
    ]
    if enc is not None:
        # encoded lanes keep their narrow dtype (zero-pad slabs must match)
        ship_dtypes = [
            np.dtype(enc[j][1]) if enc[j][0] in ("bp", "code") else ship_dtypes[j]
            for j in range(len(ship))
        ]
    meta_region = np.zeros((N, S), dtype=np.int32)
    meta_nv = np.zeros((N, S), dtype=np.int64)
    meta_off = np.zeros((N, S), dtype=np.int64)
    shard_data: list = []
    shard_nulls: list = []
    for di, dev in enumerate(devices):
        did = dev.id
        parts_d: list = [[] for _ in ship]
        parts_n: list = [[] for _ in nullable]
        si = 0
        for r, cache in enumerate(caches):
            idxs = assigns[r].get(did)
            if not idxs:
                continue
            data, nulls = pins[r][did]
            for j in range(len(ship)):
                parts_d[j].append(data[j])
            for j in range(len(nullable)):
                parts_n[j].append(nulls[j])
            keep_r = region_keeps[r]
            for b in idxs:
                meta_region[di, si] = r
                meta_nv[di, si] = (
                    0 if keep_r is not None and not keep_r[b]
                    else cache.blocks[b].n_valid)
                meta_off[di, si] = region_offsets[r][b]
                si += 1
        pad = S - si

        def _cat(parts, dtype):
            if pad:
                parts = parts + [_zero_slab(dev, pad, n_rows, dtype)]
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        shard_data.append([_cat(parts_d[j], ship_dtypes[j]) for j in range(len(ship))])
        shard_nulls.append([_cat(parts_n[j], np.bool_) for j in range(len(nullable))])

    ns = NamedSharding(flat, P("regions"))
    ns_rep = NamedSharding(flat, P())
    col_data = tuple(
        jax.make_array_from_single_device_arrays(
            (N * S, n_rows), ns, [shard_data[di][j] for di in range(N)]
        )
        for j in range(len(ship))
    )
    col_nulls = tuple(
        jax.make_array_from_single_device_arrays(
            (N * S, n_rows), ns, [shard_nulls[di][j] for di in range(N)]
        )
        for j in range(len(nullable))
    )
    slab_region = jax.device_put(meta_region.reshape(N * S), ns)
    n_valids = jax.device_put(meta_nv.reshape(N * S), ns)
    offsets = jax.device_put(meta_off.reshape(N * S), ns)
    dl_arr = jax.device_put(
        np.array([s[1] for s in specs], dtype=np.int64).reshape(R, len(group_cols)),
        ns_rep,
    )
    ref_arr = jax.device_put(
        (np.stack([np.asarray(p.refs) for p in plans])
         if plans else np.zeros((R, len(ship)), dtype=np.int64)),
        ns_rep,
    )

    key = ("xshard", tuple(d.id for d in devices), S, R, capacity,
           ship, nullable, len(group_cols), enc, params)
    fn = ev._agg_fn_cache.get(key)
    if fn is None:
        fn = ev._agg_fn_cache[key] = _xshard_program(
            ev, flat, R, capacity, ship, nullable, group_cols, enc, params)
        xkeys = [k for k in ev._agg_fn_cache if isinstance(k, tuple)
                 and k and k[0] == "xshard"]
        while len(xkeys) > 16:
            ev._agg_fn_cache.pop(xkeys.pop(0))

    packed = fn(col_data, col_nulls, slab_region, n_valids, offsets, dl_arr,
                ref_arr)
    pending = XRegionPending(ev, specs, capacity, packed, order=None,
                             prunes=region_prunes)
    # observatory encoding label for the riders' profiles
    pending.obs_encoding = "encoded" if plans else "plain"
    return pending


def run_xregion_sharded(ev: JaxDagEvaluator, caches, mesh: Mesh,
                        params: tuple = ()):
    """launch + finalize in one step (tests / single-batch callers)."""
    return launch_xregion_sharded(ev, caches, mesh, params).finalize()


class MeshServingRunner:
    """Endpoint-facing mesh execution of an eligible aggregation DAG.

    The scale-out analog of region sharding (``raftstore/src/coprocessor/
    split_check/``): ``Endpoint`` hands this runner the same MVCC scan source
    the single-device path uses; rows are decoded on host into super-blocks,
    sharded over the ``regions`` axis, and the group state stays sharded over
    ``groups`` between blocks.  Group-id assignment and finalization reuse the
    single-device evaluator's host code, so the encoded ``SelectResponse`` is
    byte-identical to the one-device (and CPU) answer.
    """

    def __init__(self, dag: DagRequest, mesh: Mesh, rows_per_shard: int = 1024):
        from math import gcd

        from ..copr.jax_eval import _analyze

        # eligibility first, before any evaluator construction: the rejection
        # path must stay cheap (Endpoint probes every device-eligible DAG)
        if _analyze(dag).agg is None:
            raise ValueError("mesh serving requires an aggregation DAG")
        self.mesh = mesh
        self.rows_per_shard = rows_per_shard
        self.n_groups = mesh.shape["groups"]
        # smallest multiple of n_groups >= 16 (doubling alone never reaches
        # divisibility for a non-power-of-two groups axis)
        cap = 16 * self.n_groups // gcd(16, self.n_groups)
        self.sharded = ShardedDagEvaluator(dag, mesh, rows_per_shard, capacity=cap)
        self.total_rows = self.sharded.total_rows
        # decode/gid/finalize machinery at super-block granularity
        self.decode_ev = JaxDagEvaluator(dag, block_rows=self.total_rows)
        # observatory profile key: cold mesh serves record under the same
        # plan sig as every other path (docs/observatory.md)
        self.obs_sig = self.decode_ev.obs_sig
        self.obs_desc = self.decode_ev.obs_desc

    def _grow(self, state, n_groups: int):
        from ..copr.jax_eval import _grow_carry

        cap = self.sharded.capacity
        while n_groups > cap:
            cap *= 2
        first, carries = jax.tree.map(np.asarray, state)
        new_first = np.full(cap, _NO_ROW, dtype=np.int64)
        new_first[: len(first)] = first
        new_carries = tuple(
            _grow_carry(da, c, cap)
            for da, c in zip(self.sharded.ev.device_aggs, carries)
        )
        self.sharded = ShardedDagEvaluator(
            self.decode_ev.dag, self.mesh, self.rows_per_shard, capacity=cap
        )
        return (jnp.asarray(new_first), new_carries)

    def run(self, source, cache=None, params: tuple = ()) -> "SelectResponse":
        """Same signature as JaxDagEvaluator.run; the block cache is a
        single-device HBM concept and is ignored here (Endpoint routes cached
        requests down the single-device path).  The runner is built from the
        whole plan, literals included (keyed by its wire bytes), so a request
        brings it no ``params``."""
        if params:
            raise ValueError("a mesh runner holds its plan's literals")
        from ..copr.groupby import GroupDict
        from ..copr.jax_eval import _ZERO_GIDS

        ev = self.decode_ev
        total = self.total_rows
        groups = GroupDict()
        state = self.sharded.init_state()
        block_base = 0
        for cols, n_valid in ev._decode_blocks(source):
            if ev.group_rpns:
                gids, n_groups = ev._assign_gids(cols, n_valid, groups)
                if n_groups > self.sharded.capacity:
                    state = self._grow(state, n_groups)
            else:
                gids = _ZERO_GIDS.setdefault(total, np.zeros(total, dtype=np.int32))
            need = set(ev.device_cols) | set(ev.nullable_cols)
            columns = {
                i: (ev._pad(cols[i].data), ev._pad(cols[i].nulls, True))
                for i in need
            }
            col_data, col_nulls, valid = _marshal_block(ev, columns, n_valid, total)
            state = self.sharded.step(col_data, col_nulls, valid, gids, state,
                                      block_base=block_base)
            block_base += total
        n_slots = len(groups) if ev.group_rpns else 1
        state_np = jax.tree.map(np.asarray, state)
        resp = ev._finalize_agg(state_np, n_slots, lambda r: groups.rows[r])
        resp._obs_path = "mesh"  # observatory path marker
        return resp
