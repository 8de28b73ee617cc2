"""Columnar block cache — the TPU-first re-expression of the coprocessor cache.

The reference caches *response bytes* keyed by region version
(``src/coprocessor/cache.rs:10``): a repeated identical request on an
unchanged region skips execution.  A TPU evaluator wants a deeper cache: the
expensive shared work is MVCC scan + row→column decode + host→device
transfer, and it is the same for EVERY query over that data.  So this cache
holds decoded column blocks keyed by (region/range, data-version ts):

* any query shape over the cached range skips scan+decode (CPU and TPU both)
* the device path additionally pins each block's arrays in HBM on first use,
  so steady-state queries are pure on-device compute — no host→device traffic

Invalidation follows the reference's rule: the key includes the region's data
version (apply index / max commit ts), so any write produces a new key.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np

from ..analysis import bufsan as _bufsan
from ..analysis.sanitizer import make_lock


# per-block pinned signatures: stacked + nvoff + zone layout + sharded slab
# stacks must coexist on a warm image without evicting each other
_MAX_DEVICE_SIGS = 6


def _pin_kind(sig: tuple) -> str:
    """Pin-signature family for the observatory's HBM watermarks: named
    kinds lead their sig tuple ("stackedenc", "blockenc", "nvoff",
    "zone_layout", "shardslab"); the plain stacked pin leads with its
    column tuple."""
    return sig[0] if sig and isinstance(sig[0], str) else "stacked"


def _entry_nbytes(entry) -> int:
    """Device bytes of one pinned entry (zone layouts report their ``dev``
    tree) — the same figure device_nbytes() sums."""
    import jax

    tree = getattr(entry, "dev", entry)
    return sum(int(getattr(leaf, "nbytes", 0) or 0)
               for leaf in jax.tree.leaves(tree))


# a changed block's edit sends the device its new rows' values padded to the
# least of these row counts that holds them (one program each, built beside
# the pin it edits); more new rows than the largest, or than a quarter of the
# block, and deletes or inserts that are not one run each, ship the block whole
_EDIT_BUCKETS = (64, 1024)
_NO_ROW = np.int32(1 << 30)  # the padding of an edit's row lists: past any row


def _edit_buckets(block_rows: int) -> tuple:
    return tuple(k for k in _EDIT_BUCKETS if 4 * k <= block_rows) or (
        (block_rows // 4,) if block_rows >= 4 else ())


@functools.lru_cache(maxsize=None)
def _edit_program(k: int):
    """The jitted edit of one block's row in every leaf of a stacked pin, and
    of the n_valid/offset pin, for an edit of one run of deleted rows, one
    run of inserted rows and up to ``k`` rows of new values.  ``idx`` is
    (block, new n_valid, delete at, deleted, insert at, inserted, -, -) and
    then the ``k`` rows that take new values (past the block where unused);
    ``ivals``/``fvals`` hold those values, a row per integer (or bool) and
    per float leaf.  The kept rows close up past the deleted run and move
    down past the inserted one (shifts, not gathers: a delete is a stable
    compaction), the new values are scattered in, and what lies past the
    new ``n_valid`` stays padding (0, nulls True), as a fresh pin pads it.
    Old arrays are read, never written (no donation)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def edit(leaves, nv, off, idx, ivals, fvals):
        b, new_nv, p, d, q, c = (idx[i] for i in range(6))
        fixed = idx[8:]
        out = []
        ii = fi = 0
        for leaf in leaves:
            n = leaf.shape[1]
            j = jnp.arange(n, dtype=jnp.int32)
            pad = jnp.full((n,), leaf.dtype == jnp.bool_, dtype=leaf.dtype)
            row = lax.dynamic_index_in_dim(leaf, b, 0, keepdims=False)
            row = jnp.where(j < p, row, lax.dynamic_slice(jnp.concatenate([row, pad]), (d,), (n,)))
            row = jnp.where(j < q, row, lax.dynamic_slice(jnp.concatenate([pad, row]), (n - c,), (n,)))
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                v, fi = fvals[fi].astype(leaf.dtype), fi + 1
            else:
                v, ii = ivals[ii].astype(leaf.dtype), ii + 1
            row = row.at[fixed].set(v, mode="drop")
            out.append(lax.dynamic_update_index_in_dim(leaf, row, b, 0))
        nv = nv.at[b].set(new_nv.astype(nv.dtype))
        off = jnp.concatenate([jnp.zeros(1, nv.dtype), jnp.cumsum(nv)[:-1]])
        return tuple(out), nv, off

    return jax.jit(edit)


@functools.lru_cache(maxsize=1)
def _block_program():
    """The jitted re-placement of one block's row in every leaf, whole, and
    of its n_valid/offset entries: for an edit too large for the buckets."""
    import jax
    import jax.numpy as jnp

    def place(leaves, nv, off, b, new_nv, rows):
        out = tuple(jax.lax.dynamic_update_index_in_dim(s, r, b, 0)
                    for s, r in zip(leaves, rows))
        nv = nv.at[b].set(new_nv)
        off = jnp.concatenate([jnp.zeros(1, nv.dtype), jnp.cumsum(nv)[:-1]])
        return out, nv, off

    return jax.jit(place)


class BlockEdit:
    """One changed block of a fold, in rows of the block: which of the old
    block's rows are deleted (``dels``, its own positions, sorted), where the
    new block's inserted rows are (``ins``) and where its inserted and
    updated rows are (``fixed``), both sorted positions of the new block.
    The values of the ``fixed`` rows are read off the new block."""

    __slots__ = ("old_nv", "new_nv", "dels", "ins", "fixed")

    def __init__(self, old_nv: int, new_nv: int, dels, ins, fixed):
        self.old_nv, self.new_nv = int(old_nv), int(new_nv)
        self.dels = np.asarray(dels, dtype=np.int32)
        self.ins = np.asarray(ins, dtype=np.int32)
        self.fixed = np.asarray(fixed, dtype=np.int32)

    def bucket(self, buckets: tuple) -> int | None:
        """The least bucket that holds the edit's new values, or None where
        none does or its deletes or inserts are not one run each (the block
        then ships whole)."""
        for a in (self.dels, self.ins):
            if len(a) and int(a[-1]) - int(a[0]) + 1 != len(a):
                return None
        return next((k for k in buckets if len(self.fixed) <= k), None)

    def idx(self, block: int, k: int) -> np.ndarray:
        """``_edit_program``'s ``idx`` for this edit of block ``block``."""
        out = np.full(8 + k, _NO_ROW, dtype=np.int32)
        p = int(self.dels[0]) if len(self.dels) else 0
        q = int(self.ins[0]) if len(self.ins) else 0
        out[:8] = (block, self.new_nv, p, len(self.dels), q, len(self.ins), 0, 0)
        out[8:8 + len(self.fixed)] = self.fixed
        return out


def _edit_vals(cols, sig, encoded: bool, rows, k: int, leaves) -> tuple:
    """The values of ``rows`` of a block's columns as each leaf of a stacked
    pin holds them, ``k`` a leaf: one int64 matrix for the integer and bool
    leaves and one float64 matrix for the float ones."""
    ints, flts = [], []
    for (ci, what), leaf in zip(_leaf_cols(sig), leaves):
        v = _row_values(cols[ci], what, encoded, rows)
        if np.issubdtype(leaf.dtype, np.floating):
            flts.append(_rows_padded(v, k, np.float64, 0))
        else:
            ints.append(_rows_padded(v, k, np.int64, 0))
    return (np.stack(ints) if ints else np.zeros((0, k), dtype=np.int64),
            np.stack(flts) if flts else np.zeros((0, k), dtype=np.float64))


def _rows_padded(a, k: int, dtype, fill) -> np.ndarray:
    out = np.full(k, fill, dtype=dtype)
    out[: len(a)] = a
    return out


def _leaf_cols(sig) -> list:
    """Per leaf of a stacked pin without run-length leaves, in
    ``jax.tree.leaves`` order: (column index, "data" | "nulls")."""
    return [(ci, "data") for ci in sig[1]] + [(ci, "nulls") for ci in sig[2]]


def _row_values(col, what: str, encoded: bool, rows: np.ndarray):
    """Rows ``rows`` of one block column as its pinned leaf holds them: the
    narrow lane of an encoded pin (``block_payload``), the decoded values of
    a plain one (``decoded_data``); nulls likewise."""
    from .encoding import EncodedColumn

    enc = isinstance(col, EncodedColumn)
    if what == "nulls":
        if enc and col.kind == "rle":
            return col.run_nulls[col._run_index(rows)]
        return np.asarray(col._nulls if enc else col.nulls)[rows]
    if enc:
        return col.packed[rows] if (encoded and col.kind == "bp") else col._decode_rows(rows)
    return np.asarray(col.data)[rows]


def _whole_rows(sig, plan, cols, leaves) -> tuple:
    """One block's row of every leaf of a stacked pin, built whole on the
    host (``stack_block_payloads``'s and ``_stacked_device``'s layouts)."""
    from .encoding import block_null_payload, block_payload, decoded_data, decoded_nulls

    n = sig[3]
    if plan is None:
        rows = [_padded(decoded_data(cols[ci]), n, 0) for ci in sig[1]]
        rows += [_padded(decoded_nulls(cols[ci]), n, True) for ci in sig[2]]
    else:
        rows = []
        for j, ci in enumerate(sig[1]):
            p = block_payload(cols[ci], n)
            rows += list(p) if plan.sig[j][0] == "rle" else [p]
        rows += [block_null_payload(cols[ci], n) for ci in sig[2]]
    return tuple(np.asarray(r).astype(leaf.dtype, copy=False) for r, leaf in zip(rows, leaves))


def warm_edits(leaves, n_blocks: int, block_rows: int) -> None:
    """Build the programs that re-place a changed block of a pin of these
    leaves (every edit bucket, and the whole block), where the pin is built,
    so that a fold compiles nothing.  Compiled ahead of time from the
    shapes: nothing is placed on the device or run there."""
    import jax

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    stacks = tuple(spec(leaf.shape, leaf.dtype) for leaf in leaves)
    nv = spec((n_blocks,), np.int64)
    n_flt = sum(1 for leaf in leaves if np.issubdtype(leaf.dtype, np.floating))
    for k in _edit_buckets(block_rows):
        _edit_program(k).lower(stacks, nv, nv, spec((8 + k,), np.int32),
                               spec((len(leaves) - n_flt, k), np.int64),
                               spec((n_flt, k), np.float64)).compile()
    _block_program().lower(stacks, nv, nv, spec((), np.int32), spec((), np.int64),
                           tuple(spec(leaf.shape[1:], leaf.dtype) for leaf in leaves)).compile()


def _replace_rows(data_nulls, sig, plan, blocks, rows, edits, nv, off, placed):
    """``data_nulls`` (a stacked pin's two trees) and the n_valid/offset
    arrays with the changed blocks ``rows`` re-placed, each by its edit where
    its ``BlockEdit`` fits a bucket and the pin has no run-length leaf, else
    whole; ``placed`` notes how each block went (a block any pin shipped
    whole is ``block``)."""
    import jax

    leaves, treedef = jax.tree.flatten(data_nulls)
    runs = plan is not None and any(d[0] == "rle" for d in plan.sig + plan.null_sig)
    buckets = () if runs else _edit_buckets(sig[3])
    for r in rows:
        e = (edits or {}).get(r)
        k = e.bucket(buckets) if e is not None else None
        cols = blocks[r].cols
        if k is not None:
            leaves, nv, off = _edit_program(k)(
                tuple(leaves), nv, off, e.idx(r, k),
                *_edit_vals(cols, sig, plan is not None, e.fixed, k, leaves))
            placed.setdefault(r, "edit")
        else:
            leaves, nv, off = _block_program()(
                tuple(leaves), nv, off, np.int32(r), np.int64(blocks[r].n_valid),
                _whole_rows(sig, plan, cols, leaves))
            placed[r] = "block"
    return tuple(jax.tree.unflatten(treedef, list(leaves))), nv, off


def nv_off(blocks) -> tuple:
    """The n_valid/offset pin's host arrays for ``blocks``."""
    nv = np.array([b.n_valid for b in blocks], dtype=np.int64)
    return nv, np.concatenate([[0], np.cumsum(nv)[:-1]]).astype(np.int64)


def _count_place(how: str, n: int) -> None:
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_region_cache_fold_place_total",
        "changed blocks a fold or a fork re-placed on the device, by how: "
        "edit (the changed rows) or block (the block whole)",
    ).inc(n, how=how)


def _padded(arr, rows: int, fill) -> np.ndarray:
    arr = np.asarray(arr)
    if len(arr) == rows:
        return arr
    if arr.dtype == object:
        ext = np.empty(rows - len(arr), dtype=object)
        ext[:] = b""
        return np.concatenate([arr, ext])
    return np.concatenate([arr, np.full(rows - len(arr), fill, dtype=arr.dtype)])


@dataclass
class _Block:
    cols: list  # list[Column] (host)
    n_valid: int
    device: dict = field(default_factory=dict)  # (cols-sig) -> (data, nulls) jnp lists
    # per-column prune statistics, built lazily by zone_maps.ensure_zones;
    # None = not built yet (fresh fills / structural repacks start here)
    zones: dict | None = None


class ColumnBlockCache:
    """Decoded blocks for one (range, version) — build once, evaluate many."""

    def __init__(self, key=None):
        self.key = key
        self.blocks: list[_Block] = []
        self.filled = False
        # sharded placement metadata (RegionColumnCache in mesh mode): one
        # owner device id per block; None = single-device (default-device
        # pins).  parallel.mesh.launch_xregion_sharded reads this to pin
        # each slab on its owner.
        self.owner_devices: list[int] | None = None
        # bumped whenever column encodings change (fill-time encode, delta
        # demotion, code-lane widening) — the device-plan memo and the
        # encoded pin signatures key on it (copr/encoding.py)
        self.enc_version = 0
        # a version made by ``successor``: its image takes writes, so
        # layouts rebuilt from the whole image per version (the zone
        # layout) are not worth building for it
        self.folded = False
        # a region image's version: a fold may make its successor, so the
        # programs that re-place a changed block are built with its stacked
        # pins (``warm_edits``)
        self.takes_folds = False
        self._mu = make_lock("copr.block_cache")

    def add(self, cols, n_valid: int) -> None:
        self.blocks.append(_Block(cols, n_valid))

    def __iter__(self):
        return iter((b.cols, b.n_valid) for b in self.blocks)

    @property
    def total_rows(self) -> int:
        return sum(b.n_valid for b in self.blocks)

    def device_arrays(self, block: _Block, sig: tuple, build) -> tuple:
        """Per-block device arrays for a plan signature, pinned on first use.
        Bounded per block: each distinct signature pins a full copy, so old
        signatures are dropped LRU-style once _MAX_DEVICE_SIGS accumulate.
        Pin/unpin byte deltas feed the observatory's per-path HBM
        watermarks (docs/observatory.md)."""
        with self._mu:
            hit = block.device.get(sig)
            if hit is not None:
                # touch for LRU order
                block.device.pop(sig)
                block.device[sig] = hit
                return hit
        built = build(block)
        with self._mu:
            added = sig not in block.device
            block.device.setdefault(sig, built)
            dropped = []
            while len(block.device) > _MAX_DEVICE_SIGS:
                old_sig = next(iter(block.device))
                dropped.append((old_sig, block.device.pop(old_sig)))
            out = block.device[sig]
        if added and self.takes_folds and _pin_kind(sig) in ("stacked", "stackedenc"):
            import jax

            warm_edits(jax.tree.leaves((built[0], built[1])), len(self.blocks), sig[3])
        if added or dropped:
            from .observatory import OBSERVATORY

            if added:
                OBSERVATORY.note_pin(_pin_kind(sig), _entry_nbytes(built))
                # pins are exposures: the host arrays behind them never
                # change (a fold makes a new version); a pin whose sample
                # fails at drop took a write behind the cache's back
                _bufsan.export("device_pin", built, site="cache.device_arrays")
            for old_sig, entry in dropped:
                OBSERVATORY.note_pin(_pin_kind(old_sig), -_entry_nbytes(entry))
                _bufsan.release(entry, site="cache.device_arrays.lru")
        return out

    def nbytes(self) -> int:
        """RESIDENT byte footprint of the blocks — encoded bytes for
        encoded columns (docs/compressed_columns.md), the decoded-array
        footprint otherwise.  Budgets and gauges use this figure: encoded
        images cost what their payload costs, which is what multiplies
        warm capacity under a fixed byte budget."""
        from .encoding import column_nbytes

        return sum(column_nbytes(c) for b in self.blocks for c in b.cols)

    def nbytes_decoded(self) -> int:
        """What the blocks WOULD cost fully decoded — the numerator of the
        compression-ratio gauge."""
        from .encoding import column_decoded_nbytes

        return sum(column_decoded_nbytes(c) for b in self.blocks for c in b.cols)

    def device_nbytes(self) -> int:
        """TRUE bytes currently pinned on devices for this cache, summed
        over every pinned signature's arrays (zone layouts report their
        ``dev`` tree).  This is the figure behind
        ``tikv_coprocessor_region_cache_device_pinned_bytes`` — with
        encoded residency it reflects the narrow/encoded payloads actually
        in HBM, not a host-side proxy."""
        import jax

        total = 0
        with self._mu:
            for b in self.blocks:
                for entry in b.device.values():
                    tree = getattr(entry, "dev", entry)
                    for leaf in jax.tree.leaves(tree):
                        total += int(getattr(leaf, "nbytes", 0) or 0)
        return total

    def clear_blocks(self) -> None:
        """Drop every block AND its pinned device copies.  The one correct
        way to discard blocks: a raw ``blocks.clear()`` would strand the
        pinned entries' bytes in the observatory's HBM gauges forever
        (the arrays themselves are freed by GC; the accounting is not)."""
        self.drop_device()
        self.blocks.clear()

    def drop_device(self) -> None:
        """Unpin every device copy; host blocks stay.  The next query
        re-transfers from host (no decode)."""
        with self._mu:
            dropped = [
                (sig, entry)
                for b in self.blocks
                for sig, entry in b.device.items()
            ]
            for b in self.blocks:
                b.device.clear()
        if dropped:
            from .observatory import OBSERVATORY

            for sig, entry in dropped:
                OBSERVATORY.note_pin(_pin_kind(sig), -_entry_nbytes(entry))
                _bufsan.release(entry, site="cache.drop_device")


    def successor(self, blocks: list, changed: set, account: bool = True,
                  edits: dict | None = None) -> "ColumnBlockCache":
        """The next version of this cache, over ``blocks``: the blocks whose
        index is in ``changed`` are new objects, every other block is this
        version's own (shared, pins and all, and never written again).  This
        version stays whole for the readers that hold it.

        The cache-wide pins live on block 0, which the successor always gets
        as a fresh object.  The stacked layouts (plain and encoded) carry
        over with only the changed blocks re-placed on the device, one
        dispatch per changed block that also updates the n_valid/offset pin,
        which carries over with them: a block with a ``BlockEdit`` in
        ``edits`` small enough for an edit bucket sends the device its
        changed rows alone (``_edit_program``), any other ships its row of
        each leaf whole (``_block_program``).  A stacked pin whose block
        count or encoding no longer matches, and every other layout, drop
        and rebuild lazily.  Changed blocks start with no pins of their own.
        ``account``: the pins this version gives up and the ones it
        re-places move the observatory's accounting (not for a version made
        for one reader beside the image's own)."""
        import jax

        from . import encoding as _enc

        new = ColumnBlockCache(self.key)
        new.enc_version = self.enc_version
        new.filled = True
        new.folded = True
        new.takes_folds = True
        old0 = self.blocks[0] if self.blocks else None
        if blocks and old0 is not None and blocks[0] is old0:
            blocks = [_Block(old0.cols, old0.n_valid, zones=old0.zones)] + list(blocks[1:])
        new.blocks = list(blocks)
        if old0 is None or not blocks:
            return new
        same_shape = len(blocks) == len(self.blocks)
        rows_changed = sorted(i for i in changed if i < len(blocks))
        kept, released, added = {}, [], []
        with self._mu:
            entries = list(old0.device.items())
        nvoff = next(((sig, e) for sig, e in entries if sig and sig[0] == "nvoff"), None)
        nv, off = nvoff[1] if nvoff is not None else nv_off(self.blocks)
        placed: dict[int, str] = {}
        for sig, entry in entries:
            kind = sig[0] if sig and isinstance(sig[0], str) else None
            out = None
            if kind is None or kind == "blockenc":
                # block 0's own per-block pin: good while block 0 is
                if 0 in changed:
                    released.append((sig, entry))
                else:
                    kept[sig] = entry
                continue
            if kind == "nvoff":
                released.append((sig, entry))
                continue
            plan = None
            if kind == "stackedenc" and same_shape:
                plan = _enc.device_plan(new, sig[1], sig[2])
                if plan is None or (plan.sig, plan.null_sig) != (sig[4], sig[5]) \
                        or not np.array_equal(np.asarray(plan.refs), np.asarray(entry[2])):
                    kind = None
            if kind in ("stacked", "stackedenc") and same_shape:
                data_nulls, nv, off = _replace_rows(
                    (entry[0], entry[1]), sig, plan, blocks, rows_changed, edits, nv, off,
                    placed)
                out = data_nulls + tuple(entry[2:])
            released.append((sig, entry))
            if out is not None:
                kept[sig] = out
                added.append((sig, out))
        if nvoff is not None and same_shape:
            # the n_valid/offset pin carries over: edited beside the stacked
            # pins, or, with none edited, made from the new blocks' counts
            if not placed:
                nv, off = jax.device_put(nv_off(blocks))
            kept[nvoff[0]] = (nv, off)
            added.append((nvoff[0], (nv, off)))
        new.blocks[0].device.update(kept)
        for i in changed:
            if 0 < i < len(self.blocks):
                with self._mu:
                    released.extend(self.blocks[i].device.items())
        for how in ("edit", "block"):
            n = sum(1 for h in placed.values() if h == how)
            if n:
                _count_place(how, n)
        if account and (released or added):
            from .observatory import OBSERVATORY

            for sig, entry in released:
                OBSERVATORY.note_pin(_pin_kind(sig), -_entry_nbytes(entry))
                _bufsan.release(entry, site="cache.successor")
            for sig, entry in added:
                OBSERVATORY.note_pin(_pin_kind(sig), _entry_nbytes(entry))
                _bufsan.export("device_pin", entry, site="cache.successor")
        return new


class CopCache:
    """Top-level cache registry keyed by (region_id, range, version)."""

    def __init__(self, max_entries: int = 64):
        self.max_entries = max_entries
        self._entries: dict = {}
        self._order: list = []
        self._mu = make_lock("copr.cop_cache")

    def get_or_create(self, key) -> ColumnBlockCache:
        with self._mu:
            e = self._entries.get(key)
            if e is None:
                e = ColumnBlockCache(key)
                self._entries[key] = e
                self._order.append(key)
                while len(self._order) > self.max_entries:
                    old = self._order.pop(0)
                    del self._entries[old]
            else:
                # LRU touch so hot entries survive cold churn
                self._order.remove(key)
                self._order.append(key)
            return e
