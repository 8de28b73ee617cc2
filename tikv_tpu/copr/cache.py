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


@functools.lru_cache(maxsize=1)
def _set_rows_program():
    import jax

    def set_rows(stacks, rows, k):
        return jax.tree.map(
            lambda s, r: jax.lax.dynamic_update_index_in_dim(s, r, k, 0), stacks, rows)

    return jax.jit(set_rows)


def _set_rows(stacks, rows, i: int):
    """``stacks`` (a tree of ``(B, ...)`` device arrays) with row ``i`` of
    each replaced by the matching leaf of ``rows``: new device arrays in one
    dispatch (the old ones are left whole for whoever holds them).  One
    program per tree of shapes and dtypes, whatever ``i``."""
    import jax

    rows = jax.tree.map(lambda s, r: np.asarray(r).astype(s.dtype, copy=False),
                        stacks, rows)
    return _set_rows_program()(stacks, rows, np.int32(i))


def _padded(arr, rows: int, fill) -> np.ndarray:
    arr = np.asarray(arr)
    if len(arr) == rows:
        return arr
    if arr.dtype == object:
        ext = np.empty(rows - len(arr), dtype=object)
        ext[:] = b""
        return np.concatenate([arr, ext])
    return np.concatenate([arr, np.full(rows - len(arr), fill, dtype=arr.dtype)])


def _patch_plain_stack(entry, sig, blocks, rows: list):
    """sig = ("stacked", ship_cols, nullable, block_rows): the rows of the
    changed blocks, padded as ``jax_eval._stacked_device`` pads them."""
    from .encoding import decoded_data, decoded_nulls

    _, ship_cols, nullable, n = sig
    for r in rows:
        cols = blocks[r].cols
        entry = _set_rows(entry, (
            tuple(_padded(decoded_data(cols[ci]), n, 0) for ci in ship_cols),
            tuple(_padded(decoded_nulls(cols[ci]), n, True) for ci in nullable)), r)
    return entry


def _patch_enc_stack(entry, sig, plan, blocks, rows: list):
    """sig = ("stackedenc", ship, nullable, block_rows, plan sig, null sig):
    the changed blocks' encoded payloads, as ``stack_block_payloads`` lays
    them out."""
    from .encoding import block_null_payload, block_payload

    _, ship_cols, nullable, n = sig[:4]
    stacks = (entry[0], entry[1])
    for r in rows:
        cols = blocks[r].cols
        stacks = _set_rows(stacks, (
            tuple(tuple(block_payload(cols[ci], n)) if plan.sig[j][0] == "rle"
                  else block_payload(cols[ci], n) for j, ci in enumerate(ship_cols)),
            tuple(block_null_payload(cols[ci], n) for ci in nullable)), r)
    return stacks[0], stacks[1], entry[2]


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


    def successor(self, blocks: list, changed: set,
                  account: bool = True) -> "ColumnBlockCache":
        """The next version of this cache, over ``blocks``: the blocks whose
        index is in ``changed`` are new objects, every other block is this
        version's own (shared, pins and all, and never written again).  This
        version stays whole for the readers that hold it.

        The cache-wide pins live on block 0, which the successor always gets
        as a fresh object: the stacked layouts (plain and encoded) carry over
        with ONLY the changed blocks' rows re-placed on the device, one
        dispatch per changed block (``_set_rows``: compiled once per layout);
        a stacked pin whose block count or encoding no longer matches, the
        n_valid/offset pin and every other layout drop and rebuild lazily.
        Changed blocks start with no pins.  ``account``: the pins this
        version gives up and the ones it re-places move the observatory's
        accounting (not for a version made for one reader beside the
        image's own)."""
        from . import encoding as _enc

        new = ColumnBlockCache(self.key)
        new.enc_version = self.enc_version
        new.filled = True
        new.folded = True
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
            if kind == "stacked" and same_shape:
                out = _patch_plain_stack(entry, sig, blocks, rows_changed)
            elif kind == "stackedenc" and same_shape:
                plan = _enc.device_plan(new, sig[1], sig[2])
                if plan is not None and (plan.sig, plan.null_sig) == (sig[4], sig[5]) \
                        and np.array_equal(np.asarray(plan.refs), np.asarray(entry[2])):
                    out = _patch_enc_stack(entry, sig, plan, blocks, rows_changed)
            released.append((sig, entry))
            if out is not None:
                kept[sig] = out
                added.append((sig, out))
        new.blocks[0].device.update(kept)
        for i in changed:
            if 0 < i < len(self.blocks):
                with self._mu:
                    released.extend(self.blocks[i].device.items())
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
