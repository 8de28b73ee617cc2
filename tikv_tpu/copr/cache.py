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
                # pins are exposures: the host arrays behind them must only
                # change through scatter_update (which re-registers); a pin
                # whose sample fails at drop took a bypass write
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

    def scatter_update(self, updates: dict) -> None:
        """Patch pinned device arrays in place after an in-place host update.

        ``updates``: block_idx -> (row_positions int array, {col_idx:
        (values ndarray, nulls ndarray)}).  Host column arrays must already
        hold the new values.  Understands the two pinned layouts the
        evaluators build — the per-cache stacked arrays and per-block column
        lists — and patches them with ``.at[].set`` scatters (a device-side
        op; the base arrays never round-trip to host).  Any other signature
        (zone layouts, mesh ``shardslab`` stacks; nvoff is kept — row counts
        are unchanged) is dropped so it rebuilds from the updated host
        blocks on its owner device."""
        from . import zone_maps as _zm

        released, repinned = [], []
        with self._mu:
            for bi, blk in enumerate(self.blocks):
                upd = updates.get(bi)
                if upd is not None and blk.zones is not None:
                    # widen the block's zone map with the incoming values —
                    # stale-but-sound maintenance (docs/zone_maps.md); the
                    # host columns already hold these values
                    _zm.fold_update(blk.zones, upd[1])
                for sig in list(blk.device):
                    kind = sig[0]
                    if kind == "nvoff":
                        continue  # in-place updates never change row counts
                    if kind in ("stackedenc", "blockenc"):
                        # encoded pins hold narrow/run payloads: a decoded-
                        # domain scatter cannot patch them in place (the
                        # ref/run structure lives in the encoding) — drop,
                        # and the next serve re-pins from the updated host
                        # payload (which try_patch/demote kept truthful)
                        released.append(blk.device.pop(sig))
                    elif kind == "stacked":
                        old = blk.device[sig]
                        blk.device[sig] = self._patch_stacked(old, sig, updates)
                        repinned.append((old, blk.device[sig]))
                    elif isinstance(kind, tuple):
                        if upd is None:
                            continue
                        old = blk.device[sig]
                        blk.device[sig] = self._patch_block(old, sig, upd)
                        repinned.append((old, blk.device[sig]))
                    else:
                        released.append(blk.device.pop(sig))
        # the mutation choke point for pins: scatter IS the coordinated
        # host-mutate-then-patch path, so patched pins re-register (new
        # sample) and dropped pins release-verify (docs/static_analysis.md)
        for entry in released:
            _bufsan.release(entry, site="cache.scatter_update")
        for old, new in repinned:
            _bufsan.release(old, site="cache.scatter_update")
            _bufsan.export("device_pin", new, site="cache.scatter_update")

    @staticmethod
    def _patch_stacked(entry, sig, updates):
        """sig = ("stacked", ship_cols, nullable, block_rows); entry =
        (data_tuple[(B, rows)] per ship col, nulls_tuple per nullable col)."""
        _, ship_cols, nullable, _rows = sig
        data, nulls = entry
        data = list(data)
        nulls = list(nulls)
        for bi, (pos, cols) in updates.items():
            for ci, (vals, nl) in cols.items():
                if ci in ship_cols:
                    j = ship_cols.index(ci)
                    vals = np.asarray(vals).astype(data[j].dtype, copy=False)
                    data[j] = data[j].at[bi, pos].set(vals)
                if ci in nullable:
                    j = nullable.index(ci)
                    nulls[j] = nulls[j].at[bi, pos].set(np.asarray(nl))
        return tuple(data), tuple(nulls)

    @staticmethod
    def _patch_block(entry, sig, upd):
        """sig = (device_cols, nullable_cols, block_rows); entry =
        ([data per device col], [nulls per nullable col]) for ONE block."""
        dev_cols, nullable, _rows = sig
        pos, cols = upd
        data, nulls = list(entry[0]), list(entry[1])
        for ci, (vals, nl) in cols.items():
            if ci in dev_cols:
                j = dev_cols.index(ci)
                data[j] = data[j].at[pos].set(np.asarray(vals))
            if ci in nullable:
                j = nullable.index(ci)
                nulls[j] = nulls[j].at[pos].set(np.asarray(nl))
        return data, nulls


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
