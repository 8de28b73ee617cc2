"""Per-block zone maps: min/max/null-count pruning statistics.

The compressed-resident stats pass (``copr/encoding.py``) already bounds
every encoded block — frame-of-reference bitpack carries its frame, RLE its
run values, dictionary columns their code range.  This module turns those
bounds (plus a cheap masked min/max for plain numeric columns) into
*prunable* per-block zone maps, and evaluates a served DAG's selection
conjuncts against them so the device paths skip blocks that provably hold
no qualifying row (docs/zone_maps.md).

Soundness contract — the only invariant pruning relies on:

* every NON-NULL value ``v`` of the column in the block satisfies
  ``lo <= v <= hi`` (``lo is None`` means the block never held a non-null
  value for this column);
* the block's null count lies within ``[null_lo, null_hi]``.

Bounds may be WIDER than the true range ("stale-but-sound"): an in-place
write-through fold widens ``lo``/``hi`` with the incoming values and flags
the zone stale, because an overwrite may have removed the extremal row —
rescanning would defeat the point of a fold.  Structural deltas (inserts /
deletes) repack blocks into fresh ``_Block`` objects, so their zones simply
rebuild lazily from the new data.

Dictionary columns are tracked in CODE (rank) space: the serve-time
conjuncts arriving here were produced by ``rewrite_dag_for_dict``
(docs/compressed_columns.md), whose constants are codes/ranks too, so the
comparison needs no value-space translation.  Plain BYTES/JSON columns are
untracked — blocks always survive predicates over them.
"""

from __future__ import annotations

import os

import numpy as np

from .rpn import RpnExpression
from ..util.metrics import REGISTRY

__all__ = [
    "ColumnZone", "build_block_zones", "ensure_zones",
    "prune_blocks", "count_prune", "enabled", "set_enabled", "PruneStats",
]


def _env_enabled() -> bool:
    return os.environ.get("TIKV_TPU_ZONE_PRUNE", "1") != "0"


_ENABLED: bool | None = None  # None = follow the environment


def enabled() -> bool:
    return _env_enabled() if _ENABLED is None else _ENABLED


def set_enabled(on: bool | None) -> None:
    """Test/bench kill switch (None = defer to TIKV_TPU_ZONE_PRUNE)."""
    global _ENABLED
    _ENABLED = on


def count_prune(path: str, outcome: str, n: int = 1) -> None:
    if n:
        REGISTRY.counter(
            "tikv_coprocessor_zone_prune_total",
            "Zone-map prune decisions by serving path and outcome",
        ).inc(n, path=path, outcome=outcome)


class ColumnZone:
    """Value/null bounds for ONE column of ONE block (see module contract)."""

    __slots__ = ("lo", "hi", "null_lo", "null_hi", "n", "stale")

    def __init__(self, lo, hi, null_lo: int, null_hi: int, n: int,
                 stale: bool = False):
        self.lo = lo
        self.hi = hi
        self.null_lo = int(null_lo)
        self.null_hi = int(null_hi)
        self.n = int(n)
        self.stale = stale

    def __repr__(self) -> str:  # debugging / test output only
        return (f"ColumnZone(lo={self.lo}, hi={self.hi}, "
                f"nulls=[{self.null_lo},{self.null_hi}]/{self.n}"
                f"{', stale' if self.stale else ''})")


def _scalar(v):
    """Numpy scalar → exact Python number (int64 math must not wrap when a
    decimal alignment factor multiplies it later)."""
    return v.item() if hasattr(v, "item") else v


def _zone_of_column(col, n_valid: int) -> ColumnZone | None:
    """Zone for one column, reading the ENCODED payload where one is
    resident (no decode).  None = untracked (object payloads)."""
    from .encoding import EncodedColumn

    if isinstance(col, EncodedColumn):
        if col.kind == "bp":
            nulls = np.asarray(col._nulls[:n_valid])
            live = ~nulls
            nn = int(nulls.sum())
            if not live.any():
                return ColumnZone(None, None, nn, nn, n_valid)
            pk = np.asarray(col.packed[:n_valid])[live]
            return ColumnZone(_scalar(pk.min()) + col.ref,
                              _scalar(pk.max()) + col.ref, nn, nn, n_valid)
        # rle: only runs intersecting the valid prefix count
        ends = np.asarray(col.run_ends)
        starts = np.concatenate([[0], ends[:-1]])
        sel = starts < n_valid
        rv = np.asarray(col.run_values)[sel]
        rn = np.asarray(col.run_nulls)[sel]
        spans = np.minimum(ends[sel], n_valid) - starts[sel]
        nn = int(spans[rn].sum())
        live = rv[~rn]
        if len(live) == 0:
            return ColumnZone(None, None, nn, nn, n_valid)
        return ColumnZone(_scalar(live.min()), _scalar(live.max()),
                          nn, nn, n_valid)
    data = np.asarray(col.data)
    if data.dtype == object:
        return None  # raw BYTES/JSON: untracked
    nulls = np.asarray(col.nulls[:n_valid])
    nn = int(nulls.sum())
    live = ~nulls
    if not live.any():
        return ColumnZone(None, None, nn, nn, n_valid)
    d = data[:n_valid][live]
    return ColumnZone(_scalar(d.min()), _scalar(d.max()), nn, nn, n_valid)


def build_block_zones(cols, n_valid: int) -> dict[int, ColumnZone]:
    """Zones for every trackable column of one block."""
    zones: dict[int, ColumnZone] = {}
    if n_valid <= 0:
        return zones
    for ci, col in enumerate(cols):
        try:
            z = _zone_of_column(col, n_valid)
        except Exception:  # noqa: BLE001 — stats must never break serving
            z = None
        if z is not None:
            zones[ci] = z
    return zones


def ensure_zones(cache) -> bool:
    """Lazily attach zones to every block of a filled cache (fill and
    structural repacks create fresh ``_Block`` objects with ``zones=None``,
    so this is also how rebuilds happen).  Returns False when the cache
    cannot carry zones."""
    blocks = getattr(cache, "blocks", None)
    if not blocks:
        return False
    for blk in blocks:
        if blk.zones is None:
            blk.zones = build_block_zones(blk.cols, blk.n_valid)
    return True


# ---------------------------------------------------------------------------
# Conjunct recognition + per-block emptiness tests
# ---------------------------------------------------------------------------

_CMP_FLIP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
             "eq": "eq", "ne": "ne"}


def _recognize(rpn: RpnExpression):
    """Recognize the prunable conjunct shapes:

    * ``cmp(col, const)`` / ``cmp(const, col)`` → ("cmp", ci, op, cscale, c)
      — the same 3-node shape ``jax_zone._recognize_conjunct`` classifies
      tiles with, decimal alignment pre-multiplied (exact Python ints);
    * ``in(col, const...)``                      → ("in", ci, cscale, consts)
    * ``is_null(col)``                           → ("is_null", ci)

    None for anything else: unrecognized conjuncts never prune."""
    nodes = rpn.nodes
    if len(nodes) == 2 and nodes[1].kind == "fn" and nodes[1].op == "is_null" \
            and nodes[0].kind == "col":
        return ("is_null", nodes[0].index)
    if len(nodes) == 3 and nodes[2].kind == "fn":
        op = nodes[2].op
        if op not in _CMP_FLIP:
            return None
        a, b, sb = nodes[0], nodes[1], nodes[2].scale_by
        if a.kind == "col" and b.kind == "const":
            c = None if b.value is None else b.value * sb[1]
            return ("cmp", a.index, op, sb[0], c)
        if a.kind == "const" and b.kind == "col":
            c = None if a.value is None else a.value * sb[0]
            return ("cmp", b.index, _CMP_FLIP[op], sb[1], c)
        return None
    if (len(nodes) >= 3 and nodes[-1].kind == "fn" and nodes[-1].op == "in"
            and nodes[0].kind == "col"
            and all(n.kind == "const" for n in nodes[1:-1])):
        sb = nodes[-1].scale_by
        if any(isinstance(n.value, (bytes, bytearray)) for n in nodes[1:-1]):
            return None  # bytes IN-lists never reach zones untranslated
        consts = tuple(
            None if n.value is None else n.value * m
            for n, m in zip(nodes[1:-1], sb[1:])
        )
        return ("in", nodes[0].index, sb[0], consts)
    return None


def _cmp_empty(op: str, lo, hi, c) -> bool:
    """True iff NO value in [lo, hi] can satisfy ``col op c`` — the same
    interval tests ``jax_zone._classify_tiles`` uses for empty tiles."""
    if op == "lt":
        return lo >= c
    if op == "le":
        return lo > c
    if op == "gt":
        return hi <= c
    if op == "ge":
        return hi < c
    if op == "eq":
        return c < lo or c > hi
    # ne: only empty when every non-null value IS the constant
    return lo == c and hi == c


def _conjunct_prunes(rec, zones: dict[int, ColumnZone]) -> bool:
    """True iff the recognized conjunct proves the block yields NO row.
    NULL three-valued logic: a NULL comparison never satisfies a filter,
    so value predicates also prune blocks with no non-null values."""
    kind = rec[0]
    if kind == "is_null":
        z = zones.get(rec[1])
        return z is not None and z.null_hi == 0
    if kind == "cmp":
        _, ci, op, cscale, c = rec
        z = zones.get(ci)
        if z is None:
            return False
        if c is None:
            return True  # cmp(col, NULL) is NULL on every row
        if z.lo is None:
            return True  # no non-null value in the block
        return _cmp_empty(op, z.lo * cscale, z.hi * cscale, c)
    # "in"
    _, ci, cscale, consts = rec
    z = zones.get(ci)
    if z is None:
        return False
    if z.lo is None:
        return True
    lo, hi = z.lo * cscale, z.hi * cscale
    return all(c is None or c < lo or c > hi for c in consts)


class PruneStats:
    __slots__ = ("examined", "pruned")

    def __init__(self, examined: int = 0, pruned: int = 0):
        self.examined = examined
        self.pruned = pruned


def prune_blocks(cache, sel_rpns, path: str = "unary",
                 stats: PruneStats | None = None,
                 count: bool = True) -> np.ndarray | None:
    """Per-block keep mask for a filled cache under the plan's selection
    conjuncts (AND semantics: any conjunct that proves a block empty prunes
    it).  Returns None when pruning is off / inapplicable / proves nothing
    — callers then keep their exact pre-zone-map code path."""
    if not enabled() or not sel_rpns or getattr(cache, "folded", False):
        # a version a write-through fold made: its blocks change with the
        # commits, and its zones would be rebuilt per version
        return None
    recs = [r for r in (_recognize(rpn) for rpn in sel_rpns) if r is not None]
    if not recs:
        return None
    if not ensure_zones(cache):
        return None
    blocks = cache.blocks
    keep = np.ones(len(blocks), dtype=bool)
    for bi, blk in enumerate(blocks):
        zones = blk.zones
        if not zones:
            continue
        for rec in recs:
            if _conjunct_prunes(rec, zones):
                keep[bi] = False
                break
    n_pruned = int((~keep).sum())
    if stats is not None:
        stats.examined += len(blocks)
        stats.pruned += n_pruned
    if count:  # advisory probes (scheduler waste accounting) don't count
        count_prune(path, "examined", len(blocks))
        count_prune(path, "pruned", n_pruned)
    if n_pruned == 0:
        return None
    return keep


# ---------------------------------------------------------------------------
# TopN zone-order early exit
# ---------------------------------------------------------------------------

def topn_cutoff_order(blocks, keep, order_col: int, desc: bool, k: int):
    """Host-only TopN early exit: among the SURVIVING blocks (iterated in
    stream order for byte-identical tie-breaks), find which can still
    contribute to the top-``k`` (docs/zone_maps.md).

    Ascending: sort candidate blocks by ``hi``; once the accumulated row
    count reaches ``k`` the threshold ``T`` is that prefix's max ``hi`` —
    ≥k rows sort at or below ``T`` (nulls sort first, so null rows count
    toward the prefix too).  A remaining block with ``lo > T`` and no nulls
    holds only rows STRICTLY above the eventual kth value: even losing
    every tie, none can enter the top-k, so it is skipped.  Descending is
    symmetric on ``lo`` with the guaranteed count shrunk by ``null_hi``
    (nulls sort last under desc).  Returns an updated keep mask, or None
    when the bound is not satisfiable from zone order (untracked order
    column, too few bounded rows, stale zones are fine — wider bounds only
    weaken the exit, never break it)."""
    cand = []
    for bi, blk in enumerate(blocks):
        if not keep[bi]:
            continue
        z = (blk.zones or {}).get(order_col)
        if z is None:
            return None  # untracked order column: no sound bound
        cand.append((bi, z))
    if not cand:
        return None
    if desc:
        # guaranteed non-null rows with value >= lo
        ordered = sorted(cand, key=lambda t: _neg_key(t[1].lo))
        got = 0
        thresh = None
        for _bi, z in ordered:
            if z.lo is None:
                break  # all-null blocks bound nothing under desc
            got += max(0, z.n - z.null_hi)
            if got >= k:
                thresh = z.lo
                break
        if thresh is None:
            return None
        out = keep.copy()
        for bi, z in cand:
            if z.hi is not None and z.hi < thresh and z.null_hi == 0:
                out[bi] = False
        return out
    ordered = sorted(cand, key=lambda t: _pos_key(t[1].hi))
    got = 0
    thresh = None
    for _bi, z in ordered:
        # nulls sort FIRST ascending: every row of the block sorts <= hi
        got += z.n
        if z.lo is None:
            continue  # all-null: rows count toward the prefix, no threshold
        if got >= k:
            thresh = z.hi
            break
    if thresh is None:
        return None
    out = keep.copy()
    for bi, z in cand:
        if z.lo is not None and z.lo > thresh and z.null_hi == 0:
            out[bi] = False
    return out


def _pos_key(v):
    # all-null blocks (hi None) sort FIRST: their rows sort before any value
    return (v is not None, v if v is not None else 0)


def _neg_key(v):
    # sort descending by lo with None (all-null) last
    return (v is None, -(v if v is not None else 0))
