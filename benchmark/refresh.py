"""TPC-H's refresh functions (cl. 2.5) cut to the one table stored here, as
pure functions of the seed and a pair's number: the client writes them, and
the comparison and the read-back rebuild from them what was written.

Pair ``i`` (cl. 2.5.2-2.5.3; ``orders`` is SF x 1,500):

- **RF1** adds the lines of ``orders`` new orders, 1-7 lines each, their
  values by ``table.py``'s rules from ``default_rng([seed, 2, i])``.  Their
  order keys come from the gaps dbgen's sparse numbering keeps free for update
  sets (cl. 4.2.3: slot 1 of every 32 keys, ``table.order_key``), and their
  handles go above every handle used so far, as TiDB's ``_tidb_rowid``
  allocator gives them out: they land in the last region.
- **RF2** deletes every line of the ``orders`` oldest orders still present: the
  loaded orders ``i * orders`` to ``(i + 1) * orders - 1``, which lie in the
  first region.  It holds the orders of ``pairs_held`` pairs (500 at 2 x
  200,000 rows); a stream that would run past them fails its run.

Each function is ``transactions`` transactions of whole orders, each inside one
region.  Only LINEITEM is stored and no index entry is written (the traffic
file's ``assumed``).
"""

from __future__ import annotations

import numpy as np

from . import table as tbl


class Refresh:
    def __init__(self, spec: dict, seed: int, table_id: int,
                 loaded: int, rows_per_region: int, regions: int):
        self.orders = int(spec["orders"])
        self.transactions = int(spec["transactions"])
        self.seed = int(seed)
        self.table_id = int(table_id)
        self.rows_per_region = int(rows_per_region)
        self.regions = int(regions)
        self.loaded = int(loaded)
        self.sf = self.loaded / tbl.SF1_ROWS
        # row index at which each loaded order starts (one past the last too)
        self.order_start = tbl.order_starts(self.loaded, self.seed)
        # pairs whose RF2 finds its orders whole in the first region
        whole = int(np.searchsorted(self.order_start, self.rows_per_region, "right")) - 1
        self.pairs_held = whole // self.orders
        self._first_handle = [self.loaded + 1]
        self._rf1: dict[int, tbl.Table] = {}

    def _rng(self, i: int):
        return np.random.default_rng([self.seed, 2, int(i)])

    def first_handle(self, i: int) -> int:
        """RF1 of pair ``i`` starts here: one above the last handle RF1 of
        every earlier pair used."""
        while len(self._first_handle) <= i:
            j = len(self._first_handle) - 1
            lines = self._rng(j).integers(1, 8, self.orders)
            self._first_handle.append(self._first_handle[-1] + int(lines.sum()))
        return self._first_handle[i]

    def rf1_table(self, i: int) -> tbl.Table:
        t = self._rf1.get(i)
        if t is None:
            rng = self._rng(i)
            lines = rng.integers(1, 8, self.orders)
            ids = i * self.orders + np.arange(self.orders)
            t = tbl.order_lines(rng, lines, ids, 1, self.first_handle(i), self.sf)
            if len(self._rf1) > 64:
                self._rf1.clear()
            self._rf1[i] = t
        return t

    def region_of(self, handles: np.ndarray) -> int:
        ks = np.minimum((np.asarray(handles) - 1) // self.rows_per_region,
                        self.regions - 1)
        if ks.min() != ks.max():
            raise ValueError(f"a refresh transaction would span regions {sorted(set(ks.tolist()))}")
        return int(ks[0])

    def pair(self, i: int) -> list[dict]:
        """The pair's transactions in the order they are run: RF1's, then
        RF2's; each ``{"function", "pair", "txn", "region", "handles"}``."""
        groups = np.array_split(np.arange(self.orders), self.transactions)
        out = []
        t = self.rf1_table(i)
        order = tbl.order_of_key(t.orderkey) - i * self.orders
        for j, g in enumerate(groups):
            h = t.handle[np.isin(order, g)]
            out.append({"function": "RF1", "pair": i, "txn": j,
                        "region": self.regions - 1, "handles": h})
        if i >= self.pairs_held:
            raise ValueError(f"RF2 of pair {i}: the first region holds the orders of "
                             f"{self.pairs_held} pairs")
        first = i * self.orders
        for j, g in enumerate(groups):
            a, b = self.order_start[first + g[0]], self.order_start[first + g[-1] + 1]
            h = np.arange(a + 1, b + 1, dtype=np.int64)
            out.append({"function": "RF2", "pair": i, "txn": j,
                        "region": self.region_of(h), "handles": h})
        return out

    def rows(self, txn: dict) -> tbl.Table:
        """The rows an RF1 transaction inserted."""
        t = self.rf1_table(int(txn["pair"]))
        return t.take(np.isin(t.handle, np.asarray(txn["handles"])))

    def mutations(self, txn: dict) -> list[dict]:
        """What ``kv_prewrite`` carries for the transaction."""
        if txn["function"] == "RF1":
            return [{"op": "put", "key": k, "value": v}
                    for k, v in tbl.encode_kvs(self.table_id, self.rows(txn))]
        from tikv_tpu.copr.table import record_key

        return [{"op": "delete", "key": record_key(self.table_id, int(h))}
                for h in txn["handles"]]

    def final_state(self, txns: list[dict]) -> dict[int, dict[bytes, bytes | None]]:
        """Per region, every key the transactions wrote and what a read after
        the last of them must find (``None``: absent), in commit order."""
        out: dict[int, dict] = {}
        for txn in sorted(txns, key=lambda x: x["commit_ts"]):
            got = out.setdefault(int(txn["region"]), {})
            for m in self.mutations(txn):
                got[m["key"]] = m.get("value")
        return out
