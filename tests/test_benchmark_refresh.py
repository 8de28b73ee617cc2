"""The benchmark's refresh-stream tests (``benchmark/tests/test_refresh.py``)
in tier-1: TPC-H's RF1/RF2 functions as the client writes them, the
comparison of every answer with the rows committed below its own
``start_ts``, the read-back of every acknowledged write, and whole runs of
the refresh traffic at a size a CPU holds in which a planted fault — a cache
that never folds a write in, a commit acknowledged then dropped — comes out
not correct and a task that meets a lock is retried.

Two of that file's whole-run tests stay with it, run by hand: the sound run
(it asserts that a 5 s window reached more than six states of the regions)
and a read served above its start_ts (it needs a commit to land between a
read's timestamp and its snapshot).  Both count what the CPU gets done in
seconds, and beside the suite's other workers it sometimes gets less done.
"""

from benchmark.tests.test_refresh import (  # noqa: F401
    test_a_commit_acknowledged_for_a_key_then_dropped,
    test_a_mix_that_writes_nothing_is_held_as_before,
    test_a_mix_without_refresh_keeps_its_ranges,
    test_a_mix_without_refresh_writes_nothing_and_prints_the_same_keys,
    test_a_region_cache_that_never_folds_a_write_in,
    test_a_task_that_meets_a_lock_is_retried_then_answered,
    test_each_state_is_the_rows_committed_below_its_start_ts,
    test_rf1_and_rf2_are_tpch_shaped,
    test_rf2_ends_where_the_first_region_runs_out_of_whole_orders,
    test_the_stream_is_the_same_for_the_same_seed,
)
