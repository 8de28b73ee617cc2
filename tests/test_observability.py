"""Operational artifacts: Grafana dashboards + alert rules must stay in
lockstep with the metric series the store actually emits (the reference
ships metrics/grafana/*.json + metrics/alertmanager/tikv.rules.yml; a
dashboard over nonexistent series is decoration, not observability)."""

import json
import os
import re

import yaml

from tikv_tpu.util.metrics import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# importing these modules registers their series in REGISTRY
import tikv_tpu.server.node  # noqa: F401,E402
import tikv_tpu.server.server  # noqa: F401,E402
import tikv_tpu.storage.txn.scheduler  # noqa: F401,E402
import tikv_tpu.util.inbound  # noqa: F401,E402

# series registered lazily at first use (counters created inside handlers)
LAZY_SERIES = {
    "tikv_bufsan_total",
    "tikv_coprocessor_request_total",
    "tikv_coprocessor_request_duration_seconds",
    "tikv_coprocessor_device_fallback_total",
    "tikv_coprocessor_cache_hit_total",
    "tikv_coprocessor_batch_total",
    "tikv_coprocessor_batch_queries_total",
    "tikv_coprocessor_sched_queue_depth",
    "tikv_coprocessor_sched_batch_occupancy",
    "tikv_coprocessor_sched_padding_waste",
    "tikv_coprocessor_sched_lane_wait_seconds",
    "tikv_coprocessor_sched_dispatch_total",
    "tikv_coprocessor_sched_dispatch_riders",
    "tikv_coprocessor_sched_batches_total",
    "tikv_coprocessor_sched_shed_total",
    "tikv_coprocessor_sched_device_occupancy",
    "tikv_coprocessor_sharded_merge_seconds",
    "tikv_coprocessor_mesh_cache_hit_total",
    "tikv_coprocessor_path_fallback_total",
    "tikv_coprocessor_breaker_event_total",
    "tikv_coprocessor_breaker_state",
    "tikv_coprocessor_deadline_expired_total",
    "tikv_wire_stage_seconds",
    "tikv_wire_coalesce_total",
    "tikv_wire_chunk_total",
    "tikv_trace_total",
    "tikv_trace_ring_traces",
    "tikv_copr_owner_forward_total",
    "tikv_chaos_injected_total",
    "tikv_client_retry_total",
    "tikv_resolved_ts_safe_ts_lag",
    "tikv_read_forward_total",
    "tikv_read_stale_serve_total",
    "tikv_read_refuse_total",
    "tikv_coprocessor_follower_read_total",
    "tikv_coprocessor_region_cache_total",
    "tikv_coprocessor_region_cache_wt_lost_total",
    "tikv_coprocessor_region_cache_lock_check_total",
    "tikv_coprocessor_region_cache_below_snapshot_total",
    "tikv_coprocessor_region_cache_repair_total",
    "tikv_coprocessor_region_cache_fold_miss_total",
    "tikv_coprocessor_region_cache_fold_place_total",
    "tikv_coprocessor_region_cache_fold_part_seconds",
    "tikv_coprocessor_integrity_mismatch_total",
    "tikv_coprocessor_integrity_quarantine_total",
    "tikv_coprocessor_integrity_scrub_total",
    "tikv_coprocessor_shadow_read_total",
    "tikv_coprocessor_checksum_total",
    "tikv_raft_consistency_check_total",
    "tikv_coprocessor_region_cache_device_bytes",
    "tikv_storage_batch_size",
    "tikv_coprocessor_region_cache_delta_rows_total",
    "tikv_coprocessor_region_cache_evict_total",
    "tikv_coprocessor_region_cache_invalidate_total",
    "tikv_coprocessor_region_cache_bytes",
    "tikv_coprocessor_region_cache_compression_ratio",
    "tikv_coprocessor_region_cache_device_pinned_bytes",
    "tikv_observatory_serve_total",
    "tikv_observatory_serve_seconds",
    "tikv_observatory_rows_total",
    "tikv_observatory_decline_total",
    "tikv_observatory_compile_total",
    "tikv_observatory_compile_seconds",
    "tikv_observatory_pinned_hbm_bytes",
    "tikv_observatory_pinned_hbm_watermark_bytes",
    "tikv_observatory_sigs",
    "tikv_observatory_evicted_sigs",
    "tikv_coprocessor_encoding_total",
    "tikv_coprocessor_encoding_demote_total",
    "tikv_coprocessor_encoded_path_total",
    "tikv_coprocessor_encoded_decline_total",
    "tikv_coprocessor_encoded_rewrite_total",
    "tikv_coprocessor_zone_prune_total",
    "tikv_coprocessor_join_total",
    "tikv_coprocessor_cost_route_total",
    "tikv_coprocessor_cost_route_delta_ms_total",
    "tikv_coprocessor_geometry_tune_total",
    "tikv_overload_admission_total",
    "tikv_overload_demote_total",
    "tikv_overload_bucket_level",
    "tikv_overload_effective_scale",
    "tikv_overload_controller_total",
    "tikv_overload_hbm_bytes",
    "tikv_overload_hbm_evict_total",
    "tikv_overload_device_block_total",
    "tikv_gcworker_gc_tasks_total",
    "tikv_memory_usage_bytes",
    "tikv_raftstore_proposal_total",
    "tikv_raftstore_apply_duration_seconds",
    "tikv_raftstore_apply_batch_entries",
    "tikv_engine_wal_bytes",
    "tikv_engine_memtable_bytes",
    "tikv_engine_run_count",
    "tikv_engine_perf_events",
    "tikv_engine_closed_call_total",
    "tikv_server_stop_abandoned_thread_total",
}

_METRIC_RE = re.compile(r"\btikv_[a-z0-9_]+")


def _known_series() -> set:
    known = set(REGISTRY._metrics) | set(LAZY_SERIES)
    # histograms expose _bucket/_sum/_count series
    for name in list(known):
        known.update({name + "_bucket", name + "_sum", name + "_count"})
    return known


def test_dashboard_panels_reference_real_series():
    """EVERY dashboard in metrics/grafana must only reference series the
    store actually emits (summary + raft + engine + coprocessor)."""
    gdir = os.path.join(REPO, "metrics", "grafana")
    dashes = sorted(f for f in os.listdir(gdir) if f.endswith(".json"))
    assert len(dashes) >= 4, "expected summary + raft + engine + copr dashboards"
    known = _known_series()
    for fn in dashes:
        dash = json.loads(open(os.path.join(gdir, fn)).read())
        exprs = [
            t["expr"]
            for p in dash["panels"]
            for t in p.get("targets", [])
            if "expr" in t
        ]
        assert len(exprs) >= 6, f"{fn} lost its panels"
        for expr in exprs:
            for name in _METRIC_RE.findall(expr):
                assert name in known, f"{fn} references unknown series {name}"


def test_alert_rules_reference_real_series():
    path = os.path.join(REPO, "metrics", "alertmanager", "tikv_tpu.rules.yml")
    doc = yaml.safe_load(open(path).read())
    rules = doc["groups"][0]["rules"]
    assert len(rules) >= 8
    known = _known_series()
    for rule in rules:
        assert rule["alert"] and rule["expr"] and rule["labels"]["level"]
        for name in _METRIC_RE.findall(rule["expr"]):
            assert name in known, f"alert {rule['alert']} references unknown {name}"


def test_served_metrics_include_dashboard_sources():
    """Drive a live server + endpoint and confirm /metrics exposes the
    headline series the dashboard's top row draws from."""
    from tikv_tpu.copr.endpoint import Endpoint
    from tikv_tpu.server.server import Client, Server
    from tikv_tpu.server.service import KvService
    from tikv_tpu.storage.storage import Storage

    storage = Storage()
    svc = KvService(storage, Endpoint(storage.engine))
    srv = Server(svc)
    srv.start()
    c = Client(*srv.addr)
    c.call("kv_get", {"key": b"x", "version": 10, "context": {}})
    c.close()
    srv.stop()
    text = REGISTRY.render()
    for series in ("tikv_grpc_msg_total", "tikv_grpc_msg_duration_seconds",
                   "tikv_raftstore_region_count", "tikv_scheduler_commands_total"):
        assert series in text, f"{series} missing from /metrics"
    assert 'method="kv_get"' in text
