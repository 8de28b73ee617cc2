#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the only one that initialises a JAX backend, so it holds the
chip: it fails at once on anything but a TPU, assembles the deployment the
cell's configuration file describes (``assembly.py``), loads it, cold-fills
every region's image, and warms up with the cell's own traffic.  All of that is
``setup_s``.  The window's load comes from a client process (``client.py``)
that reaches the store only through its socket.  After the window the answers
are held to the plain reference (``check.py``), outside the timed window.

Everything that belongs to one configuration, one traffic mix, one plan or one
metric is a file found by its name in ``BENCHMARK.json``; none of those names
occurs here.  The last line of standard output is the result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
sys.path.insert(0, ROOT)

# counters of the store whose movement over the window goes into the result's
# ``detail``, for whoever reads a run: no metric reads them from there
DIAGNOSTIC_SERIES = (
    "tikv_coprocessor_region_cache_total", "tikv_observatory_serve_total",
    "tikv_coprocessor_path_fallback_total", "tikv_coprocessor_sched_batches_total",
    "tikv_coprocessor_sched_shed_total", "tikv_coprocessor_deadline_expired_total")
CLIENT_SLACK_S = 90.0   # an answer may come this long after the window's close


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}; it has {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_of(bench: dict, kind: str, cell: str) -> list[dict]:
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def require_tpu(chips: int) -> dict:
    """What JAX runs on; anything but ``chips`` TPU devices ends the run with
    no result."""
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if found["platform"] != "tpu" or found["count"] != chips:
        print(f"benchmark: needs {chips} TPU device(s), JAX found {found}",
              file=sys.stderr)
        raise SystemExit(1)
    return found


def place_cache() -> str:
    """The program's own placement (``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``), and every program kept whatever it cost."""
    import jax
    from tikv_tpu.util.compile_cache import place_compile_cache

    where = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class Run:
    def __init__(self, args, bench: dict, device: dict, compiles, overrides=None):
        from benchmark.assembly import Deployment

        self.args = args
        self.device = device
        self.compiles = compiles
        self.cell, cfg = find_cell(bench, args.workload)
        self.bench = bench
        overrides = overrides or {}
        # a CPU backend writes no device plane into its trace
        self.rehearsal = bool(overrides.get("rehearsal"))
        self.config = dict(load_json(ROOT, cfg["file"]), **overrides.get("config", {}))
        self.mix = dict(load_json(HERE, "traffic", self.cell["traffic"] + ".json"),
                        **overrides.get("traffic", {}))
        self.seed = abs(int(args.seed))
        self.dep = Deployment(self.config, self.seed)
        self.child = None
        self.n_clients = 0
        # a mix with a refresh stream: what it wrote, warm-ups included
        self.refresh = None
        self.txns: list[dict] = []
        self.pairs_run = 0

    # -- the client process --------------------------------------------------

    def client(self, seconds: float, on_start=None) -> dict:
        """Runs the cell's traffic for ``seconds`` from a process of its own
        and returns its log; ``on_start`` is called at the window's first
        instant."""
        self.n_clients += 1
        log_path = os.path.join(self.dep.tmp, f"client-{self.n_clients}.json")
        job = dict(self.dep.job(), traffic=self.mix, seed=self.seed,
                   seconds=seconds, log=log_path, prewarm=self.n_clients == 1)
        if self.refresh is not None:
            job["first_pair"] = self.pairs_run
        job_path = log_path + ".job"
        with open(job_path, "w") as f:
            json.dump(job, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)  # it compiles nothing
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py"), job_path],
            stdout=subprocess.PIPE, env=env, cwd=ROOT, text=True)
        try:
            line = self.child.stdout.readline()
            if line.strip() != "START":
                raise RuntimeError(f"the client process said {line!r}, not START")
            if on_start is not None:
                on_start()
            rc = self.child.wait(timeout=seconds + CLIENT_SLACK_S)
        finally:
            if self.child.poll() is None:
                self.child.kill()
                self.child.wait()
            self.child.stdout.close()
        if not os.path.exists(log_path):
            raise RuntimeError(f"the client process left no log (exit code {rc})")
        log = load_json(log_path)
        if log["errors"]:
            raise RuntimeError(f"the client process failed: {log['errors']}")
        self.txns.extend(log.get("txns", []))
        self.pairs_run += len(log.get("pairs", []))
        return log

    # -- set-up ----------------------------------------------------------------

    def set_up(self) -> dict:
        from benchmark import check
        from benchmark import table as tbl

        dep = self.dep
        tbl.selfcheck(dep.table_id)
        dep.start()
        dep.split(to_table_end="refresh" in self.mix)
        dep.load()
        if "refresh" in self.mix:
            from benchmark.refresh import Refresh

            self.refresh = Refresh(
                self.mix["refresh"], self.seed, dep.table_id,
                dep.regions * dep.rows_per_region, dep.rows_per_region, dep.regions)
        # every image the mix's plans read, built before any stream asks
        fills = [check.plan_module(p) for p in self.mix["plans"]]
        dep.cold_fill([f.dag(dep.table_id, f.DEFAULTS) for f in fills])
        warmups = []
        for _ in range(int(self.mix["max_warmups"])):
            before = self.compiles.programs
            log = self.client(float(self.mix["warmup_seconds"]))
            warmups.append({"queries": len(log["queries"]),
                            "programs": self.compiles.programs - before})
            print(f"benchmark: warm-up {len(warmups)}: {warmups[-1]}", file=sys.stderr)
            if not warmups[-1]["programs"]:
                break
        return {"warmups": warmups, "rows": dep.regions * dep.rows_per_region,
                **dep.timings}

    # -- the window ------------------------------------------------------------

    def traced_client(self, seconds: float, on_start, marks: dict,
                      trace_dir: str) -> tuple[dict, tuple[float, float]]:
        """The window with its last seconds traced; returns the client's log
        and the traced span in seconds into the window.  The trace is stopped
        once the client has closed: collecting it holds the interpreter for
        longer than the span it covers, and must not stall a query."""
        import jax

        length = min(float(self.mix["trace_seconds"]), seconds / 2)
        begun: list[float] = []

        def begin():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            begun.append(time.perf_counter() - marks["t0"])

        timer = threading.Timer(seconds - length, begin)

        def started():
            on_start()
            timer.start()

        try:
            log = self.client(seconds, started)
        finally:
            timer.cancel()
            if "t0" in marks:
                timer.join()
        if not begun:
            raise RuntimeError("the window closed before its trace began")
        end = time.perf_counter() - marks["t0"]
        jax.profiler.stop_trace()
        return log, (begun[0], end)


    def window(self, setup: dict) -> dict:
        import jax
        from benchmark import check, counters
        from benchmark import trace as trace_mod
        from tikv_tpu.copr.breaker import PATHS as breaker_paths

        seconds = float(self.args.seconds)
        tracing = bool(self.args.trace)
        trace_dir = os.path.join(self.dep.tmp, "trace")
        span = (0.0, 0.0)
        marks: dict = {}

        def on_start():
            marks["t0"] = time.perf_counter()
            setup["setup_s"] = marks["t0"] - _T0

        before = counters.snapshot()
        compiles_before = self.compiles.programs
        if tracing:
            log, span = self.traced_client(seconds, on_start, marks, trace_dir)
        else:
            log = self.client(seconds, on_start)
        after_snap = counters.snapshot()
        compiles_after = self.compiles.programs
        stats = [d.memory_stats() or {} for d in jax.devices()]
        peak = max((s.get("peak_bytes_in_use") or 0) for s in stats)

        ep = self.dep.srv.copr
        faults = {
            "device_fallbacks": int(counters.moved(
                before, after_snap, "tikv_coprocessor_device_fallback_total")),
            "breakers_open": sum(1 for p in breaker_paths
                                 if ep.breaker.state_of(p) != "closed"),
            "last_device_error": ep.last_device_error,
        }
        base = self.dep.base
        missing = None
        if self.refresh is not None:
            # every acknowledged write read back before the store stops
            t_rb = time.perf_counter()
            missing = self.dep.read_back(self.refresh.final_state(self.txns))
            read_back_s = time.perf_counter() - t_rb
        # the program's state goes before the reference runs
        self.dep.stop()

        t_ref = time.perf_counter()
        held = check.compare(log, base, int(self.config["load_batch_rows"]),
                             self.txns, self.refresh)
        ref_s = time.perf_counter() - t_ref
        if missing is not None:
            held["numbers"]["acked_rows_missing"] = missing

        work = {p: check.plan_module(p).work(self.dep.rows_per_region)
                for p in self.mix["plans"]}
        ctx = {
            "log": log, "seconds": seconds, "wrong": held["wrong"], "work": work,
            "before": before, "after": after_snap,
            "compiles": {"before": compiles_before, "after": compiles_after},
            "setup": setup, "trace": None, "trace_span": span,
            "peaks": self.peaks(), "memory_peak_bytes": peak,
        }
        device = dict(self.device, memory_peak_bytes=peak)
        breakdown = None
        if tracing:
            events = trace_mod.load_events(trace_mod.find_xplane(trace_dir))
            ctx["trace"] = tr = trace_mod.reduce(events, span[1] - span[0])
            if tr is None and not self.rehearsal:
                raise RuntimeError("no operation ran on the device in the traced span")
            if tr is not None:
                device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
                breakdown = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
        disk_bytes = sum(os.path.getsize(os.path.join(d, f))
                         for d, _sub, files in os.walk(self.dep.tmp) for f in files)
        self.dep.remove_files()

        kind, readers = (("per_layer", "layer_metrics") if tracing
                         else ("end_to_end", "end_to_end"))
        metrics = {}
        for m in metrics_of(self.bench, kind, self.cell["name"]):
            reader = importlib.import_module(f"benchmark.{readers}.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        numbers = held["numbers"]
        compared, correct = check.judge(
            numbers, check.WRITE_LIMITS if self.refresh is not None else None)
        attempted = numbers["compared"] + numbers["unanswered"]
        # A task answered from the CPU after a device fault, or turned away
        # from the device by an open breaker, was answered behind the
        # device's back: it counts as failed.
        faults["breaker_turned_away"] = int(counters.moved(
            before, after_snap, "tikv_coprocessor_path_fallback_total",
            cause="breaker_open"))
        failed = (numbers["wrong_answers"] + numbers["unanswered"]
                  + faults["device_fallbacks"] + faults["breaker_turned_away"])
        result = {
            "correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device,
        }
        if self.args.control:
            # the control's answers go through the same judgement
            result["control"], result["control_correct"] = check.judge(held["control"])
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["workload"] = self.cell["name"]
        result["seed"] = self.seed
        result["detail"] = {
            "queries": len(log["queries"]),
            "by_plan_ms": by_plan(log),
            "closed_after_s": log["closed_after"], "reference_s": ref_s,
            "disk_bytes_at_close": disk_bytes,
            "setup": setup, "faults": faults,
            "compiles": {"programs": self.compiles.programs,
                         "fetched": self.compiles.fetched,
                         "seconds": self.compiles.seconds},
            "numbers": numbers, "wrong_detail": held["wrong_detail"],
            "moved": {
                f"{name}{dict(key) or ''}": after_snap[name][key] - before.get(name, {}).get(key, 0.0)
                for name in sorted(after_snap) if name.startswith(DIAGNOSTIC_SERIES)
                for key in sorted(after_snap[name])
                if after_snap[name][key] != before.get(name, {}).get(key, 0.0)},
        }
        if self.refresh is not None:
            result["detail"]["refresh"] = refresh_detail(
                log, self.txns, held["states"], read_back_s)
        result["compared"] = compared
        return result

    def peaks(self) -> dict:
        table = load_json(HERE, "peaks.json")
        kind = self.device["kind"]
        if kind not in table:
            raise RuntimeError(f"benchmark/peaks.json has no device {kind!r}")
        return table[kind]


def by_plan(log: dict) -> dict:
    """Per plan: queries, median and slowest, for the reader of a run."""
    from benchmark.reduce import percentile

    out: dict = {}
    for q in log["queries"]:
        out.setdefault(q["plan"], []).append((q["done"] - q["issued"]) * 1e3)
    return {p: {"n": len(v), "p50": percentile(v, 50), "max": max(v)}
            for p, v in sorted(out.items())}


def refresh_detail(log: dict, txns: list, states: int, read_back_s: float) -> dict:
    """The refresh stream as the window saw it, for the reader of a run."""
    from benchmark.reduce import commit_ms, percentile

    ms = commit_ms(log)
    pair_s = [p["done"] - p["started"] for p in log["pairs"]]
    retries = [t["lock_retries"] for _q in log["queries"] for t in _q["tasks"]
               if t.get("lock_retries")]
    return {
        "pairs": len(log["pairs"]), "txns": len(log["txns"]),
        "txns_before_window": len(txns) - len(log["txns"]),
        "rows_written": sum(len(t["handles"]) for t in log["txns"]),
        "commit_p50_ms": percentile(ms, 50) if ms else None,
        "commit_p95_ms": percentile(ms, 95) if ms else None,
        "commit_max_ms": max(ms, default=None),
        "pair_max_s": max(pair_s, default=None),
        "lock_retries": sum(retries), "tasks_retried": len(retries),
        "lock_retries_max": max(retries, default=0),
        "states_compared": states, "read_back_s": read_back_s,
    }


def run_cell(args, device: dict, bench: dict, overrides=None) -> dict:
    """Everything after the look for a chip.  ``bench`` is ``BENCHMARK.json``
    (a test may add the cell that the file does not hold yet); ``overrides``
    lays keys over the configuration's and the traffic's files: a rehearsal's
    smaller size."""
    from benchmark.counters import CompileCount

    compiles = CompileCount()
    compiles.listen()
    run = Run(args, bench, device, compiles, overrides)
    try:
        run.peaks()
        setup = run.set_up()
        return run.window(setup)
    except BaseException:
        # a failed phase can leave requests in flight, and closing the engines
        # under them ends in a use-after-free: only the files go
        if run.child is not None and run.child.poll() is None:
            run.child.kill()
            run.child.wait()
        run.dep.remove_files()
        raise


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the control's answers (check.py), which "
                         "must come out as not correct; the driver's runs do not")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, _cfg = find_cell(bench, args.workload)
    place_cache()
    device = require_tpu(int(cell["chips"]))
    try:
        result = run_cell(args, device, bench)
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']} "
              + (f"(limit {c['limit']})" if "limit" in c
                 else f"(at least {c['at_least']})"), file=sys.stderr)
    print(json.dumps(result), flush=True)
    sys.stdout.flush()
    sys.stderr.flush()
    # the store's threads are daemons of a server that is already stopped
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
