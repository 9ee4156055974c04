"""Run one cell of ``BENCHMARK.json`` on the chip this process finds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start): worker processes make the cell's
traffic from the seed while the parent brings up the chip, assembles the
node's verification path (``client.assemble_device_path("jax")``) and sends
the warm-up requests through the timed entry, which traces and compiles the
one shape the cell uses.  Then the mix's kind (``benchmark/traffic/<kind>.py``)
offers the pool's requests for ``--seconds`` by its own loop, closed or open;
each latency runs from a request's hand-over (or arrival) to its verdict, and
each end-to-end metric is the statistic the mix's ``report`` names for it,
over all requests of the window.  After the window every verdict is
compared with the one the traffic was made to have, and the route is
checked: no host fallback, every flight-recorder batch on the device.

``--trace 1`` runs the same window, the JAX profiler on until the first
``trace_requests`` requests (the mix file's) are done, and prints the
per-layer metrics (``benchmark/metrics/<name>.py``) in place of the
end-to-end ones.  The last stdout line is the result's JSON; the last
stderr lines are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import breakdown  # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402
from benchmark.traffic import gen  # noqa: E402

#: The numbers compared, each with its limit: every one is a count that a
#: sound run holds at 0 (PERF.md, "End-to-end metrics" and the control).
LIMITS = {"verdict_mismatches": 0, "request_errors": 0, "host_fallbacks": 0,
          "host_routed_batches": 0}


class NoChip(RuntimeError):
    pass


def load_cell(name: str, root: str = ROOT):
    """The cell, its configuration and its mix, found by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    return bench, cell, cfg, gen.load_mix(cell["traffic"], root)


def metric_reader(name: str, root: str = ROOT):
    """``<root>/benchmark/metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(root, "benchmark", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_for(bench: dict, cell: dict) -> list:
    """The per-layer metrics this cell reports."""
    reported = {m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in reported]


def _say(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=False), flush=True)


class _CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.on = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event in self.counts:
            self.counts[event] += 1


def bring_up(chips: int, require_tpu: bool) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform: {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def _route_marks():
    from lighthouse_tpu import device_telemetry, metrics

    return (sum(metrics.DEVICE_HOST_FALLBACK.snapshot().values()),
            device_telemetry.FLIGHT_RECORDER.recorded_total)


def _route_since(marks) -> dict:
    from lighthouse_tpu import device_telemetry, metrics

    fallbacks0, seq0 = marks
    rec = device_telemetry.FLIGHT_RECORDER
    records = [r for r in rec.recent(limit=rec.capacity) if r["seq"] > seq0]
    return {"host_fallbacks": sum(metrics.DEVICE_HOST_FALLBACK.snapshot()
                                  .values()) - fallbacks0,
            "host_routed_batches": sum(bool(r["host_fallback"]) for r in records),
            "records": records,
            "lost_records": max(0, rec.recorded_total - seq0 - len(records))}


def _spans_in(lo: float, hi: float) -> dict:
    """The program's own spans that started inside ``[lo, hi]`` (perf
    counter), by name, flattened out of the completed traces."""
    from lighthouse_tpu import tracing

    out: dict = {}

    def walk(sp):
        if lo <= sp.start_pc <= hi:
            out.setdefault(sp.name, []).append(
                {"start_pc": sp.start_pc, "end_pc": sp.end_pc,
                 "duration": sp.duration, "fields": dict(sp.fields)})
        for child in sp.children:
            walk(child)

    for t in tracing.TRACES.recent(limit=1 << 20):
        walk(t.root)
    return out


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile of all ``values`` by nearest rank."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


#: The statistics a mix may report an end-to-end metric as (its ``report``),
#: each over ALL requests of the window: ``w`` holds their latencies in ms,
#: the completed count and the window's length.
STATS = {
    "latency_p50": lambda w: statistics.median(w["latency_ms"]),
    "latency_p90": lambda w: nearest_rank(w["latency_ms"], 0.90),
    "latency_p95": lambda w: nearest_rank(w["latency_ms"], 0.95),
    "requests_per_s": lambda w: w["completed"] / w["window_s"],
}


def reported(mix: dict, e2e: list) -> dict:
    """End-to-end metric -> statistic, for each the cell reports besides
    ``setup_s``.  An unknown statistic, or a metric the mix does not say how
    to compute, is an error before anything runs."""
    report = mix.get("report", {})
    for metric, stat in report.items():
        if stat not in STATS:
            raise ValueError(f"the mix reports {metric!r} as {stat!r}, "
                             f"not a statistic: known {sorted(STATS)}")
    names = [m["name"] for m in e2e if m["name"] != "setup_s"]
    missing = [n for n in names if n not in report]
    if missing:
        raise ValueError(f"the mix does not report {missing}")
    return {n: report[n] for n in names}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, root: str = ROOT, workers=None,
             t0: float = T0) -> tuple:
    """One run: set-up, window, checks.  Returns ``(info, result)``: the
    earlier line's details and the result object.  Raises :class:`NoChip`
    before any result when the chip is missing."""
    bench, cell, cfg, mix = load_cell(name, root)
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    stats = reported(mix, e2e)
    # The compile cache lives in the checkout, at a fixed path, with no size
    # cap that could evict one cell's programs for another's.  Set before
    # JAX is imported: it reads both when it loads.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    import lighthouse_tpu  # noqa: F401  (fail before any worker starts)

    traffic = gen.open_traffic(cfg, mix, seed, root)
    workers = workers or max(1, min(12, (os.cpu_count() or 2) - 1))
    setup = {}
    with gen.Prefetch(traffic, mix["warmup_items"], mix["pool_items"],
                      workers) as pf:
        import jax

        devices = bring_up(cell["chips"], require_tpu)
        setup["chip_s"] = time.perf_counter() - t0
        from lighthouse_tpu.client import assemble_device_path

        assemble_device_path("jax")
        counter = _CompileCounter()
        pf.shared()
        warm = pf.warmup()
        setup["traffic_first_s"] = time.perf_counter() - t0
        warm_wrong = 0
        for raw in warm:
            t = time.perf_counter()
            warm_wrong += traffic.call(traffic.request(raw)) is not raw["expect"]
            setup.setdefault("warmup_calls_s", []).append(time.perf_counter() - t)
        pool = pf.pool()
    setup_s = time.perf_counter() - t0
    setup["pool_items"] = len(pool)

    marks = _route_marks()
    counter.on = True
    lock = threading.Lock()
    issued = [0]
    done, errors = [], []
    n_trace = mix["trace_requests"] if trace else 0
    traced = threading.Event()

    def next_request():
        with lock:
            i = issued[0]
            issued[0] += 1
        raw = pool[i] if i < len(pool) else gen.make_inline(traffic, "window", i)
        return raw, traffic.request(raw)

    def fire(job, due):
        raw, req = job
        t = time.perf_counter() if due is None else due
        try:
            got = traffic.call(req)
        except Exception as e:  # noqa: BLE001 — a failed request, counted
            got = None
            errors.append(f"{type(e).__name__}: {e}")
        t1 = time.perf_counter()
        with lock:
            done.append({"start": t, "end": t1, "got": got,
                         "expect": raw["expect"], "work": traffic.work(raw)})
            if len(done) == n_trace:
                traced.set()

    failure = []

    def drive():
        try:
            traffic.drive(next_request, fire, deadline)
        except BaseException as e:  # noqa: BLE001 — raised again below
            failure.append(e)

    w0 = time.perf_counter()
    deadline = w0 + seconds
    driver = threading.Thread(target=drive, name="bench-loop")
    if trace:
        # The profiler records every device op (~200k a block), so it traces
        # only the window's first ``trace_requests`` requests: more would
        # drop trace buffers and take minutes to read.
        profiler_dir = os.path.join(root, ".bench_trace", f"{name}-{seed}")
        shutil.rmtree(profiler_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(profiler_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench_window"):
            t_start = time.perf_counter()
            driver.start()
            traced.wait(timeout=seconds)
            t_end = time.perf_counter()
        jax.profiler.stop_trace()
    else:
        driver.start()
    driver.join()
    if failure:
        raise failure[0]
    w_end = time.perf_counter()
    counter.on = False
    route = _route_since(marks)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    mismatches = warm_wrong + sum(r["got"] is not None and r["got"] is not r["expect"]
                                  for r in done)
    checks = {"verdict_mismatches": mismatches, "request_errors": len(errors),
              "host_fallbacks": route["host_fallbacks"],
              "host_routed_batches": route["host_routed_batches"]}
    correct = bool(done) and all(checks[k] <= LIMITS[k] for k in LIMITS)
    window = {"latency_ms": [(r["end"] - r["start"]) * 1e3 for r in done],
              "completed": len(done), "window_s": w_end - w0}
    lat = window["latency_ms"]
    info = {
        "workload": name, "seed": seed, "requests": len(done),
        "p50_ms": STATS["latency_p50"](window) if lat else None,
        "p90_ms": STATS["latency_p90"](window) if lat else None,
        "max_ms": max(lat) if lat else None,
        "window_s": window["window_s"],
        "tampered": sum(not r["expect"] for r in done),
        "pool_items": len(pool), "pool_outrun": issued[0] > len(pool),
        "compiles_in_window": counter.counts,
        "setup": setup, "batches": len(route["records"]),
        "lost_flight_records": route["lost_records"],
        "errors": errors[:5],
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(done),
              "failed": mismatches + len(errors), "metrics": {}, "device": device}
    units = {m["name"]: m["unit"] for m in e2e}
    if not trace:
        result["metrics"]["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
        if lat:
            for metric, stat in stats.items():
                result["metrics"][metric] = {"value": STATS[stat](window),
                                             "unit": units[metric]}
    else:
        device_trace = trace_mod.DeviceTrace.from_dir(profiler_dir, "bench_window")
        shutil.rmtree(profiler_dir, ignore_errors=True)
        in_trace = [r for r in done if t_start <= r["start"] and r["end"] <= t_end]
        ctx = {"cell": cell, "config": cfg, "mix": mix,
               "spans": _spans_in(w0, w_end), "flight": route["records"],
               "device": device_trace, "device_kind": devices[0].device_kind,
               "work": [r["work"] for r in in_trace]}
        for m in per_layer_for(bench, cell):
            value = metric_reader(m["name"], root)(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = device_trace.busy_s
        device["window_s"] = device_trace.window_s
        result["breakdown"] = breakdown.breakdown(
            device_trace, ctx["spans"], [(r["start"], r["end"]) for r in in_trace],
            t_start)
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]} for k, v in checks.items()}
    return info, result


def report(info: dict, result: dict) -> None:
    """The earlier line, the numbers compared (last on stderr) and the
    result (last on stdout)."""
    _say(info)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr,
              flush=True)
    _say(result)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        info, result = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace))
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    finally:
        if "lighthouse_tpu.device_pipeline" in sys.modules:
            sys.modules["lighthouse_tpu.device_pipeline"].shutdown()
    report(info, result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
