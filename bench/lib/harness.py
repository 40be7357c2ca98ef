"""One run of one cell: set-up, the measured window, the check against the
reference, and the result line.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is found by name from ``BENCHMARK.json``: ``bench/configs/``,
``bench/traffic/``, ``bench/metrics/<name before the first dot>.py`` and
``bench/limits/<cell>.json``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import threading
import time
from typing import Callable, Optional

import numpy as np

from . import trace as tr
from . import traffic as tf
from .data import BENCH, Data, generate, rng_for, to_dataset
from .reference import Reference, compare, match_measures, measure_columns

ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
TENANT = "bench"
DRAIN_S = 60.0  # how long past the window's close a due request may still be answered


class NoChip(Exception):
    """The run cannot measure here: no TPU, too few chips, unknown device."""


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    mix: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str) -> Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "limits", f"{name}.json")) as f:
        limits = json.load(f)

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name, wl, config, tf.load(wl["traffic"]), limits,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def peak_for(kind: str) -> dict:
    """The peaks of a device kind from ``bench/peaks.json``; an unknown kind
    is an error, never a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise NoChip(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


def start_jax(chips: int, require_tpu: bool):
    """JAX on the chip, with its compilation cache in ``.jax_cache`` inside the
    checkout: the benchmark gives the directory, the program's
    ``enable_compilation_cache`` turns the cache on."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no size cap: JAX's capped cache keeps access-time files beside each
    # entry and stops writing once one of them is missing
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    if not require_tpu:
        return jax, devs, None
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return jax, devs, peak_for(devs[0].device_kind)


def _program():
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise NoChip(f"no program under {SRC}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def build_service(data: Data, schema_name: str):
    import importlib

    from repro.core import SemanticCache
    from repro.olap.executor import OlapExecutor
    from repro.service import CacheService

    schema = importlib.import_module(f"repro.workloads.{schema_name}").build_schema()
    ds = to_dataset(data, schema)
    backend = OlapExecutor(ds)
    svc = CacheService()
    svc.register_tenant(TENANT, schema=schema, backend=backend,
                        cache=SemanticCache(schema, level_mapper=ds.level_mapper()))
    return svc, backend, ds


# ------------------------------------------------------------------ window
class Recorder:
    def __init__(self):
        self.records: list[dict] = []
        self.submits: list[dict] = []
        self.pool_short = 0  # closed loop: clients that found the pool used up
        self._lock = threading.Lock()

    def submit(self, svc, reqs: list, t0: float, trace_on: bool, due=None) -> None:
        from repro.service import QueryRequest

        batch = [QueryRequest(sql=r.sql, tenant=TENANT) for r in reqs]
        start = time.perf_counter()
        if trace_on:
            with tr.span("submit_batch"):
                res = svc.submit_batch(batch)
        else:
            res = svc.submit_batch(batch)
        done = time.perf_counter()
        recs = []
        for r, q in zip(reqs, res):
            recs.append({
                "rid": r.rid, "request": r, "result": q, "status": q.status,
                "batched": q.batched, "provenance": q.provenance,
                "timings": dict(q.timings_ms),
                "start": start - t0, "done": done - t0,
                "due": (r.due if due is None else due),
            })
        sub = {"start": start - t0, "done": done - t0, "records": recs,
               "misses": sum(q.status == "miss" for q in res),
               "execute_ms": max((q.timings_ms.get("execute", 0.0) for q in res),
                                 default=0.0),
               "selected_rows": None, "columns": None}
        with self._lock:
            self.records.extend(recs)
            self.submits.append(sub)


def open_loop(svc, sched: tf.Schedule, seconds: float, trace_on: bool, rec: Recorder):
    reqs = sched.requests
    nxt = [0]
    lock = threading.Lock()
    t0 = time.perf_counter()

    def worker():
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= len(reqs):
                return
            r = reqs[i]
            wait = t0 + r.due - time.perf_counter()
            if wait > 0:
                if trace_on:
                    with tr.span("wait_arrival"):
                        time.sleep(wait)
                else:
                    time.sleep(wait)
            if time.perf_counter() - t0 > seconds + DRAIN_S:
                return
            rec.submit(svc, [r], t0, trace_on)

    threads = [threading.Thread(target=worker) for _ in range(sched.workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t0


def closed_loop(svc, sched: tf.Schedule, seconds: float, trace_on: bool, rec: Recorder):
    """Clients take dashboards from the pool in order; a client that finds the
    pool used up before the window closes stops and is counted in
    ``rec.pool_short``: no dashboard is sent twice."""
    queue = sched.dashboards
    nxt = [0]
    lock = threading.Lock()
    t0 = time.perf_counter()

    def client():
        while time.perf_counter() - t0 < seconds:
            with lock:
                i = nxt[0]
                nxt[0] += 1
                if i >= len(queue):
                    rec.pool_short += 1
                    return
            rec.submit(svc, queue[i], t0, trace_on, due=time.perf_counter() - t0)

    threads = [threading.Thread(target=client) for _ in range(sched.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t0


# ------------------------------------------------------------------ checks
def sample_intents(records: list[dict], seed: int, k: int) -> list[str]:
    """Intents to check, drawn from the seed: one for each way of being
    served that the window shows, the one with the largest answer, then
    others at random up to ``k``."""
    rng = rng_for(seed, 201)
    by_way: dict[str, list[str]] = {}
    sizes: dict[str, int] = {}
    for r in records:
        q = r["result"]
        if q.table is None:
            continue
        key = tf.intent_key(r["request"].intent)
        way = q.status + (":batched" if q.batched else "") + \
            (":isolated_retry" if "execute:isolated_retry" in q.provenance else "")
        by_way.setdefault(way, []).append(key)
        sizes[key] = max(sizes.get(key, 0), q.table.num_rows)
    chosen: list[str] = []
    for way in sorted(by_way):
        keys = sorted(set(by_way[way]))
        chosen.append(keys[int(rng.integers(len(keys)))])
    if sizes:
        chosen.append(max(sorted(sizes), key=lambda x: sizes[x]))
    rest = sorted(set(sizes) - set(chosen))
    rng.shuffle(rest)
    chosen += rest[: max(0, k - len(set(chosen)))]
    return sorted(set(chosen))


def check(records: list[dict], ref: Reference, keys: list[str],
          precision: Optional[str] = None) -> dict:
    """Compare every served answer of the chosen intents with the reference.
    With ``precision``, the reference computed at that precision takes the
    program's place (the control)."""
    want: dict[str, object] = {}
    n, wrong, worst = 0, 0, 0.0
    for r in records:
        q = r["result"]
        key = tf.intent_key(r["request"].intent)
        if key not in keys or q.table is None:
            continue
        intent = r["request"].intent
        if key not in want:
            want[key] = ref.table(intent)
        n += 1
        if precision is None:
            got = q.table.columns
            mapping = match_measures(intent["measures"],
                                     [(m.agg, m.expr) for m in q.signature.measures])
        else:
            ctl = ref.table(intent, precision)
            got = {lv: np.asarray([k[i] for k in ctl.keys], dtype=object)
                   for i, lv in enumerate(ctl.levels)}
            got.update({f"m{j}": ctl.values[:, j] for j in range(ctl.values.shape[1])})
            mapping = list(range(ctl.values.shape[1]))
        if mapping is None:
            wrong += 1
            continue
        ok, err = compare(want[key], got, mapping)
        if not ok:
            wrong += 1
        else:
            worst = max(worst, err)
    return {"compared": n, "distinct": len(want), "wrong_keys": wrong, "max_err": worst}


# ---------------------------------------------------------------- metrics
def percentile(xs: list[float], q: float) -> float:
    s = sorted(xs)
    if not s:
        return math.inf
    pos = q * (len(s) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def load_reader(metric: str) -> Callable:
    base = metric.split(".", 1)[0]
    path = os.path.join(BENCH, "metrics", f"{base}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    records: list[dict]
    submits: list[dict]
    trace: Optional[dict]
    peaks: Optional[dict]
    memory: Optional[dict]


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------------- run
def run(workload: str, seed: int, seconds: float, trace_on: bool, t_start: float,
        require_tpu: bool = True, control: Optional[str] = None,
        config_override: Optional[dict] = None,
        mix_override: Optional[dict] = None) -> dict:
    """One run; returns the result object (the last line of stdout)."""
    cell = load_cell(workload)
    config = {**cell.config, **(config_override or {})}
    mix = {**cell.mix, **(mix_override or {})}
    _program()
    jax, devs, peaks = start_jax(cell.workload["chips"], require_tpu)

    data = generate(config, seed)
    log(f"data: {data.num_rows:,} {data.fact} rows ({time.perf_counter() - t_start:.1f} s)")
    svc, backend, ds = build_service(data, config["schema"])
    sched = tf.schedule(cell.workload["traffic"], data, seed, seconds, mix=mix)
    warm_rec = Recorder()
    for reqs in sched.warmup:
        warm_rec.submit(svc, reqs, time.perf_counter(), False)
    svc.invalidate(TENANT, schema_change=True)
    bad = [r for r in warm_rec.records if r["status"] in ("error", "degraded", "bypass")]
    for r in bad[:3]:
        log(f"warm-up: {r['status']} {r['result'].error} {r['request'].sql}")
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.1f} s: {len(warm_rec.records)} warm-up requests")

    rec = Recorder()
    loop = open_loop if sched.loop == "open" else closed_loop
    logdir = os.path.join(BENCH, "out", "trace", workload)
    if trace_on:
        with tr.record(logdir):
            with tr.span("window"):
                t0 = loop(svc, sched, seconds, True, rec)
    else:
        t0 = loop(svc, sched, seconds, False, rec)
    t_close = time.perf_counter() - t0
    log(f"window: {len(rec.records)} requests in {len(rec.submits)} submits, "
        f"closed {t_close:.1f} s after it opened")

    mem = None
    stats = devs[0].memory_stats() if hasattr(devs[0], "memory_stats") else None
    if stats:
        mem = {"peak_bytes_in_use": max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devs[:cell.workload["chips"]]),
               "bytes_limit": int(stats.get("bytes_limit", 0))}
    reduced = None
    if trace_on:
        reduced = tr.reduce(tr.read(logdir, "tpu" if devs[0].platform == "tpu" else "cpu"))
        shutil.rmtree(logdir, ignore_errors=True)  # the trace is read; keep the disk clean
    del svc, backend
    ds._device = None
    gc.collect()

    # --------------------------------------------------------- end to end
    if sched.loop == "open":
        attempted = len(sched.requests)
        lat = {r["rid"]: r["done"] - r["due"] for r in rec.records
               if r["status"] not in ("error", "degraded", "bypass")}
        served_ok = len(lat)
        lats = [lat.get(r.rid, math.inf) * 1e3 for r in sched.requests]
        e2e = {"p50_ms": percentile(lats, 0.50), "p95_ms": percentile(lats, 0.95)}
    else:
        attempted = len(rec.records)
        served_ok = sum(r["status"] not in ("error", "degraded", "bypass")
                        for r in rec.records)
        in_window = sum(r["status"] not in ("error", "degraded", "bypass")
                        and r["done"] <= seconds for r in rec.records)
        e2e = {"qps": in_window / seconds}
    failed = attempted - served_ok
    e2e["setup_s"] = setup_s

    # -------------------------------------------------------- correctness
    ref = Reference(data)
    t_ref = time.perf_counter()
    keys = sample_intents(rec.records, seed, cell.limits["sample_intents"])
    got = check(rec.records, ref, keys)
    log(f"reference: {got['compared']} answers of {got['distinct']} intents in "
        f"{time.perf_counter() - t_ref:.1f} s")
    if control:
        # the control takes the program's place in the comparison; the
        # program's own reading is printed beside it for the limits' readings
        log(f"program: wrong_keys {got['wrong_keys']} max_err {got['max_err']!r}")
        got = check(rec.records, ref, keys, precision=control)
        log(f"control {control}: wrong_keys {got['wrong_keys']} max_err {got['max_err']!r}")
    checks = {
        "unserved": {"value": failed, "limit": 0},
        "wrong_keys": {"value": got["wrong_keys"], "limit": 0},
        "max_err": {"value": got["max_err"], "limit": cell.limits["max_err"]},
    }
    if sched.loop == "closed":
        checks["pool_short"] = {"value": rec.pool_short, "limit": 0}
    correct = (got["compared"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))

    # ---------------------------------------------------------- per layer
    metrics: dict = {}
    if trace_on:
        for s in rec.submits:
            miss = [r["request"].intent for r in s["records"] if r["status"] == "miss"]
            if miss:
                s["selected_rows"] = ref.selected_rows(miss)
                cols = {c for it in miss for _, e in it["measures"] for c in measure_columns(e)}
                s["columns"] = len(cols) + int(any(it["levels"] for it in miss))
        ctx = Context(rec.records, rec.submits, reduced, peaks, mem)
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.workload["chips"],
              "memory_peak_bytes": mem["peak_bytes_in_use"] if mem else 0}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": tr.top(reduced["op_s"]),
                            "idle_gaps": tr.top(reduced["gaps"])}
    kinds: dict[str, int] = {}
    for r in rec.records:
        way = r["status"] + (":batched" if r["batched"] else "")
        kinds[way] = kinds.get(way, 0) + 1
    log(f"served: {json.dumps(kinds, sort_keys=True)}")
    log(f"median service ms by kind: {json.dumps(summarize_latency(rec.records))}")
    if mem:
        log(f"memory: {json.dumps(mem)}")
    if reduced is not None:
        log(f"device programs: {json.dumps(tr.top(reduced['module_s'], 12))}")
    log(f"end to end: {json.dumps({k: round(v, 4) for k, v in e2e.items()})}")
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return out


def summarize_latency(records: list[dict]) -> dict:
    by: dict[str, list[float]] = {}
    for r in records:
        by.setdefault(r["request"].kind, []).append((r["done"] - r["start"]) * 1e3)
    return {k: round(statistics.median(v), 3) for k, v in sorted(by.items())}
