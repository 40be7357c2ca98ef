"""One run of one cell: set-up, the measured window, the check against the
reference, and the result line.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is found by name from ``BENCHMARK.json``: ``bench/configs/``,
``bench/traffic/``, ``bench/metrics/<name before the first dot>.py`` and
``bench/limits/<cell>.json``.  A configuration with a ``model`` object also
serves questions through the program's model canonicalizer, whose weights
and plain reference come from ``bench/models/<reference>.py``.
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
import types
from typing import Callable, Optional

import numpy as np

from . import grammar, nl
from . import trace as tr
from . import traffic as tf
from .data import BENCH, Data, generate, rng_for, to_dataset
from .reference import (Reference, compare, intent_of_signature, match_measures,
                        measure_columns)

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


def build_service(data: Data, schema_name: str, nl_canon=None):
    """The service with one tenant over the data; ``nl_canon`` is its
    canonicalizer of questions, if any."""
    import importlib

    from repro.core import SemanticCache
    from repro.olap.executor import OlapExecutor
    from repro.service import CacheService

    schema = importlib.import_module(f"repro.workloads.{schema_name}").build_schema()
    ds = to_dataset(data, schema)
    backend = OlapExecutor(ds)
    svc = CacheService()
    svc.register_tenant(TENANT, schema=schema, backend=backend,
                        cache=SemanticCache(schema, level_mapper=ds.level_mapper()), nl=nl_canon)
    return svc, backend, ds


# ------------------------------------------------------------------- model
def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class EngineTap:
    """Stands between the canonicalizer service and the program's serving
    engine: passes every ``generate`` call on with the configuration's
    ``max_new_tokens``, and keeps each call's prompts with
    the token ids served for them; ``window()`` counts the work after the
    window has closed."""

    def __init__(self, engine, tok, max_new_tokens: int):
        self.engine = engine
        self.tok = tok
        self.max_new_tokens = max_new_tokens
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.calls: list[list[tuple[str, list[int]]]] = []
            self.steps0 = self.engine.steps

    @property
    def served(self) -> list[tuple[str, list[int]]]:
        return [pt for call in self.calls for pt in call]

    def generate(self, prompts: list[str], max_new_tokens: Optional[int] = None,
                 constrained: bool = False) -> list[dict]:
        outs = self.engine.generate(prompts, max_new_tokens=self.max_new_tokens,
                                    constrained=constrained)
        with self._lock:
            self.calls.append([(p, [int(t) for t in o["tokens"]]) for p, o in zip(prompts, outs)])
        return outs

    def window(self) -> dict:
        """calls, prompts, prompt_tokens, prefill_tokens (batch x longest
        prompt: the left-padded prefill), generated_tokens, decode_steps."""
        c = dict.fromkeys(("prompts", "prompt_tokens", "prefill_tokens",
                           "generated_tokens"), 0)
        for call in self.calls:
            lens = [len(self.tok.encode(p, add_bos=True)) for p, _ in call]
            c["prompts"] += len(call)
            c["prompt_tokens"] += sum(lens)
            c["prefill_tokens"] += len(call) * max(lens)
            c["generated_tokens"] += sum(len(t) for _, t in call)
        return {"calls": len(self.calls), **c,
                "decode_steps": self.engine.steps - self.steps0}


@dataclasses.dataclass
class ModelInfo:
    """What a per-layer reader learns of the model: the program's
    ``ModelConfig`` as served and the window's counters (``EngineTap``)."""
    config: object
    counters: dict


class Model:
    """The configuration's ``model``: the program's architecture
    (``repro.configs.registry.get(arch)`` with ``overrides``), weights made
    by ``bench/models/<reference>.py`` from the seed, the program's
    ``ServingEngine`` behind ``MemoizedNL(CanonicalizerService(...))``, as
    ``launch/serve.py`` builds them, and a schema header of ``header_words``
    words."""

    def __init__(self, spec: dict, data: Data, schema_name: str, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.configs.registry import get
        from repro.core import MemoizedNL
        from repro.serving.engine import CanonicalizerService, ServingEngine
        from repro.training.tokenizer import build_tokenizer

        self.cfg = dataclasses.replace(get(spec["arch"]), **spec.get("overrides", {}))
        self.plain = {f.name: getattr(self.cfg, f.name)
                      for f in dataclasses.fields(self.cfg) if f.name != "dtype"}
        self.plain["dtype"] = jnp.dtype(self.cfg.dtype).name
        vocab = importlib.import_module(f"repro.workloads.{schema_name}").build_vocab()
        self.tok = build_tokenizer([types.SimpleNamespace(name=schema_name, vocab=vocab)])
        if self.cfg.vocab < self.tok.vocab_size:
            raise ValueError(f"model vocab {self.cfg.vocab} is narrower than the "
                             f"tokenizer's {self.tok.vocab_size} ids")
        self.vocab = [self.tok.id_to_str(i) for i in range(self.tok.vocab_size)]
        self.max_len = int(spec["max_len"])
        self.max_new_tokens = int(spec["max_new_tokens"])
        self.reference = load_module("models", spec["reference"])
        key = jax.random.PRNGKey(int(rng_for(seed, 301).integers(0, 2**31 - 1)))
        self.params = self.reference.init(self.plain, key)
        jax.block_until_ready(self.params)
        self.engine = ServingEngine(self.cfg, self.params, self.tok, max_len=self.max_len)
        self.tap = EngineTap(self.engine, self.tok, self.max_new_tokens)
        self.header = nl.header(data, int(spec["header_words"]))
        self.canon = MemoizedNL(CanonicalizerService(self.tap, schema_name,
                                                     prompt_header=self.header))

    def prompt(self, question: str) -> str:
        """The text the canonicalizer service makes of a question."""
        return f"{self.header}question: {question}\nsignature: "

    def warm(self, sched: tf.Schedule) -> int:
        """Run each (batch, prompt length) that the schedule's submits of
        questions give through the engine once, so that the window compiles
        nothing; returns the number of shapes.  Raises where a prompt would
        not fit the engine's ``max_len`` with the tokens it may generate."""
        budget = self.max_new_tokens
        shapes: dict[tuple[int, int], list[str]] = {}
        head = len(self.tok.encode(self.header, add_bos=True))
        for submit in sched.warmup + [[r] for r in sched.requests] + sched.dashboards:
            texts = list(dict.fromkeys(r.nl for r in submit if r.nl is not None))
            if not texts:
                continue
            # the header ends in a newline, so a prompt's ids are the
            # header's followed by the question's
            plen = head + max(len(self.tok.encode(self.prompt(t)[len(self.header):]))
                              for t in texts)
            if plen > self.max_len // 2 or plen + budget > self.max_len:
                raise ValueError(f"a prompt of {plen} tokens with {budget} new ones does not "
                                 f"fit the engine's max_len {self.max_len}, which keeps "
                                 f"{self.max_len // 2} prompt tokens: shorten header_words")
            shapes.setdefault((len(texts), plen), [self.prompt(t) for t in texts])
        for (b, plen), prompts in sorted(shapes.items()):
            if max(len(self.tok.encode(p, add_bos=True)) for p in prompts) != plen:
                raise RuntimeError("the tokenizer does not encode a prompt as its header "
                                   "followed by its question")
            self.engine.generate(prompts, max_new_tokens=2, constrained=False)
        return len(shapes)

    def release(self) -> None:
        """Drop the program's engine and canonicalizer; the weights stay for
        the reference."""
        self.engine = self.canon = None
        self.tap.engine = None


# ------------------------------------------------------------------ logits
def round_weights(params, precision: str):
    """Every weight matrix (the last two axes of a leaf) rounded to
    ``float8_e4m3fn`` with a scale of its own that maps its largest
    magnitude to the format's largest, back in float32; vectors unchanged.
    The rounding is made on the host by ``ml_dtypes``: on the chip XLA may
    fold a float32 -> float8 -> float32 round trip into nothing."""
    import jax
    import ml_dtypes

    if precision != "float8":
        raise ValueError(f"no weight control {precision!r}")
    top = float(ml_dtypes.finfo(ml_dtypes.float8_e4m3fn).max)

    def one(w):
        w = np.asarray(w, np.float32)
        if w.ndim < 2:
            return jax.device_put(w)
        scale = np.abs(w).max(axis=(-2, -1), keepdims=True) / top
        scale = np.where(scale > 0, scale, 1.0)
        low = (w / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * scale
        if np.array_equal(low, w):
            raise RuntimeError("float8 rounding left a weight matrix unchanged")
        return jax.device_put(low)

    return jax.tree.map(one, params)


def sample_served(served: list[tuple[str, list[int]]], seed: int, k: int) -> list:
    """Prompts with their served tokens, drawn from the seed: the one with
    the most tokens, then others at random up to ``k``."""
    pool = sorted({(p, tuple(t)) for p, t in served if t})
    if not pool:
        return []
    rng = rng_for(seed, 202)
    longest = max(range(len(pool)), key=lambda i: len(pool[i][1]))
    rest = [i for i in rng.permutation(len(pool)) if i != longest]
    return [pool[i] for i in [longest] + rest[: max(0, k - 1)]]


def check_logits(model: Model, seed: int, k: int, control: Optional[str] = None) -> dict:
    """Read each sampled prompt with its served tokens through the plain
    reference, and take at every served position the gap by which the served
    token's logit lies below the best logit of a token the grammar allows
    there, over the spread (standard deviation) of the reference's row.
    With ``control`` the reference at that lower precision takes the
    program's place: at each position its best allowed token is read."""
    pick = sample_served(model.tap.served, seed, k)
    params = model.params
    low = round_weights(params, control) if control else None
    V = len(model.vocab)
    out = {"prompts": len(pick), "positions": 0, "program": 0.0, "control": 0.0}
    for prompt, served in pick:
        ids = model.tok.encode(prompt, add_bos=True)
        seq = ids + list(served)
        # one program for every sequence: pad to max_len at the end, where
        # the causal mask keeps the padding from every row that is read
        padded = seq + [model.tok.pad] * (model.max_len - len(seq))
        rows = slice(len(ids) - 1, len(seq) - 1)
        ref = np.asarray(model.reference.logits(model.plain, params, padded))[rows, :V]
        ctl = None if low is None else \
            np.asarray(model.reference.logits(model.plain, low, padded))[rows, :V]
        text = ""
        for j, t in enumerate(served):
            allowed = grammar.legal(text, model.vocab)
            row = ref[j].astype(np.float64)
            spread = float(row.std()) or 1.0
            best = float(row[allowed].max()) if allowed.any() else math.inf
            gap = (best - row[t]) / spread if allowed[t] else math.inf
            out["program"] = max(out["program"], gap)
            if ctl is not None:
                c = int(np.argmax(np.where(allowed, ctl[j], -np.inf)))
                out["control"] = max(out["control"], (best - row[c]) / spread)
            out["positions"] += 1
            text += model.vocab[t]
    out["program"], out["control"] = float(out["program"]), float(out["control"])
    out["max_logit_gap"] = out["control"] if control else out["program"]
    return out


# ------------------------------------------------------------------ window
class Recorder:
    def __init__(self):
        self.records: list[dict] = []
        self.submits: list[dict] = []
        self.pool_short = 0  # closed loop: clients that found the pool used up
        self._lock = threading.Lock()

    def submit(self, svc, reqs: list, t0: float, trace_on: bool, due=None) -> None:
        from repro.service import QueryRequest

        batch = [QueryRequest(sql=r.sql, tenant=TENANT) if r.nl is None
                 else QueryRequest(nl=r.nl, tenant=TENANT) for r in reqs]
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
def outcome(r: dict) -> str:
    """A SQL request is 'ok' or 'failed', as the parent counted it.  A
    question is 'answered' (a table came back, whatever its status),
    'refused' (no table: the canonicalizer, the validator or the gate
    declined it) or 'failed' (an error, a degraded answer, or nothing)."""
    q = r["result"]
    if r["request"].nl is None:
        return "failed" if q.status in ("error", "degraded", "bypass") else "ok"
    if q.status in ("error", "degraded"):
        return "failed"
    if q.table is not None:
        return "answered"
    return "refused" if q.status == "bypass" else "failed"


def served_intent(r: dict, date_column: Optional[str] = None) -> Optional[dict]:
    """The intent an answer is compared against: the rendered one for SQL,
    the one of the signature the program served for a question (None where
    the reference cannot compute it)."""
    if r["request"].nl is None:
        return r["request"].intent
    sig = r["result"].signature
    return None if sig is None else intent_of_signature(sig.to_json(), date_column)


def sample_intents(records: list[dict], seed: int, k: int,
                   date_column: Optional[str] = None) -> list[str]:
    """Intents to check, drawn from the seed: one for each way of being
    served that the window shows, the one with the largest answer, then
    others at random up to ``k``."""
    rng = rng_for(seed, 201)
    by_way: dict[str, list[str]] = {}
    sizes: dict[str, int] = {}
    for r in records:
        q = r["result"]
        intent = None if q.table is None else served_intent(r, date_column)
        if intent is None:
            continue
        key = tf.intent_key(intent)
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
          precision: Optional[str] = None, date_column: Optional[str] = None) -> dict:
    """Compare every served answer of the chosen intents with the reference.
    With ``precision``, the reference computed at that precision takes the
    program's place (the control)."""
    want: dict[str, object] = {}
    n, wrong, worst = 0, 0, 0.0
    for r in records:
        q = r["result"]
        intent = None if q.table is None else served_intent(r, date_column)
        key = None if intent is None else tf.intent_key(intent)
        if key not in keys:
            continue
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
    return load_module("metrics", metric.split(".", 1)[0]).read


@dataclasses.dataclass
class Context:
    records: list[dict]
    submits: list[dict]
    trace: Optional[dict]
    peaks: Optional[dict]
    memory: Optional[dict]
    model: Optional[ModelInfo] = None


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------------- run
def run(workload: str, seed: int, seconds: float, trace_on: bool, t_start: float,
        require_tpu: bool = True, control: Optional[str] = None,
        config_override: Optional[dict] = None,
        mix_override: Optional[dict] = None, cell: Optional[Cell] = None) -> dict:
    """One run; returns the result object (the last line of stdout).  A
    ``cell`` given takes the place of the one ``BENCHMARK.json`` names."""
    cell = cell or load_cell(workload)
    config = {**cell.config, **(config_override or {})}
    mix = {**cell.mix, **(mix_override or {})}
    limits = cell.limits
    _program()
    jax, devs, peaks = start_jax(cell.workload["chips"], require_tpu)

    data = generate(config, seed)
    log(f"data: {data.num_rows:,} {data.fact} rows ({time.perf_counter() - t_start:.1f} s)")
    model = Model(config["model"], data, config["schema"], seed) if "model" in config else None
    if control == "float8" and model is None:
        raise ValueError("the float8 control reads a model's logits; the cell has no model")
    svc, backend, ds = build_service(data, config["schema"],
                                     None if model is None else model.canon)
    fact_date = ds.schema.fact.date_column
    date_column = None if fact_date is None else f"{data.fact}.{fact_date}"
    sched = tf.schedule(cell.workload["traffic"], data, seed, seconds, mix=mix)
    asks = any(r.nl is not None for sub in sched.warmup + [sched.requests] + sched.dashboards
               for r in sub)
    if asks and model is None:
        raise ValueError("the mix sends questions; the configuration has no model")
    warm_rec = Recorder()
    for reqs in sched.warmup:
        warm_rec.submit(svc, reqs, time.perf_counter(), False)
    if model is not None:
        sent = {p for p, _ in model.tap.served}
        made = {model.prompt(r.nl) for reqs in sched.warmup for r in reqs if r.nl is not None}
        if sent != made:
            raise RuntimeError("the canonicalizer's prompts are not the ones Model.prompt makes")
        log(f"model: {model.warm(sched)} prompt shapes warmed")
        model.canon.clear()
        model.tap.reset()
    svc.invalidate(TENANT, schema_change=True)
    bad = [r for r in warm_rec.records if outcome(r) == "failed"]
    for r in bad[:3]:
        log(f"warm-up: {r['status']} {r['result'].error} {r['request'].nl or r['request'].sql}")
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
    model_info = None
    if model is not None:
        model_info = ModelInfo(model.cfg, model.tap.window())
        model.release()
    del svc, backend
    ds._device = None
    gc.collect()

    # --------------------------------------------------------- end to end
    ends = {r["rid"]: outcome(r) for r in rec.records}
    if sched.loop == "open":
        attempted = len(sched.requests)
        lat = {r["rid"]: r["done"] - r["due"] for r in rec.records
               if ends[r["rid"]] != "failed"}
        served_ok = len(lat)
        lats = [lat.get(r.rid, math.inf) * 1e3 for r in sched.requests]
        e2e = {"p50_ms": percentile(lats, 0.50), "p95_ms": percentile(lats, 0.95)}
    else:
        attempted = len(rec.records)
        served_ok = sum(ends[r["rid"]] != "failed" for r in rec.records)
        in_window = sum(ends[r["rid"]] != "failed" and r["done"] <= seconds
                        for r in rec.records)
        e2e = {"qps": in_window / seconds}
    failed = attempted - served_ok
    e2e["setup_s"] = setup_s

    # -------------------------------------------------------- correctness
    ref = Reference(data)
    t_ref = time.perf_counter()
    keys = sample_intents(rec.records, seed, limits["sample_intents"], date_column)
    got = check(rec.records, ref, keys, date_column=date_column)
    log(f"reference: {got['compared']} answers of {got['distinct']} intents in "
        f"{time.perf_counter() - t_ref:.1f} s")
    if control == "bfloat16":
        # the control takes the program's place in the comparison; the
        # program's own reading is printed beside it for the limits' readings
        log(f"program: wrong_keys {got['wrong_keys']} max_err {got['max_err']!r}")
        got = check(rec.records, ref, keys, precision=control, date_column=date_column)
        log(f"control {control}: wrong_keys {got['wrong_keys']} max_err {got['max_err']!r}")
    checks = {
        "unserved": {"value": failed, "limit": 0},
        "wrong_keys": {"value": got["wrong_keys"], "limit": 0},
        "max_err": {"value": got["max_err"], "limit": limits["max_err"]},
    }
    if sched.loop == "closed":
        checks["pool_short"] = {"value": rec.pool_short, "limit": 0}
    compared = got["compared"] > 0
    if model is not None:
        t_ref = time.perf_counter()
        lg = check_logits(model, seed, limits["sample_prompts"],
                          control if control == "float8" else None)
        log(f"reference model: {lg['positions']} served tokens of {lg['prompts']} prompts in "
            f"{time.perf_counter() - t_ref:.1f} s; program max_logit_gap {lg['program']!r}"
            + (f", control {control} {lg['control']!r}" if control == "float8" else ""))
        checks["max_logit_gap"] = {"value": lg["max_logit_gap"],
                                   "limit": limits["max_logit_gap"]}
        # answers are compared where any came back that the reference computes
        compared = lg["positions"] > 0 and (compared or not any(
            r["result"].table is not None and served_intent(r, date_column) is not None
            for r in rec.records))
    correct = compared and all(c["value"] <= c["limit"] for c in checks.values())

    # ---------------------------------------------------------- per layer
    metrics: dict = {}
    if trace_on:
        for s in rec.submits:
            miss = [served_intent(r, date_column) for r in s["records"]
                    if r["status"] == "miss"]
            miss = [it for it in miss if it is not None]
            if miss:
                s["selected_rows"] = ref.selected_rows(miss)
                cols = {c for it in miss for _, e in it["measures"] for c in measure_columns(e)}
                s["columns"] = len(cols) + int(any(it["levels"] for it in miss))
        ctx = Context(rec.records, rec.submits, reduced, peaks, mem, model_info)
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
    if asks:
        ends_nl = [ends[r["rid"]] for r in rec.records if r["request"].nl is not None]
        out["nl_answered"] = ends_nl.count("answered")
        out["nl_refused"] = ends_nl.count("refused")
    kinds: dict[str, int] = {}
    for r in rec.records:
        way = r["status"] + (":batched" if r["batched"] else "")
        kinds[way] = kinds.get(way, 0) + 1
    log(f"served: {json.dumps(kinds, sort_keys=True)}")
    log(f"median service ms by kind: {json.dumps(summarize_latency(rec.records))}")
    if mem:
        log(f"memory: {json.dumps(mem)}")
    if model_info is not None:
        log(f"model: {json.dumps(model_info.counters)}")
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
