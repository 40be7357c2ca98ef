#!/usr/bin/env python3
"""Bring-up check: the SSB serving path end to end on a TPU, in one process.

One chip (the default) builds SSB ``lineorder`` at ``--rows`` rows (SF1 is
6,000,000) from ``--seed``, registers one tenant on ``CacheService`` with the
default ``OlapExecutor`` and a ``SemanticCache``, and sends through
``submit_batch``:

* a 12-tile SQL dashboard (all misses, one shared scan), then the same
  dashboard with new literals (the first call compiles, the second is warm);
* single tiles with new literals (filter-fused kernel with MIN and MAX, more
  groups than one group tile, a tile without filters);
* a filter on ``lo_extendedprice``, which is not f32-exact and so takes the
  host-mask kernel path;
* a replay of the first dashboard (exact hits) and one roll-up (a
  derivation hit);
* one tile to a second tenant on the per-measure executor
  (``fused=False``), which takes the plain ``seg_agg`` entry point;
* two NL requests through the ``canonicalizer-100m`` model at its published
  width, with random weights from the seed (the repo holds no checkpoint).

Every table served is compared with ``OlapExecutor(impl="numpy")``, and the
flash and decode attention kernels are compared with their references at
the model's shapes.  ``--chips 4`` runs only the partitioned scan instead:
60,000,000 rows over ``OlapExecutor(partitions=4)``, one partition per chip,
checked against the oracle and for placement.

Any failure exits non-zero.  Without a TPU, or without the repository's
``src/`` next to this file, it exits non-zero before printing a result.  The
last line of stdout is ``{"ok": true, "device": {...}}``.  Printed times are
smoke timings of one run, not metrics.

    python3 chip_smoke.py [--seed 0] [--rows 6000000]
    python3 chip_smoke.py --chips 4 [--rows 60000000]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
MFGRS = ["MFGR#1", "MFGR#2", "MFGR#3", "MFGR#4", "MFGR#5"]
JOINS = ("FROM lineorder "
         "JOIN dates ON lineorder.lo_orderdate = dates.d_key "
         "JOIN customer ON lineorder.lo_custkey = customer.c_key "
         "JOIN supplier ON lineorder.lo_suppkey = supplier.s_key "
         "JOIN part ON lineorder.lo_partkey = part.p_key ")
TILE = ("SELECT d_year, c_region, SUM(lo_revenue) AS revenue, COUNT(*) AS orders, "
        "MIN(lo_supplycost) AS min_cost, MAX(lo_revenue) AS max_rev " + JOINS)
# SSB spec sizes at SF1 that the generator holds fixed (workloads/ssb.py)
REDUCED = ("customer 3,000 rows (spec 30,000 x SF), supplier 1,000 "
           "(spec 2,000 x SF), part 1,200 (spec 200,000 x (1 + log2 SF)); "
           "dates 2,557 days 1992-1998 as in the spec")


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def dashboard(shift: int) -> list[str]:
    """12 tiles over one (levels, measures) pair that differ only in
    f32-exact filters, so the miss planner gives them one shared scan."""
    tiles = []
    for i, region in enumerate(REGIONS):
        d = (i + shift) % 8
        tiles.append(TILE + f"WHERE s_region = '{region}' AND lo_discount "
                     f"BETWEEN {d} AND {d + 2} GROUP BY d_year, c_region")
    for i, mfgr in enumerate(MFGRS):
        tiles.append(TILE + f"WHERE p_mfgr = '{mfgr}' AND lo_quantity < "
                     f"{10 + 5 * i + shift} GROUP BY d_year, c_region")
    for i in range(2):
        tiles.append(TILE + f"WHERE s_region = '{REGIONS[i + shift % 3]}' AND "
                     f"p_mfgr = '{MFGRS[i + 2]}' GROUP BY d_year, c_region")
    return tiles


SINGLES = [
    # filter-fused kernel, MIN and MAX blocks
    ("SELECT p_mfgr, MIN(lo_supplycost) AS min_cost, MAX(lo_revenue) AS max_rev, "
     "SUM(lo_quantity) AS qty " + JOINS +
     "WHERE s_region = 'ASIA' AND lo_discount BETWEEN 2 AND 4 GROUP BY p_mfgr"),
    # ~1,400 groups: more than one 512-group tile
    ("SELECT d_year, p_brand, SUM(lo_revenue) AS revenue " + JOINS +
     "WHERE p_category = 'MFGR#14' AND s_region = 'EUROPE' "
     "GROUP BY d_year, p_brand"),
    # no filter at all: the P = 0 path of seg_agg_fused
    ("SELECT d_year, COUNT(*) AS n_orders, MAX(lo_discount) AS max_disc "
     + JOINS + "GROUP BY d_year"),
]
# lo_extendedprice has cents: not exact in f32, so the mask is built on host
HOST_MASK = ("SELECT c_region, SUM(lo_revenue) AS revenue, "
             "MIN(lo_extendedprice) AS min_price " + JOINS +
             "WHERE lo_extendedprice > 5000.5 GROUP BY c_region")
NL = ["Show total revenue by customer region in 1994",
      "number of orders by year for suppliers in asia"]


def device_checks():
    impl_env = os.environ.get("REPRO_KERNELS", "")
    check(impl_env in ("", "pallas"),
          f"REPRO_KERNELS={impl_env!r}: this run checks the compiled kernels only")
    check(os.path.isdir(os.path.join(SRC, "repro")),
          f"no repro package under {SRC}: run from a checkout of the repository")
    sys.path.insert(0, SRC)
    import jax

    check(jax.default_backend() == "tpu",
          f"JAX backend is {jax.default_backend()!r}, not 'tpu'")
    from repro.kernels.seg_agg.ops import kernel_impl
    from repro.launch.compile_cache import enable_compilation_cache

    check(kernel_impl() == "pallas", f"kernel impl is {kernel_impl()!r}")
    print(f"compilation cache: {enable_compilation_cache()}")
    devs = jax.devices()
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"kernel impl: {kernel_impl()}")
    return jax, devs


class Oracle:
    """Tables from the independent numpy executor, memoized by signature."""

    def __init__(self, ds):
        from repro.olap.executor import OlapExecutor

        self.ex = OlapExecutor(ds, impl="numpy")
        self.memo = {}

    def check(self, results, what: str) -> None:
        for r in results:
            check(r.status not in ("error", "degraded"),
                  f"{what}: status {r.status} ({r.error})")
            check(r.table is not None and r.signature is not None,
                  f"{what}: status {r.status} without a table or signature")
            ref = self.memo.get(r.signature)
            if ref is None:
                ref = self.memo[r.signature] = self.ex.execute(r.signature)
            check(r.table.equals(ref),
                  f"{what}: {r.status} table differs from the numpy oracle "
                  f"for {r.signature}")


def run_one(args, jax, devs) -> None:
    from repro.configs.registry import get
    from repro.core import SafetyPolicy, SemanticCache
    from repro.kernels.decode_attn.kernel import decode_attention_pallas
    from repro.kernels.decode_attn.ref import decode_attention_ref
    from repro.kernels.flash_attn.kernel import flash_attention_pallas
    from repro.kernels.flash_attn.ref import mha_ref
    from repro.kernels.seg_agg import ops as seg_ops
    from repro.olap.executor import OlapExecutor
    from repro.serving.engine import CanonicalizerService, ServingEngine
    from repro.service import CacheService, QueryRequest
    from repro.training.tokenizer import build_tokenizer
    from repro.workloads import ssb

    t0 = time.perf_counter()
    wl = ssb.build(n_fact=args.rows, seed=args.seed)
    print(f"rows: {wl.dataset.fact.num_rows:,} lineorder (seed {args.seed}); "
          f"built in {time.perf_counter() - t0:.1f} s")
    print(f"reduced: {REDUCED}")

    cfg = get("canonicalizer-100m")
    engine = ServingEngine(cfg, cfg.build().init_params(
        cfg, jax.random.PRNGKey(args.seed)), build_tokenizer([wl]))
    backend = OlapExecutor(wl.dataset)
    svc = CacheService()
    svc.register_tenant(
        "ssb", schema=wl.schema, backend=backend,
        cache=SemanticCache(wl.schema, level_mapper=wl.dataset.level_mapper()),
        nl=CanonicalizerService(engine, wl.schema.name),
        policy=SafetyPolicy.balanced(wl.spatial_ambiguous))
    oracle = Oracle(wl.dataset)

    def sql(texts):
        return svc.submit_batch([QueryRequest(sql=t, tenant="ssb") for t in texts])

    seg_ops.reset_launch_count()
    tiles = dashboard(0)
    groups0 = backend.batch_groups
    t0 = time.perf_counter()
    first = sql(tiles)
    first_s = time.perf_counter() - t0
    if not all(r.status == "miss" and r.batched for r in first):
        # the pipeline re-runs the tiles of a failed shared scan one by one:
        # run the shared scan again outside it, so its own error surfaces
        backend.execute_batch([r.signature for r in first])
        fail(f"dashboard: {[(r.status, r.provenance) for r in first]} "
             "(want 12 batched misses)")
    check(backend.batch_groups - groups0 == 1,
          f"dashboard took {backend.batch_groups - groups0} shared scans, not 1")
    oracle.check(first, "dashboard")
    t0 = time.perf_counter()
    warm = sql(dashboard(1))
    warm_s = time.perf_counter() - t0
    check(all(r.status == "miss" for r in warm), "warm dashboard: not all misses")
    oracle.check(warm, "warm dashboard")
    print(f"smoke timing (one run, not a metric): 12-tile dashboard first call "
          f"{first_s:.2f} s incl. compile, warm {warm_s:.2f} s")

    for text in SINGLES + [HOST_MASK]:
        res = sql([text])
        check(res[0].status == "miss", f"single tile: {res[0].status}")
        oracle.check(res, "single tile")
    replay = sql(tiles)
    check(all(r.status == "hit_exact" for r in replay),
          f"replay: {[r.status for r in replay]}")
    oracle.check(replay, "replay")
    rollup = sql([TILE.replace("d_year, c_region,", "c_region,") + tiles[0].split(
        JOINS, 1)[1].replace("GROUP BY d_year, c_region", "GROUP BY c_region")])
    check(rollup[0].status == "hit_rollup", f"roll-up: {rollup[0].status}")
    oracle.check(rollup, "roll-up")
    # a tenant on the per-measure executor (fused=False): the plain seg_agg
    # entry point, one SUM/MIN/MAX kernel call per measure
    svc.register_tenant("ssb-per-measure", schema=wl.schema,
                        backend=OlapExecutor(wl.dataset, fused=False),
                        cache=SemanticCache(wl.schema))
    res = svc.submit_batch([QueryRequest(sql=SINGLES[0], tenant="ssb-per-measure")])
    check(res[0].status == "miss", f"per-measure tenant: {res[0].status}")
    oracle.check(res, "per-measure tenant")
    launches = {e: seg_ops.launch_count(e) for e in (
        "seg_agg", "seg_agg_batch_blocks", "seg_agg_fused", "seg_agg_masked")}
    print(f"seg_agg launches by entry point: {launches}")
    check(all(launches.values()), "an entry point of the served path never ran")
    print(f"SQL: {len(oracle.memo)} distinct tables equal to the numpy oracle; "
          "0 error, 0 degraded, 0 false hits")

    steps0 = engine.steps
    nl = svc.submit_batch([QueryRequest(nl=t, tenant="ssb") for t in NL])
    check(all(r.status != "error" for r in nl),
          f"NL: {[(r.status, r.error) for r in nl]}")
    check(engine.steps > steps0, "NL: the model took no decode step")
    print(f"NL: {[r.status for r in nl]} after {engine.steps - steps0} decode "
          f"steps of {cfg.name} (random weights)")

    attention_checks(jax, cfg, args.seed, flash_attention_pallas, mha_ref,
                     decode_attention_pallas, decode_attention_ref)
    peak = devs[0].memory_stats()["peak_bytes_in_use"]
    print(f"peak_bytes_in_use: {peak:,}")


def attention_checks(jax, cfg, seed, flash, flash_ref, decode, decode_ref):
    """Both attention kernels against their references at the model's
    shapes (prefill B=8, S=256; decode B=8 over a 512-slot cache)."""
    import jax.numpy as jnp

    b, s, cache = 8, 256, 512
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)

    def rnd(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(cfg.dtype)

    q = rnd(ks[0], (b, cfg.n_heads, s, cfg.head_dim))
    k = rnd(ks[1], (b, cfg.kv_heads, s, cfg.head_dim))
    v = rnd(ks[2], (b, cfg.kv_heads, s, cfg.head_dim))
    qd = rnd(ks[3], (b, cfg.n_heads, cfg.head_dim))
    kc = rnd(ks[4], (b, cfg.kv_heads, cache, cfg.head_dim))
    vc = rnd(ks[5], (b, cfg.kv_heads, cache, cfg.head_dim))
    pos = jax.random.randint(ks[6], (b,), 1, cache + 1)
    with jax.default_matmul_precision("highest"):
        ref_f = flash_ref(q, k, v, causal=True)
        ref_d = decode_ref(qd, kc, vc, pos)
    for name, out, ref in (("flash_attention_pallas", flash(q, k, v, causal=True), ref_f),
                           ("decode_attention_pallas", decode(qd, kc, vc, pos), ref_d)):
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
        print(f"{name}: max |kernel - reference| = {err:.3e} at {tuple(out.shape)} "
              f"{out.dtype}")
        check(err < 2e-2, f"{name} differs from its reference by {err}")


def run_four(args, jax, devs) -> None:
    from repro.olap.executor import OlapExecutor
    from repro.service import CacheService, QueryRequest
    from repro.core import SemanticCache
    from repro.workloads import ssb

    check(len(devs) == 4, f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    t0 = time.perf_counter()
    wl = ssb.build(n_fact=args.rows, seed=args.seed)
    print(f"rows: {wl.dataset.fact.num_rows:,} lineorder (seed {args.seed}) over "
          f"4 partitions; built in {time.perf_counter() - t0:.1f} s")
    print(f"reduced: {REDUCED}")
    backend = OlapExecutor(wl.dataset, partitions=4)
    svc = CacheService()
    svc.register_tenant("ssb", schema=wl.schema, backend=backend,
                        cache=SemanticCache(wl.schema))
    oracle = Oracle(wl.dataset)
    texts = dashboard(0)[:3] + SINGLES[:1]
    t0 = time.perf_counter()
    res = svc.submit_batch([QueryRequest(sql=t, tenant="ssb") for t in texts])
    print(f"smoke timing (one run, not a metric): {len(texts)} misses "
          f"{time.perf_counter() - t0:.2f} s incl. compile")
    check(all(r.status == "miss" for r in res), f"{[r.status for r in res]}")
    oracle.check(res, "partitioned scan")
    plan = backend._scan_plan()
    for p, chunks in enumerate(plan.chunks):
        sub = backend._subs[chunks[0]]
        mirror = sub.ds._device
        arrays = list(mirror._store.values()) + list(mirror._dim_store.values())
        placed = {d for a in arrays for d in a.devices()}
        check(placed == {devs[p]},
              f"partition {p} rows {chunks[0]}: arrays on {placed}, want {devs[p]}")
        print(f"partition {p}: rows {chunks[0][0]:,}-{chunks[0][1]:,}, "
              f"{len(arrays)} arrays on {devs[p]}")
    print(f"{len(oracle.memo)} tables equal to the numpy oracle")
    for d in devs:
        print(f"peak_bytes_in_use {d}: {d.memory_stats()['peak_bytes_in_use']:,}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="lineorder rows (default 6,000,000; 60,000,000 with --chips 4)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if args.rows is None:
        args.rows = 60_000_000 if args.chips == 4 else 6_000_000
    jax, devs = device_checks()
    (run_four if args.chips == 4 else run_one)(args, jax, devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
