"""Serving launcher: the full paper pipeline with the in-framework LLM.

Boots a workload (schema + data + OLAP backend), a canonicalizer LLM served
by our engine (optionally restored from a training checkpoint), and the
semantic cache middleware — then replays a query stream and reports cache
statistics.  ``--simulated-llm`` swaps in the calibrated SimulatedLLM
(no model inference), which is what the paper-table benchmarks use.  Exits
non-zero when any request ends with status ``error``.

Usage:
    python -m repro.launch.serve --workload ssb --queries 100 --simulated-llm
    python -m repro.launch.serve --workload ssb --ckpt-dir ckpts/canon
"""
from __future__ import annotations

import argparse
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ssb", choices=["ssb", "nyc_tlc", "tpcds"])
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--n-fact", type=int, default=50_000)
    ap.add_argument("--order", default="sequential")
    ap.add_argument("--simulated-llm", action="store_true")
    ap.add_argument("--model", default="gpt-4o-mini")
    ap.add_argument("--arch", default="canonicalizer-100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--capacity", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8,
                    help="requests per submit_batch (dashboard refresh size)")
    args = ap.parse_args()

    import jax

    from .compile_cache import enable_compilation_cache

    enable_compilation_cache()
    from ..core import MemoizedNL, SafetyPolicy, SemanticCache, SimulatedLLM
    from ..olap.executor import OlapExecutor
    from ..service import CacheService, QueryRequest
    from ..workloads import nyc_tlc, ssb, tpcds

    wl = {"ssb": ssb, "nyc_tlc": nyc_tlc, "tpcds": tpcds}[args.workload].build(
        n_fact=args.n_fact)

    if args.simulated_llm:
        nl = MemoizedNL(SimulatedLLM(wl.vocab, model=args.model))
    else:
        from ..configs.registry import get, reduced
        from ..serving.engine import CanonicalizerService, ServingEngine
        from ..training.checkpoint import restore_latest
        from ..training.tokenizer import build_tokenizer

        cfg = reduced(args.arch) if args.reduced else get(args.arch)
        tok = build_tokenizer([wl])
        mod = cfg.build()
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        if args.ckpt_dir:
            restored, step, _ = restore_latest(args.ckpt_dir, {"p": params})
            if restored is not None:
                params = restored["p"]
                print(f"[serve] restored canonicalizer from step {step}")
        engine = ServingEngine(cfg, params, tok)
        nl = MemoizedNL(CanonicalizerService(engine, wl.schema.name))

    backend = OlapExecutor(wl.dataset)
    cache = SemanticCache(wl.schema, capacity=args.capacity,
                          level_mapper=wl.dataset.level_mapper())
    svc = CacheService()
    tenant = svc.register_tenant(
        args.workload, schema=wl.schema, backend=backend, cache=cache, nl=nl,
        policy=SafetyPolicy.balanced(wl.spatial_ambiguous))

    stream = wl.queries(order=args.order)[: args.queries]
    # submit in refresh-sized batches: misses within a batch share one
    # backend scan and identical in-flight intents are deduped
    reqs = [QueryRequest(sql=q.text, tenant=args.workload) if q.kind == "sql"
            else QueryRequest(nl=q.text, tenant=args.workload) for q in stream]
    results = []
    for i in range(0, len(reqs), args.batch):
        results += svc.submit_batch(reqs[i:i + args.batch])
    s = cache.stats
    n = len(stream)
    print(f"[serve] {n} queries (batch={args.batch}) | hit rate {s.hit_rate:.3f} "
          f"(exact {s.hits_exact}, rollup {s.hits_rollup}, "
          f"filterdown {s.hits_filterdown}) | misses {s.misses} "
          f"| bypasses {tenant.stats.bypasses} "
          f"| batched misses {tenant.stats.batched_misses} "
          f"| deduped {tenant.stats.deduped_misses} "
          f"| backend execs {backend.executions} "
          f"| rows scanned {backend.rows_scanned:,}")
    errors = [r for r in results if r.status == "error"]
    for r in errors[:5]:
        print(f"[serve] error: {r.error}", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
