"""The plain reference against the program's independent numpy executor,
for every intent that the three traffic mixes render, at 50,000 rows."""
import benchpath  # noqa: F401
import pytest

from benchpath import MIXES, small_config


@pytest.mark.parametrize("config,mix", MIXES)
def test_reference_equals_numpy_executor(config, mix):
    import importlib

    from lib import traffic
    from lib.data import generate, to_dataset
    from lib.reference import Reference, compare, match_measures
    from repro.core.sql_canon import SQLCanonicalizer
    from repro.olap.executor import OlapExecutor

    cfg = small_config(config)
    data = generate(cfg, 5)
    schema = importlib.import_module(f"repro.workloads.{cfg['schema']}").build_schema()
    oracle = OlapExecutor(to_dataset(data, schema), impl="numpy")
    canon = SQLCanonicalizer(schema)
    ref = Reference(data)
    sched = traffic.schedule(mix, data, 5, 20.0)
    reqs = [r for w in sched.warmup for r in w] + sched.requests + \
        [r for d in sched.dashboards[:3] for r in d]
    seen = {}
    for r in reqs:
        seen.setdefault(traffic.intent_key(r.intent), r)
    assert len(seen) >= 10
    for r in seen.values():
        sig = canon.canonicalize(r.sql)
        table = oracle.execute(sig)
        mapping = match_measures(r.intent["measures"],
                                 [(m.agg, m.expr) for m in sig.measures])
        assert mapping is not None, r.sql
        ok, err = compare(ref.table(r.intent), table.columns, mapping)
        assert ok, r.sql
        # the executor rounds measure inputs to float32 before it sums
        assert err < 1e-6, (r.sql, err)
