"""Questions in the traffic: the renderer, the share of a mix sent as
questions, the grammar the logits check reads tokens against, and the
intent of a served signature."""
import hashlib
import json

import benchpath  # noqa: F401
import numpy as np
import pytest

from benchpath import MIXES, small_config

# sha256 of every request (rid, intent, SQL, kind, due, group, question) of
# each mix at seed 2**31 + 7 over 30 s, as the generator made them before
# questions existed
BEFORE = {
    "ssb_drill": "106ff3c2641c28aefe858c1e472de426dca66ff582cfe8972656075288ad5940",
    "tpcds_refresh": "84f56b22482995b06cf235324b4d6d3755833909682c1845a2eb3e7c11692d53",
    "ssb_adhoc": "7fe7a1b73c4351dc589afa0755965ce21dab5c48bf862c5f2cb75c2855d3734a",
}


def _schedule(config, mix, seed, extra=None):
    from lib import traffic
    from lib.data import generate

    data = generate(small_config(config), 1)
    return traffic.schedule(mix, data, seed, 30.0, mix={**traffic.load(mix), **(extra or {})})


def _all(s):
    return [r for w in s.warmup for r in w] + s.requests + [r for d in s.dashboards for r in d]


@pytest.mark.parametrize("config,mix", MIXES)
def test_mix_without_nl_share_schedules_as_before(config, mix):
    h = hashlib.sha256()
    for r in _all(_schedule(config, mix, 2**31 + 7)):
        h.update(json.dumps([r.rid, r.intent, r.sql, r.kind, r.due, r.group, r.nl],
                            sort_keys=True).encode())
    assert h.hexdigest() == BEFORE[mix]


@pytest.mark.parametrize("config,mix", MIXES)
def test_nl_share_asks_the_same_requests_for_every_seed(config, mix):
    from lib import nl

    a = _all(_schedule(config, mix, 3, {"nl_share": 0.25}))
    b = _all(_schedule(config, mix, 4, {"nl_share": 0.25}))
    plain = _all(_schedule(config, mix, 3))
    asked = [r.nl is not None for r in a]
    assert asked == [r.nl is not None for r in b]
    assert 0.15 < np.mean(asked) < 0.35
    # the same requests, the questions rendered from their intents
    assert [r.sql for r in a] == [r.sql for r in plain]
    assert all(r.nl == nl.question(r.intent) for r in a if r.nl is not None)
    everything = _all(_schedule(config, mix, 3, {"nl_share": 1.0}))
    assert all(r.nl is not None for r in everything)


def test_question_of_a_tile():
    from lib import nl

    tile = {"levels": ["date_dim.d_year", "store.s_state"],
            "measures": [["SUM", "store_sales.ss_ext_sales_price"], ["COUNT", "*"],
                         ["MIN", "store_sales.ss_net_profit"],
                         ["SUM", ["*", "lineorder.lo_extendedprice", "lineorder.lo_discount"]]],
            "filters": [["item.i_category", "=", "Books"],
                        ["store_sales.ss_quantity", "between", [3, 17]],
                        ["customer.c_region", "in", ["ASIA", "EUROPE"]]]}
    assert nl.question(tile) == (
        "total ext sales price, number of rows, minimum net profit and total "
        "extendedprice times discount by year and state where category is Books, "
        "quantity between 3 and 17 and region in ASIA or EUROPE")


def test_grammar_agrees_with_the_program():
    from lib import grammar
    from repro.serving.json_decode import JsonSigAutomaton

    vocab = ["", "{", "}", "[", "]", '"', ":", ",", " ", "a", "Z", "é", "'", "!", '"sum"',
             "}}", "-1.5", "x" * 300, "MFGR#12", "ASIA EUROPE", "\n"]
    automaton = JsonSigAutomaton()
    rng = np.random.default_rng(0)
    for _ in range(300):
        text = ("{" if rng.random() < 0.8 else "") + "".join(
            vocab[i] for i in rng.integers(0, len(vocab), rng.integers(0, 8)))
        want = automaton.token_mask(text, vocab) if automaton.is_legal_prefix(text) \
            else np.zeros(len(vocab), bool)
        assert (grammar.legal(text, vocab) == want).all(), text


SQLS = [
    "SELECT d_year, SUM(lo_revenue) FROM lineorder JOIN dates ON lineorder.lo_orderdate = "
    "dates.d_key WHERE lo_discount BETWEEN 1 AND 3 GROUP BY d_year",
    "SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder JOIN dates ON "
    "lineorder.lo_orderdate = dates.d_key WHERE d_year = 1993 AND lo_quantity < 25",
    "SELECT c_region, AVG(lo_quantity), COUNT(*) FROM lineorder JOIN customer ON "
    "lineorder.lo_custkey = customer.c_key WHERE c_region IN ('ASIA', 'EUROPE') "
    "GROUP BY c_region",
]


@pytest.mark.parametrize("sql", SQLS)
def test_intent_of_a_served_signature(sql):
    """The reference of the signature the program's SQL canonicalizer
    serves equals the program's own answer to it."""
    from lib.data import generate, to_dataset
    from lib.reference import Reference, compare, intent_of_signature, match_measures
    from repro.core.sql_canon import SQLCanonicalizer
    from repro.olap.executor import OlapExecutor
    from repro.workloads.ssb import build_schema

    data = generate(small_config("ssb-sf10"), 5)
    schema = build_schema()
    sig = SQLCanonicalizer(schema).canonicalize(sql)
    intent = intent_of_signature(sig.to_json(), f"lineorder.{schema.fact.date_column}")
    assert intent is not None
    got = OlapExecutor(to_dataset(data, schema), impl="numpy").execute(sig)
    mapping = match_measures(intent["measures"], [(m.agg, m.expr) for m in sig.measures])
    ok, err = compare(Reference(data).table(intent), got.columns, mapping)
    assert ok and err < 1e-9


def test_parse_expr():
    from lib.reference import intent_of_signature, parse_expr

    assert parse_expr("*") == "*"
    assert parse_expr("(a.x*(b.y-c.z))") == ["*", "a.x", ["-", "b.y", "c.z"]]
    assert parse_expr("a.x*b.y+c.z") == ["+", ["*", "a.x", "b.y"], "c.z"]
    assert parse_expr("a.x*2") is None and parse_expr("(a.x") is None
    sig = {"measures": [{"agg": "SUM", "expr": "f.v"}], "levels": [], "filters": [],
           "time_window": {"start": "1994-01-01", "end": "1995-01-01"}}
    assert intent_of_signature(sig, "f.d")["filters"] == [
        ["f.d", ">=", "1994-01-01"], ["f.d", "<", "1995-01-01"]]
    assert intent_of_signature({**sig, "limit": 5}, "f.d") is None
