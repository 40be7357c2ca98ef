"""Puts ``bench/`` and the program's ``src/`` on the import path for the
benchmark's own tests, and gives the small sizes they run at."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {
    "ssb": {"rows": {"lineorder": 50_000, "customer": 3_000, "supplier": 1_000,
                     "part": 2_000, "dates": 2_556}},
    "tpcds": {"rows": {"store_sales": 50_000, "item": 2_000, "store": 102,
                       "promotion": 500, "date_dim": 73_049}},
}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = tuple(w["name"] for w in json.load(f)["workloads"])
# every traffic mix with the configuration it is written for, the mixes kept
# for later cells among them
MIXES = (("ssb-sf10", "ssb_drill"), ("tpcds-sf10", "tpcds_refresh"),
         ("ssb-sf10", "ssb_adhoc"))


def small_config(name):
    """A configuration with its table sizes cut to ``SMALL``."""
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    return {**cfg, **SMALL[cfg["schema"]]}
