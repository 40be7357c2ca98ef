"""A cell of questions for the benchmark's own tests: the dense
``canonicalizer-100m`` cut to two layers of width 64 (its family's
``reduced`` sizes with a head of 512 ids, wider than the tokenizer's 280),
over the 50,000-row SSB star, answering the ad-hoc mix sent all as
questions, one to a submit.  The limit of ``max_logit_gap`` was set from
twelve seeds on the CPU: the program read 0 to 0.037, the float8 control
0.114 to 0.511."""
import time

import benchpath  # noqa: F401
from benchpath import small_config

MODEL = {"arch": "canonicalizer-100m",
         "overrides": {"n_layers": 2, "d_model": 64, "d_ff": 128, "vocab": 512,
                       "n_heads": 4, "kv_heads": 2, "head_dim": 16},
         "max_len": 512, "max_new_tokens": 24, "header_words": 6, "reference": "dense"}
LIMITS = {"max_err": 5e-05, "sample_intents": 16, "sample_prompts": 8,
          "max_logit_gap": 0.07}
MIX = {"rate_per_s": 20.0, "nl_share": 1.0}


def cell():
    from lib import harness, traffic

    return harness.Cell(
        "ssb-small.nl", {"name": "ssb-small.nl", "config": "ssb-small", "traffic": "ssb_adhoc",
                         "chips": 1},
        {**small_config("ssb-sf10"), "model": MODEL}, {**traffic.load("ssb_adhoc"), **MIX},
        LIMITS, [{"name": "p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}], [])


def run(seed=107, control=None):
    """One run of the cell with a second's window on the CPU."""
    from lib import harness

    return harness.run("ssb-small.nl", seed, 1.0, False, time.perf_counter(),
                       require_tpu=False, control=control, cell=cell())
