"""Faults planted where the model canonicalizer produces its output make a
run of questions incorrect: one weight matrix of the served model changed,
a generated token altered, an answer to a question altered.  Questions that
the program answers are compared with the reference of the signature it
served."""
import dataclasses

import benchpath  # noqa: F401
import nlcell
import numpy as np
import pytest


def test_perturbed_weight_fails(monkeypatch):
    import jax
    from repro.serving import engine

    init = engine.ServingEngine.__init__

    def perturbed(self, cfg, params, *a, **kw):
        w = params["layers"]["mlp_w2"]
        noise = jax.random.normal(jax.random.PRNGKey(1), w.shape[1:]) * w[0].std()
        params = {**params, "layers": {**params["layers"],
                                       "mlp_w2": w.at[0].add(noise.astype(w.dtype))}}
        init(self, cfg, params, *a, **kw)

    monkeypatch.setattr(engine.ServingEngine, "__init__", perturbed)
    out = nlcell.run()
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > nlcell.LIMITS["max_logit_gap"]


def test_altered_token_fails(monkeypatch):
    from repro.serving import engine

    sample = engine.constrained_sample
    calls = [0]

    def altered(logits, prefix, vocab, automaton, *a, **kw):
        nid = sample(logits, prefix, vocab, automaton, *a, **kw)
        calls[0] += 1
        if nid >= 0 and calls[0] % 10 == 0:  # the worst token the grammar allows
            allowed = automaton.token_mask(prefix, vocab)
            nid = int(np.argmin(np.where(allowed, logits, np.inf)))
        return nid

    monkeypatch.setattr(engine, "constrained_sample", altered)
    out = nlcell.run()
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > nlcell.LIMITS["max_logit_gap"]


@pytest.mark.parametrize("alter", [False, True])
def test_answered_questions_are_compared(monkeypatch, capfd, alter):
    """The canonicalizer still runs the model, but its signature is the one
    the SQL of the same intent gives: every question is answered, the
    answers are compared with the reference of the served signature, and
    one altered where the executor produces it is wrong."""
    from lib import traffic
    from lib.data import generate
    from repro.core.sql_canon import SQLCanonicalizer
    from repro.olap.executor import OlapExecutor
    from repro.serving.engine import CanonicalizerService
    from repro.workloads.ssb import build_schema

    cell = nlcell.cell()
    data = generate(cell.config, 107)
    sched = traffic.schedule("ssb_adhoc", data, 107, 1.0, mix=cell.mix)
    sql = {r.nl: r.sql for reqs in sched.warmup + [sched.requests] for r in reqs}
    canon = SQLCanonicalizer(build_schema())
    batch = CanonicalizerService.canonicalize_batch

    def answered(self, texts, now=None):
        return [dataclasses.replace(r, signature=canon.canonicalize(sql[t]), confidence=0.99,
                                    error=None)
                for r, t in zip(batch(self, texts, now), texts)]

    monkeypatch.setattr(CanonicalizerService, "canonicalize_batch", answered)
    if alter:
        build = OlapExecutor._build_result

        def altered(self, *a, **kw):
            t = build(self, *a, **kw)
            if t.num_rows and "m0" in t.columns:
                t.columns["m0"] = t.columns["m0"] * np.where(
                    np.arange(t.num_rows) == 0, 1.001, 1.0)
            return t

        monkeypatch.setattr(OlapExecutor, "_build_result", altered)
    out = nlcell.run()
    assert out["nl_answered"] == out["attempted"] > 0
    assert "reference: 0 answers" not in capfd.readouterr().err
    assert out["correct"] is not alter, out["checks"]
