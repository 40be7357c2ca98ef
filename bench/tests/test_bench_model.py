"""The model canonicalizer on the benchmark's normal path, on the CPU: the
plain reference of the dense family equals the program's forward; a sound
run of questions is correct and counts what the gate declines as refused,
not as unserved; the float8 control is not correct."""
import dataclasses

import benchpath  # noqa: F401
import nlcell
import numpy as np
import pytest


@pytest.mark.parametrize("variant", [
    {},
    {"activation": "geglu", "qk_norm": False, "rope_fraction": 0.5, "tie_embeddings": True},
    {"activation": "squared_relu", "kv_heads": 4},
])
def test_reference_equals_the_program_forward(variant):
    import jax
    import jax.numpy as jnp
    from lib.harness import load_module
    from repro.configs.registry import reduced
    from repro.models import transformer

    cfg = dataclasses.replace(reduced("canonicalizer-100m"), vocab=512,
                              dtype=jnp.float32, **variant)
    plain = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
             if f.name != "dtype"}
    plain["dtype"] = "float32"
    dense = load_module("models", "dense")
    params = dense.init(plain, jax.random.PRNGKey(3))
    ids = np.random.default_rng(0).integers(0, 512, 37)
    with jax.default_matmul_precision("highest"):
        program = transformer.forward(cfg, params, tokens=jnp.asarray(ids)[None])[0]
    ref = dense.logits(plain, params, ids)
    assert ref.shape == (37, 512) and ref.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(program), np.asarray(ref), atol=1e-4, rtol=1e-4)
    # the reference reads every weight: a norm weight changed moves it
    moved = dict(params, final_norm=params["final_norm"] + 0.01)
    assert float(jnp.abs(dense.logits(plain, moved, ids) - ref).max()) > 1e-3


def test_sound_run_is_correct_and_declined_questions_are_refused():
    out = nlcell.run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["checks"]["unserved"]["value"] == 0
    # random weights write no signature that parses: the canonicalizer
    # declines every question, and a refusal is an answer given in time
    assert out["nl_refused"] + out["nl_answered"] == out["attempted"] > 0
    assert out["nl_refused"] > 0
    assert 0 <= out["checks"]["max_logit_gap"]["value"] <= nlcell.LIMITS["max_logit_gap"]
    assert list(out)[-1] == "checks"


def test_float8_control_fails():
    out = nlcell.run(control="float8")
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > nlcell.LIMITS["max_logit_gap"]
