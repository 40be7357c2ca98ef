"""Whole runs of each cell at 50,000 rows on the CPU, past the harness's
look for a chip: a sound run is correct; the bfloat16 control, an answer
altered where the executor produces it, and a scan that leaves out half of
the rows and doubles the rest each make ``correct`` false, and so does a
closed loop that uses up its pool of dashboards."""
import json
import os
import shutil
import subprocess
import sys
import time

import benchpath  # noqa: F401
import numpy as np
import pytest

from benchpath import BENCH, CELLS, ROOT, SMALL

def _run(cell, seed=7, control=None):
    from lib import harness

    c = harness.load_cell(cell)
    # a second's window at a rate that sends some twenty requests
    mix = {"rate_per_s": 20.0} if c.mix["loop"] == "open" else None
    return harness.run(cell, seed, 1.0, False, time.perf_counter(), require_tpu=False,
                       control=control, config_override=SMALL[c.config["schema"]],
                       mix_override=mix)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_fails(cell):
    from lib import harness

    out = _run(cell, control="bfloat16")
    assert not out["correct"], out["checks"]
    limit = harness.load_cell(cell).limits["max_err"]
    assert out["checks"]["max_err"]["value"] > limit


@pytest.mark.parametrize("cell", [c for c in CELLS if c.endswith(".refresh")])
def test_used_up_dashboard_pool_fails(cell):
    """A window that would need a dashboard twice is not correct: the
    clients stop rather than replay tiles that the cache now holds."""
    from lib import harness

    c = harness.load_cell(cell)
    out = harness.run(cell, 7, 1.0, False, time.perf_counter(), require_tpu=False,
                      config_override=SMALL[c.config["schema"]],
                      mix_override={"pool_dashboards": 1})
    assert out["checks"]["pool_short"]["value"] > 0
    assert not out["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_fails(cell, monkeypatch):
    from repro.olap.executor import OlapExecutor

    build = OlapExecutor._build_result

    def altered(self, *a, **kw):
        t = build(self, *a, **kw)
        if t.num_rows and "m0" in t.columns:
            t.columns["m0"] = t.columns["m0"] * np.where(
                np.arange(t.num_rows) == 0, 1.001, 1.0)
        return t

    monkeypatch.setattr(OlapExecutor, "_build_result", altered)
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_rows_left_out_fails(cell, monkeypatch):
    import jax.numpy as jnp

    from repro.olap import executor

    def half(values, op):
        keep = (jnp.arange(values.shape[0]) % 2 == 0)[:, None]
        if op == "sum":
            return jnp.where(keep, values * 2.0, 0.0)
        return jnp.where(keep, values, jnp.inf)

    fused, blocks = executor.seg_agg_fused, executor.seg_agg_batch_blocks

    def fused_half(values, ids, pred, bounds, g, op="sum", **kw):
        return fused(half(jnp.asarray(values), op), ids, pred, bounds, g, op, **kw)

    def blocks_half(sums, mm, ids, pred, bounds, g, **kw):
        return blocks(half(jnp.asarray(sums), "sum"),
                      None if mm is None else half(jnp.asarray(mm), "min"),
                      ids, pred, bounds, g, **kw)

    monkeypatch.setattr(executor, "seg_agg_fused", fused_half)
    monkeypatch.setattr(executor, "seg_agg_batch_blocks", blocks_half)
    assert not _run(cell)["correct"]


def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _command(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out", ".cache"))
    p = _command(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert json.load(open(tmp_path / "BENCHMARK.json"))["paths"] == ["bench"]
