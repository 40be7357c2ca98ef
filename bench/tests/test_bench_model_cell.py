"""A model cell is added as new files and entries alone: in a copy of the
benchmark, a configuration with a ``model`` object, a traffic mix with
``nl_share``, a limits file and a per-layer reader of the model's counters
are added, with entries in ``BENCHMARK.json``; no file that was there
changes, and a traced run of the new cell is correct and reads the new
metric."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import benchpath  # noqa: F401
import nlcell

from benchpath import BENCH, ROOT

READER = '''"""Tokens the model generated per question in the window."""


def read(ctx):
    if ctx.model is None or not ctx.model.counters["prompts"]:
        return None
    return ctx.model.counters["generated_tokens"] / ctx.model.counters["prompts"]
'''


def _digests(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, top)] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_model_cell_added_as_new_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out", ".cache"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    before = _digests(tmp_path / "bench")
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    old = json.loads(json.dumps(bench))

    cell = nlcell.cell()
    new = {
        "configs/ssb-small-nl.json": {**cell.config, "name": "ssb-small-nl"},
        "traffic/ssb_adhoc_nl.json": cell.mix,
        "limits/ssb-small-nl.adhoc.json": cell.limits,
    }
    for rel, obj in new.items():
        (tmp_path / "bench" / rel).write_text(json.dumps(obj))
    (tmp_path / "bench" / "metrics" / "tokens_per_question.py").write_text(READER)
    bench["configs"].append({
        "name": "ssb-small-nl", "source": "https://www.cs.umb.edu/~poneil/StarSchemaB.PDF",
        "file": "bench/configs/ssb-small-nl.json", "reduced": ["rows", "n_layers"],
        "why": "SSB at 50,000 rows with a two-layer canonicalizer"})
    bench["workloads"].append({
        "name": "ssb-small-nl.adhoc", "config": "ssb-small-nl", "traffic": "ssb_adhoc_nl",
        "chips": 1, "why": "ad-hoc questions, one to a submit, through the model"})
    bench["per_layer"].append({
        "name": "tokens_per_question.nl", "unit": "tokens", "better": "lower",
        "source": "program_counter", "layer": "model canonicalizer (serving/engine.py)",
        "moves": "setup_s", "workloads": ["ssb-small-nl.adhoc"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # entries only: what was there is there unchanged
    after = _digests(tmp_path / "bench")
    assert {k: after[k] for k in before} == before
    for key, entries in old.items():
        assert bench[key][:len(entries)] == entries if isinstance(entries, list) \
            else bench[key] == entries

    code = ("import json, sys, time; sys.path.insert(0, 'bench'); from lib import harness; "
            "print(json.dumps(harness.run('ssb-small-nl.adhoc', 107, 1.0, True, "
            "time.perf_counter(), require_tpu=False)))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["nl_refused"] + out["nl_answered"] == out["attempted"] > 0
    assert out["metrics"]["tokens_per_question.nl"]["value"] > 0
