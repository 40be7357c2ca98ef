"""The trace reducer on a trace recorded here on the CPU, and the peak
table's refusal of a device it does not know."""
import benchpath  # noqa: F401
import pytest


def test_reduce_cpu_trace(tmp_path):
    import time

    import jax
    import jax.numpy as jnp

    from lib import trace as tr

    @jax.jit
    def work(x):
        return (x @ x).sum()

    x = jnp.ones((256, 256))
    work(x).block_until_ready()
    logdir = str(tmp_path / "trace")
    with tr.record(logdir):
        with tr.span("window"):
            for _ in range(3):
                with tr.span("submit_batch"):
                    work(x).block_until_ready()
                with tr.span("wait_arrival"):
                    time.sleep(0.02)
    red = tr.reduce(tr.read(logdir, "cpu"))
    assert 0.06 <= red["window_s"] < 5.0
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["op_s"] and all(v > 0 for v in red["op_s"].values())
    # the sleeps are idle time, and they are put down to the wait spans
    assert red["gaps"].get("wait_arrival", 0.0) >= 0.05
    assert sum(red["gaps"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6, abs=1e-6)
    top = tr.top(red["gaps"], 10)
    assert len(top) <= 10 and top[0][1] >= top[-1][1]


def test_union_and_gaps_by_hand():
    from lib import trace as tr

    ev = tr.Events(
        ops=[[("a", 100, 50), ("b", 120, 60), ("c", 300, 100)]],
        modules=[[("jit_scan", 90, 200), ("jit_other", 295, 110)]],
        spans=[("bench.window", 0, 1000), ("bench.submit_batch", 80, 400),
               ("bench.wait_arrival", 500, 400)])
    red = tr.reduce(ev)
    assert red["busy_s"] == pytest.approx(180e-9)  # [100, 180) and [300, 400)
    # [400, 1000) is one gap, put down to the span around its middle
    assert red["gaps"]["wait_arrival"] == pytest.approx(600e-9)
    assert red["gaps"]["submit_batch"] == pytest.approx(120e-9)  # [180, 300)
    assert red["gaps"]["other"] == pytest.approx(100e-9)  # [0, 100)
    assert red["op_s"]["jit_scan:a"] == pytest.approx(50e-9)
    assert red["op_s"]["jit_other:c"] == pytest.approx(100e-9)
    assert tr.program_seconds(red, ["jit_scan"]) == pytest.approx(200e-9)


def test_unknown_device_is_an_error():
    from lib import harness

    assert harness.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoChip):
        harness.peak_for("TPU v99")
