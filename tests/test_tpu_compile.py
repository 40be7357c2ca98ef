"""Compile-only checks of the main-path kernels for a described TPU v5e.

Nothing runs: each case lowers a kernel at the size a deployment uses (SSB
SF1, 6,000,000 fact rows; the canonicalizer-100m model's widths) and compiles
it with the TPU compiler for a chip that is described, not attached.  That
catches what interpret mode cannot: Mosaic layout rules, block-shape rules
and device-memory blow-ups.  Each case asserts that a Pallas kernel is in the
program and bounds its temp bytes below what the previous (N, M) layout
needed (9.2 GB for one 3-measure SUM at 6M rows).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = 6_000_000  # SSB SF1 lineorder rows
M, G, P, K, S = 3, 300, 3, 2, 12
GB = 1 << 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, shapes, sharding, kernel=None):
    """Compile ``fn`` for the described chip; return its temp bytes.  With
    ``kernel``, the Pallas call must carry that name in the compiled
    program, where a profiler trace reads it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if kernel is not None:
        assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text)
    return compiled.memory_analysis().temp_size_in_bytes


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("op", ["sum", "min"])
def test_seg_agg_pallas_compiles(one_chip, op):
    from repro.kernels.seg_agg.kernel import seg_agg_pallas

    temp = _compile(lambda v, i, m: seg_agg_pallas(v, i, m, G, op),
                    [((N, M), F32), ((N,), I32), ((N,), F32)], one_chip,
                    kernel=f"seg_agg_{op}")
    assert temp < GB // 2


@pytest.mark.parametrize("op", ["sum", "min"])
def test_seg_agg_fused_pallas_compiles(one_chip, op):
    from repro.kernels.seg_agg.kernel import seg_agg_fused_pallas

    temp = _compile(lambda v, i, p, b: seg_agg_fused_pallas(v, i, p, b, G, op),
                    [((N, M), F32), ((N,), I32), ((N, P), F32), ((P, 2 * K), F32)],
                    one_chip, kernel=f"seg_agg_fused_{op}")
    assert temp < GB // 2


@pytest.mark.parametrize("op", ["sum", "min"])
def test_seg_agg_batch_blocks_pallas_compiles(one_chip, op):
    """The shared-scan path of ``seg_agg_batch_blocks`` for a 12-tile
    dashboard: it still materialises the (S·M, N) masked block (ROADMAP
    S4), but lane-dense — the (N, S·M) layout needed 9.2 GB."""
    from repro.kernels.seg_agg.ops import _batch_jit

    temp = _compile(lambda v, i, p, b: _batch_jit(v, i, p, b, G, op, "pallas"),
                    [((N, 1 + M), F32), ((N,), I32), ((N, P), F32),
                     ((S, P, K, 2), F32)], one_chip, kernel=f"seg_agg_{op}")
    assert temp < 3 * GB


def test_refresh_batch_compacted_groups_compiles(one_chip):
    """The TPC-DS SF10 refresh dashboard's shared scan (28,800,991 rows, 12
    tiles, d_year x s_state, two columns per block) at its 60 observed
    groups, one 128-group tile: it compiles, in no more temp than the 2,010
    dense groups of the full date_dim need."""
    from repro.kernels.seg_agg.ops import _batch_jit

    n, s, m, p, k = 28_800_991, 12, 2, 3, 1
    shapes = [((n, m), F32), ((n,), I32), ((n, p), F32), ((s, p, k, 2), F32)]
    temp = {g: _compile(lambda v, i, pc, b, g=g: _batch_jit(v, i, pc, b, g, "min",
                                                             "pallas"),
                        shapes, one_chip, kernel="seg_agg_min")
            for g in (60, 2010)}
    assert temp[60] <= temp[2010]


def test_flash_attention_compiles(one_chip):
    """canonicalizer-100m prefill: B=8, S=256, 12 query / 4 KV heads of 64."""
    from repro.configs.registry import get
    from repro.kernels.flash_attn.kernel import flash_attention_pallas

    cfg = get("canonicalizer-100m")
    b, s = 8, 256
    q = ((b, cfg.n_heads, s, cfg.head_dim), cfg.dtype)
    kv = ((b, cfg.kv_heads, s, cfg.head_dim), cfg.dtype)
    temp = _compile(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
                    [q, kv, kv], one_chip)
    assert temp < 64 << 20


def test_decode_attention_compiles(one_chip):
    """canonicalizer-100m decode over a 512-slot cache; ``pos`` rides in
    SMEM (a (1, 1) VMEM block of it broke the TPU block rule)."""
    from repro.configs.registry import get
    from repro.kernels.decode_attn.kernel import decode_attention_pallas

    cfg = get("canonicalizer-100m")
    b, cache = 8, 512
    kv = ((b, cfg.kv_heads, cache, cfg.head_dim), cfg.dtype)
    temp = _compile(lambda q, k, v, p: decode_attention_pallas(q, k, v, p),
                    [((b, cfg.n_heads, cfg.head_dim), cfg.dtype), kv, kv,
                     ((b,), I32)], one_chip)
    assert temp < 64 << 20
