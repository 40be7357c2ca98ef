"""Per-kernel correctness sweeps: Pallas (interpret mode) vs pure-jnp oracle
across shapes and dtypes, as required for every kernel in kernels/."""
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(42)


# ------------------------------------------------------------------ seg_agg


@pytest.mark.parametrize("n,m,g", [(512, 1, 16), (1000, 3, 17), (4096, 2, 512),
                                   (777, 4, 1000), (64, 1, 5)])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_seg_agg(n, m, g, op):
    from repro.kernels.seg_agg.kernel import seg_agg_pallas
    from repro.kernels.seg_agg.ref import seg_agg_ref

    vals = rng.normal(size=(n, m)).astype(np.float32)
    ids = rng.integers(0, g, size=n).astype(np.int32)
    mask = (rng.random(n) > 0.3).astype(np.float32)
    ref = np.asarray(seg_agg_ref(vals, ids, mask, g, op))
    out = np.asarray(seg_agg_pallas(vals, ids, mask, g, op, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_seg_agg_dtypes():
    from repro.kernels.seg_agg.kernel import seg_agg_pallas
    from repro.kernels.seg_agg.ref import seg_agg_ref

    vals = rng.normal(size=(256, 2)).astype(np.float16).astype(np.float32)
    ids = rng.integers(0, 31, size=256).astype(np.int32)
    mask = np.ones(256, np.float32)
    for dt in (jnp.float32, jnp.bfloat16):
        v = jnp.asarray(vals, dt)
        ref = np.asarray(seg_agg_ref(v, ids, mask, 31, "sum"))
        out = np.asarray(seg_agg_pallas(v, ids, mask, 31, "sum", interpret=True))
        np.testing.assert_allclose(out, ref, rtol=1e-2, atol=1e-2)


def _identity_masked_ref(vals, ids, mask, g, op):
    """Masked-out rows hold the op identity (NaN-safe contract): only NaNs
    of selected rows reach a group."""
    from repro.kernels.seg_agg.ref import IDENTITY, seg_agg_ref

    v = np.where(mask[:, None] > 0.5, vals, np.float32(IDENTITY[op]))
    return np.asarray(seg_agg_ref(v, ids, np.ones(len(ids), np.float32), g, op))


@pytest.mark.parametrize("n,m,g,tn,tg", [
    (1000, 3, 17, 128, 128),     # N not a multiple of the row tile
    (2500, 2, 1000, 1024, 512),  # G > TG, partial last row tile
    (300, 1, 130, 128, 128),     # G just over one group tile
    (96, 4, 5, 1024, 512),       # N below one row tile
])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("with_mask", [True, False])
def test_seg_agg_lane_layout(n, m, g, tn, tg, op, with_mask):
    """Lane-major plain kernel: partial row tiles, several group tiles, NaNs
    in masked-in and masked-out rows, and the mask-free form."""
    from repro.kernels.seg_agg.kernel import seg_agg_pallas

    vals = rng.normal(size=(n, m)).astype(np.float32)
    vals[rng.random((n, m)) < 0.03] = np.nan
    ids = rng.integers(0, g, size=n).astype(np.int32)
    mask = (rng.random(n) > 0.3).astype(np.float32) if with_mask \
        else np.ones(n, np.float32)
    vals[:2, 0] = np.nan  # row 0 selected; row 1 masked out when masking
    mask[0], mask[1] = 1.0, 0.0 if with_mask else 1.0
    ref = _identity_masked_ref(vals, ids, mask, g, op)
    out = np.asarray(seg_agg_pallas(vals, ids, mask if with_mask else None, g, op,
                                    tn=tn, tg=tg, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------ seg_agg filter-fused


def _rand_bounds(p, k, lo=0, hi=10):
    """Random (P, K, 2) inclusive range bounds with some never-match pads."""
    b = np.empty((p, k, 2), np.float32)
    b[..., 0], b[..., 1] = np.inf, -np.inf
    for i in range(p):
        for j in range(rng.integers(1, k + 1)):
            a = rng.integers(lo, hi, size=2)
            b[i, j] = (min(a), max(a))
    return b


@pytest.mark.parametrize("n,m,g,p,k", [(512, 1, 16, 1, 1), (1000, 3, 17, 2, 2),
                                       (777, 2, 100, 3, 2), (64, 4, 5, 1, 4)])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_seg_agg_fused(n, m, g, p, k, op):
    """Filter-fused kernel (mask built in-tile from bounds) vs fused oracle,
    interpret mode, including NaN-bearing values."""
    from repro.kernels.seg_agg.kernel import seg_agg_fused_pallas
    from repro.kernels.seg_agg.ref import seg_agg_fused_ref

    vals = rng.normal(size=(n, m)).astype(np.float32)
    vals[rng.random((n, m)) < 0.02] = np.nan
    ids = rng.integers(0, g, size=n).astype(np.int32)
    pred = rng.integers(0, 10, size=(n, p)).astype(np.float32)
    bounds = _rand_bounds(p, k)
    ref = np.asarray(seg_agg_fused_ref(vals, ids, pred, bounds, g, op))
    flat = np.concatenate([bounds[:, :, 0], bounds[:, :, 1]], axis=1)
    out = np.asarray(seg_agg_fused_pallas(vals, ids, pred, flat, g, op, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,m,g,p,k,tn,tg", [
    (1000, 3, 17, 1, 3, 128, 128),     # K > 1, partial row tile
    (2500, 2, 700, 3, 2, 1024, 512),   # G > TG, P = 3
    (700, 1, 9, 2, 1, 256, 128),
])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_seg_agg_fused_lane_layout(n, m, g, p, k, tn, tg, op):
    """Lane-major filter-fused kernel with its bounds in SMEM: partial row
    tiles, several group tiles, K ranges per predicate, NaNs in selected and
    in filtered-out rows."""
    from repro.kernels.seg_agg.kernel import seg_agg_fused_pallas
    from repro.kernels.seg_agg.ref import bounds_mask_ref, seg_agg_fused_ref

    vals = rng.normal(size=(n, m)).astype(np.float32)
    vals[rng.random((n, m)) < 0.03] = np.nan
    ids = rng.integers(0, g, size=n).astype(np.int32)
    pred = rng.integers(0, 10, size=(n, p)).astype(np.float32)
    bounds = _rand_bounds(p, k)
    pred[0] = bounds[:, 0, 0]  # row 0 passes every predicate
    pred[1, 0] = 99.0  # row 1 fails the first
    vals[:2, 0] = np.nan
    sel = np.asarray(bounds_mask_ref(pred, bounds))
    assert sel[0] and not sel[1]
    ref = np.asarray(seg_agg_fused_ref(vals, ids, pred, bounds, g, op))
    flat = np.concatenate([bounds[:, :, 0], bounds[:, :, 1]], axis=1)
    out = np.asarray(seg_agg_fused_pallas(vals, ids, pred, flat, g, op, tn=tn,
                                          tg=tg, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_seg_agg_fused_no_predicates(op):
    """P = 0 through the dispatcher: every row counts, on the mask-free
    kernel, across a partial last row tile and two group tiles."""
    from repro.kernels.seg_agg.ops import seg_agg_fused
    from repro.kernels.seg_agg.ref import seg_agg_fused_ref

    n, m, g = 2500, 2, 600
    vals = rng.normal(size=(n, m)).astype(np.float32)
    vals[rng.random((n, m)) < 0.02] = np.nan
    ids = rng.integers(0, g, size=n).astype(np.int32)
    pred = np.zeros((n, 0), np.float32)
    bounds = np.zeros((0, 1, 2), np.float32)
    ref = np.asarray(seg_agg_fused_ref(vals, ids, pred, bounds, g, op))
    out = np.asarray(seg_agg_fused(vals, ids, pred, bounds, g, op,
                                   impl="interpret"))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_bounds_mask_matches_numpy():
    from repro.kernels.seg_agg.ref import bounds_mask_ref

    n, p = 2000, 3
    pred = rng.integers(-5, 15, size=(n, p)).astype(np.float32)
    bounds = _rand_bounds(p, 2, lo=-5, hi=15)
    expect = np.ones(n, bool)
    for i in range(p):
        any_i = np.zeros(n, bool)
        for j in range(2):
            lo, hi = bounds[i, j]
            any_i |= (pred[:, i] >= lo) & (pred[:, i] <= hi)
        expect &= any_i
    got = np.asarray(bounds_mask_ref(pred, bounds))
    np.testing.assert_array_equal(got, expect)


def test_seg_agg_fused_empty_mask():
    """All-never bounds: sums are zero, mins stay at the identity."""
    from repro.kernels.seg_agg.ref import seg_agg_fused_ref

    vals = rng.normal(size=(128, 2)).astype(np.float32)
    ids = rng.integers(0, 7, size=128).astype(np.int32)
    pred = np.zeros((128, 1), np.float32)
    bounds = np.full((1, 1, 2), 0, np.float32)
    bounds[..., 0], bounds[..., 1] = np.inf, -np.inf
    out = np.asarray(seg_agg_fused_ref(vals, ids, pred, bounds, 7, "sum"))
    np.testing.assert_array_equal(out, np.zeros((7, 2), np.float32))
    out = np.asarray(seg_agg_fused_ref(vals, ids, pred, bounds, 7, "min"))
    np.testing.assert_array_equal(out, np.full((7, 2), np.inf, np.float32))


# ---------------------------------------------------- seg_agg batch entry


@pytest.mark.parametrize("impl,with_rect", [("xla", True), ("xla", False),
                                            ("interpret", False)])
def test_seg_agg_batch_blocks_matches_per_op(impl, with_rect):
    """The combined one-launch entry (shared masks/gathers for the SUM and
    MIN/MAX blocks) must agree with the per-op ``seg_agg_batch`` dispatch —
    keeps the two public batch paths from drifting apart."""
    from repro.kernels.seg_agg.ops import seg_agg_batch, seg_agg_batch_blocks

    n, g, s = 1000, 8, 5
    sum_vals = rng.normal(size=(n, 3)).astype(np.float32)
    mm_vals = rng.normal(size=(n, 2)).astype(np.float32)
    mm_vals[rng.integers(0, n, size=4), 0] = np.nan  # NaN-confinement contract
    ids = rng.integers(0, g, size=n).astype(np.int32)
    pred = rng.integers(0, 10, size=(n, 2)).astype(np.float32)
    bounds = np.stack([_rand_bounds(2, 2) for _ in range(s)])
    rect = None
    if with_rect:
        counts = np.bincount(ids, minlength=g)
        r = int(counts.max())
        order = np.argsort(ids, kind="stable")
        starts = np.concatenate([[0], np.cumsum(counts[:-1])])
        pos = np.arange(n) - starts[ids[order]]
        rect = np.full((g, r), n, np.int32)
        rect[ids[order], pos] = order
    sums, mm = seg_agg_batch_blocks(sum_vals, mm_vals, ids, pred, bounds, g,
                                    impl=impl, rect_idx=rect)
    ref_sums = np.asarray(seg_agg_batch(sum_vals, ids, pred, bounds, g,
                                        "sum", impl=impl))
    ref_mm = np.asarray(seg_agg_batch(mm_vals, ids, pred, bounds, g,
                                      "min", impl=impl))
    np.testing.assert_allclose(np.asarray(sums), ref_sums, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(mm), ref_mm, rtol=1e-5, atol=1e-5)
    sums_only, none_mm = seg_agg_batch_blocks(sum_vals, None, ids, pred,
                                              bounds, g, impl=impl, rect_idx=rect)
    assert none_mm is None
    np.testing.assert_allclose(np.asarray(sums_only), ref_sums, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------- flash attn


@pytest.mark.parametrize("b,h,hkv,s,dh", [
    (2, 4, 2, 256, 64), (1, 8, 1, 128, 32), (1, 4, 4, 100, 64), (2, 2, 2, 64, 128),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(b, h, hkv, s, dh, causal):
    from repro.kernels.flash_attn.kernel import flash_attention_pallas
    from repro.kernels.flash_attn.ref import mha_ref

    q = rng.normal(size=(b, h, s, dh)).astype(np.float32)
    k = rng.normal(size=(b, hkv, s, dh)).astype(np.float32)
    v = rng.normal(size=(b, hkv, s, dh)).astype(np.float32)
    ref = np.asarray(mha_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))
    out = np.asarray(flash_attention_pallas(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal,
        tq=64, tk=64, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    from repro.kernels.flash_attn.kernel import flash_attention_pallas
    from repro.kernels.flash_attn.ref import mha_ref

    q = jnp.asarray(rng.normal(size=(1, 4, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    ref = np.asarray(mha_ref(q, k, v)).astype(np.float32)
    out = np.asarray(flash_attention_pallas(q, k, v, tq=64, tk=64, interpret=True)).astype(np.float32)
    np.testing.assert_allclose(out, ref, rtol=5e-2, atol=5e-2)


# -------------------------------------------------------------- decode attn


@pytest.mark.parametrize("b,h,hkv,s,dh,tk", [
    (2, 8, 2, 512, 64, 128), (1, 4, 1, 300, 128, 128), (3, 4, 4, 128, 32, 64),
])
def test_decode_attention(b, h, hkv, s, dh, tk):
    from repro.kernels.decode_attn.kernel import decode_attention_pallas
    from repro.kernels.decode_attn.ref import decode_attention_ref

    q = rng.normal(size=(b, h, dh)).astype(np.float32)
    k = rng.normal(size=(b, hkv, s, dh)).astype(np.float32)
    v = rng.normal(size=(b, hkv, s, dh)).astype(np.float32)
    pos = rng.integers(1, s + 1, size=b).astype(np.int32)
    ref = np.asarray(decode_attention_ref(*map(jnp.asarray, (q, k, v, pos))))
    out = np.asarray(decode_attention_pallas(
        *map(jnp.asarray, (q, k, v, pos)), tk=tk, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_decode_attention_pos_mask_exact():
    """Entries beyond pos must not contribute at all."""
    from repro.kernels.decode_attn.kernel import decode_attention_pallas

    b, h, s, dh = 1, 2, 64, 32
    q = rng.normal(size=(b, h, dh)).astype(np.float32)
    k = rng.normal(size=(b, h, s, dh)).astype(np.float32)
    v = rng.normal(size=(b, h, s, dh)).astype(np.float32)
    pos = np.asarray([10], np.int32)
    out1 = np.asarray(decode_attention_pallas(*map(jnp.asarray, (q, k, v, pos)),
                                              tk=32, interpret=True))
    k2, v2 = k.copy(), v.copy()
    k2[:, :, 10:] = 999.0
    v2[:, :, 10:] = -999.0
    out2 = np.asarray(decode_attention_pallas(*map(jnp.asarray, (q, k2, v2, pos)),
                                              tk=32, interpret=True))
    np.testing.assert_allclose(out1, out2, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------- ssd scan


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 256, 4, 64, 32, 64), (1, 100, 2, 32, 16, 32), (1, 512, 3, 16, 64, 128),
])
def test_ssd_scan(b, s, h, p, n, chunk):
    from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
    from repro.kernels.ssd_scan.ref import ssd_chunked_xla, ssd_ref

    x = rng.normal(size=(b, s, h, p)).astype(np.float32)
    dt = (0.001 + rng.random((b, s, h)) * 0.1).astype(np.float32)
    A = (-rng.random(h) * 2 - 0.1).astype(np.float32)
    Bm = rng.normal(size=(b, s, n)).astype(np.float32)
    Cm = rng.normal(size=(b, s, n)).astype(np.float32)
    ref, _ = ssd_ref(*map(jnp.asarray, (x, dt, A, Bm, Cm)))
    ref = np.asarray(ref)
    xla = np.asarray(ssd_chunked_xla(*map(jnp.asarray, (x, dt, A, Bm, Cm)), chunk=chunk))
    pal = np.asarray(ssd_scan_pallas(*map(jnp.asarray, (x, dt, A, Bm, Cm)),
                                     chunk=chunk, interpret=True))
    np.testing.assert_allclose(xla, ref, rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(pal, ref, rtol=5e-3, atol=5e-3)


def test_ssd_final_state_matches_sequential():
    from repro.kernels.ssd_scan.ref import ssd_final_state, ssd_ref

    b, s, h, p, n = 1, 96, 2, 16, 8
    x = rng.normal(size=(b, s, h, p)).astype(np.float32)
    dt = (0.01 + rng.random((b, s, h)) * 0.05).astype(np.float32)
    A = (-rng.random(h) - 0.1).astype(np.float32)
    Bm = rng.normal(size=(b, s, n)).astype(np.float32)
    Cm = rng.normal(size=(b, s, n)).astype(np.float32)
    _, final = ssd_ref(*map(jnp.asarray, (x, dt, A, Bm, Cm)))
    est = ssd_final_state(*map(jnp.asarray, (x, dt, A, Bm)))
    np.testing.assert_allclose(np.asarray(est), np.asarray(final), rtol=1e-4, atol=1e-4)
