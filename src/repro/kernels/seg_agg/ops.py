"""Dispatching wrappers for grouped aggregation.

Implementation selection (shared convention for all kernels in this repo):

* ``REPRO_KERNELS=pallas``     — compiled Pallas (TPU),
* ``REPRO_KERNELS=interpret``  — Pallas interpret mode (CPU correctness),
* ``REPRO_KERNELS=xla``        — pure-jnp reference (XLA lowering),
* unset                        — pallas on TPU, xla elsewhere.

Entry points:

* ``seg_agg``        — plain (N, M) grouped aggregation with an explicit mask
  (the seed per-measure path keeps using this);
* ``seg_agg_fused``  — filter-fused variant: the mask is built on-device from
  encoded predicate range bounds (no HBM mask round-trip on the Pallas path);
* ``seg_agg_batch``  — shared-scan batch: S signatures' bounds against one
  value block, one kernel launch, returns (S, num_groups, M);
* ``seg_agg_batch_blocks`` — one launch for a whole shared-scan group: the
  fused SUM block plus the optional MIN/MAX block, sharing the per-signature
  masks and rect gathers between the two reduces (the service miss
  planner's entry point).

Every dispatcher call counts as one kernel launch in a module-level probe
(``launch_count``/``reset_launch_count``), kept per entry point, so tests can
assert the executor's single-launch property and a run on the chip can show
which entry points it drove.  Kernels are validated against ref.py in
interpret mode by the test suite and compiled for a described v5e by
``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import collections
import functools
import os

import jax
import jax.numpy as jnp

from .kernel import seg_agg_fused_pallas, seg_agg_lanes, seg_agg_pallas
from .ref import IDENTITY, bounds_mask_ref, seg_agg_fused_ref, seg_agg_ref

_LAUNCHES: collections.Counter = collections.Counter()


def launch_count(entry: str | None = None) -> int:
    """Kernel launches since the last reset: all of them, or those of one
    entry point (``'seg_agg_fused'``, ...)."""
    return _LAUNCHES[entry] if entry else sum(_LAUNCHES.values())


def reset_launch_count() -> None:
    _LAUNCHES.clear()


def _record_launch(entry: str) -> None:
    _LAUNCHES[entry] += 1


def kernel_impl() -> str:
    env = os.environ.get("REPRO_KERNELS", "").lower()
    if env in ("pallas", "interpret", "xla"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def seg_agg(values, ids, mask, num_groups: int, op: str = "sum", impl: str | None = None):
    """Grouped aggregation: (N, M) values + (N,) ids -> (num_groups, M)."""
    impl = impl or kernel_impl()
    _record_launch("seg_agg")
    if impl == "xla":
        return seg_agg_ref(values, ids, mask, num_groups, op)
    return seg_agg_pallas(
        values, ids, mask, num_groups, op, interpret=(impl == "interpret")
    )


@functools.partial(jax.jit, static_argnames=("num_groups", "op"))
def _fused_ref_jit(values, ids, pred_cols, bounds, num_groups, op):
    return seg_agg_fused_ref(values, ids, pred_cols, bounds, num_groups, op)


def _rect_reduce(values, mask, rect_idx, op):
    """Gather-based segment reduce over a precomputed (G, R) row-index
    rectangle (rows of group g, padded with out-of-range indices).  Avoids
    XLA's serial scatter on CPU — the hot reduce becomes a vectorized gather
    + axis reduce — and tree-reduces instead of sequentially accumulating
    (tighter f32 error).  Pad cells read mask=False, so they contribute the
    op identity; NaNs stay confined to their own group cell."""
    mrect = jnp.take(mask, rect_idx, axis=0, mode="fill", fill_value=False)
    vrect = jnp.take(values, rect_idx, axis=0, mode="fill", fill_value=0.0)
    if op == "sum":
        return jnp.sum(jnp.where(mrect[..., None], vrect, 0.0), axis=1)
    ident = jnp.inf if op == "min" else -jnp.inf
    vrect = jnp.where(mrect[..., None], vrect, ident)
    return jnp.min(vrect, axis=1) if op == "min" else jnp.max(vrect, axis=1)


@functools.partial(jax.jit, static_argnames=("op",))
def _fused_rect_jit(values, pred_cols, bounds, rect_idx, op):
    mask = bounds_mask_ref(pred_cols, bounds)
    return _rect_reduce(jnp.asarray(values, jnp.float32), mask, rect_idx, op)


def seg_agg_fused(values, ids, pred_cols, bounds, num_groups: int,
                  op: str = "sum", impl: str | None = None, rect_idx=None):
    """Filter-fused grouped aggregation (single launch).

    values (N, M), ids (N,), pred_cols (N, P) f32, bounds (P, K, 2) f32
    inclusive [lo, hi] ranges (OR over K, AND over P) -> (num_groups, M).
    With P == 0 (no predicates) this degrades to a plain all-rows reduce.
    ``rect_idx`` (optional, XLA path) is a cached (num_groups, R) row-index
    rectangle for these ids; when given, the reduce is gather-based instead
    of scatter-based (much faster on CPU backends).
    """
    impl = impl or kernel_impl()
    _record_launch("seg_agg_fused")
    p = int(bounds.shape[0])
    if impl == "xla":
        b = jnp.asarray(bounds, jnp.float32)
        if rect_idx is not None:
            return _fused_rect_jit(values, pred_cols, b, rect_idx, op)
        return _fused_ref_jit(values, ids, pred_cols, b, num_groups, op)
    if p == 0:
        return seg_agg_pallas(values, ids, None, num_groups, op,
                              interpret=(impl == "interpret"))
    b = jnp.asarray(bounds, jnp.float32)
    flat = jnp.concatenate([b[:, :, 0], b[:, :, 1]], axis=1)  # (P, 2K)
    return seg_agg_fused_pallas(values, ids, pred_cols, flat, num_groups, op,
                                interpret=(impl == "interpret"))


# unrolled per-group GEMM below this many groups; einsum (one fused
# batched-dot) above it, where unrolling would bloat the program
_BATCH_GEMM_UNROLL_MAX_G = 64


def _rect_batch_masks(pred_cols, bounds, rect_idx):
    """(S, G, R) per-signature mask rectangles, built in one vmapped pass
    over the batch's (S, P, K, 2) bounds."""
    masks = jax.vmap(lambda b: bounds_mask_ref(pred_cols, b))(bounds)  # (S, N)
    return jnp.take(masks, rect_idx, axis=1, mode="fill", fill_value=False)


def _rect_batch_sum(mrect, values, rect_idx):
    """Batched masked segment-sum on the rect layout.

    The (G, R, M) value gather does not depend on the signature, so it is
    done once and shared by all S masks; the reduce is then a G-batched
    (S, R) x (R, 2M) matmul over [NaN-cleaned values | NaN indicators]
    (GEMM instead of S separate where+sum sweeps over the rectangle), with
    groups whose selected rows carried NaNs re-poisoned afterwards — the
    same NaN contract as ``seg_agg_fused``.
    """
    values = jnp.asarray(values, jnp.float32)
    m = values.shape[1]
    vrect = jnp.take(values, rect_idx, axis=0, mode="fill", fill_value=0.0)  # (G,R,M)
    nan = jnp.isnan(vrect)
    stacked = jnp.concatenate(
        [jnp.where(nan, 0.0, vrect), nan.astype(jnp.float32)], axis=-1)
    mf = mrect.astype(jnp.float32)
    g = stacked.shape[0]
    if g <= _BATCH_GEMM_UNROLL_MAX_G:
        both = jnp.stack([mf[:, i, :] @ stacked[i] for i in range(g)], axis=1)
    else:
        both = jnp.einsum("sgr,grm->sgm", mf, stacked)
    return both[..., :m] + jnp.where(both[..., m:] > 0, jnp.nan, 0.0)


def _rect_batch_minmax(mrect, values, rect_idx, op):
    """Batched masked min/max on the rect layout: values are gathered once
    in (M, G, R) layout so each signature's reduce runs over the contiguous
    last axis (a strided (G, R, M) reduce is ~2x slower on CPU)."""
    ident = jnp.inf if op == "min" else -jnp.inf
    red = jnp.min if op == "min" else jnp.max
    vrect_t = jnp.take(jnp.asarray(values, jnp.float32).T, rect_idx,
                       axis=1, mode="fill", fill_value=ident)  # (M, G, R)
    outs = [red(jnp.where(mrect[i][None], vrect_t, ident), axis=2)  # (M, G)
            for i in range(mrect.shape[0])]
    return jnp.stack(outs).transpose(0, 2, 1)  # (S, G, M)


@functools.partial(jax.jit, static_argnames=("op",))
def _batch_rect_jit(values, pred_cols, bounds, rect_idx, op):
    mrect = _rect_batch_masks(pred_cols, bounds, rect_idx)
    if op == "sum":
        return _rect_batch_sum(mrect, values, rect_idx)
    return _rect_batch_minmax(mrect, values, rect_idx, op)


@jax.jit
def _batch_blocks_rect_jit(sum_block, mm_block, pred_cols, bounds, rect_idx):
    mrect = _rect_batch_masks(pred_cols, bounds, rect_idx)
    return (_rect_batch_sum(mrect, sum_block, rect_idx),
            _rect_batch_minmax(mrect, mm_block, rect_idx, "min"))


@functools.partial(jax.jit, static_argnames=("op",))
def _masked_rect_jit(values, mask, rect_idx, op):
    return _rect_reduce(jnp.asarray(values, jnp.float32), mask > 0.5, rect_idx, op)


_SEGMENT = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
            "max": jax.ops.segment_max}


@functools.partial(jax.jit, static_argnames=("num_groups", "op"))
def _masked_xla_jit(values, ids, mask, num_groups, op):
    v = jnp.where((mask > 0.5)[:, None], jnp.asarray(values, jnp.float32),
                  IDENTITY[op])
    return _SEGMENT[op](v, ids, num_segments=num_groups)


def seg_agg_masked(values, ids, mask, num_groups: int, op: str = "sum",
                   impl: str | None = None, rect_idx=None):
    """Fused grouped aggregation with an explicit row mask (single launch).

    Same NaN contract as ``seg_agg_fused`` (masked-out rows contribute the
    op identity; NaNs stay in their own group — unlike the seed ``seg_agg``,
    whose mask-multiply lets masked-out NaNs poison their group).  Used when
    predicates need exact host-side evaluation (values outside the f32-exact
    range) but the aggregation should stay fused and device-side.
    """
    impl = impl or kernel_impl()
    _record_launch("seg_agg_masked")
    mask = jnp.asarray(mask, jnp.float32)
    if impl != "xla":
        # the kernel drops masked-out rows (op identity) and is NaN-safe
        return seg_agg_pallas(values, ids, mask, num_groups, op,
                              interpret=(impl == "interpret"))
    if rect_idx is not None:
        return _masked_rect_jit(values, mask, rect_idx, op)
    return _masked_xla_jit(values, ids, mask, num_groups, op)


@functools.partial(jax.jit, static_argnames=("num_groups", "op", "impl"))
def _batch_jit(values, ids, pred_cols, bounds, num_groups, op, impl):
    s = bounds.shape[0]
    n, m = values.shape
    values = jnp.asarray(values, jnp.float32)
    # one vmapped bounds pass (as in _rect_batch_masks) instead of unrolling
    # S copies of the mask computation into the program
    masks = jax.vmap(lambda b: bounds_mask_ref(pred_cols, b))(bounds)  # (S, N)
    if impl == "xla":
        v = jnp.where(masks.T[:, :, None], values[:, None, :], IDENTITY[op])
        out = _SEGMENT[op](v.reshape(n, s * m), ids, num_segments=num_groups)
        return out.reshape(num_groups, s, m).transpose(1, 0, 2)
    # lane-major (S*M, N) block: masked-out rows hold the op identity; the
    # kernel keeps NaNs of selected rows in their own group.  Concatenating
    # S (M, N) slabs writes the block in the kernel's layout; a reshape of
    # an (S, M, N) select instead costs a relayout copy of the whole block.
    vt = values.T
    v = jnp.concatenate([jnp.where(masks[k][None, :], vt, IDENTITY[op])
                         for k in range(s)], axis=0)
    out = seg_agg_lanes(v, ids, None, num_groups, op,
                        interpret=(impl == "interpret"))  # (S*M, G)
    return out.reshape(s, m, num_groups).transpose(0, 2, 1)


def seg_agg_batch(values, ids, pred_cols, bounds, num_groups: int,
                  op: str = "sum", impl: str | None = None, rect_idx=None):
    """Shared-scan batched aggregation for S signatures (one launch).

    values (N, M), ids (N,), pred_cols (N, P) over the union of the batch's
    predicate columns, bounds (S, P, K, 2) per-signature ranges ->
    (S, num_groups, M).  Rows are scanned once; each signature's mask selects
    its slice of the expanded value block.  Masked-out rows are replaced by
    the op identity before reducing (NaN-safe, same contract as
    ``seg_agg_fused``).  ``rect_idx`` as in ``seg_agg_fused``.
    """
    impl = impl or kernel_impl()
    _record_launch("seg_agg_batch")
    if impl == "xla" and rect_idx is not None:
        return _batch_rect_jit(values, jnp.asarray(pred_cols, jnp.float32),
                               jnp.asarray(bounds, jnp.float32), rect_idx, op)
    return _batch_jit(values, ids, jnp.asarray(pred_cols, jnp.float32),
                      jnp.asarray(bounds, jnp.float32), num_groups, op, impl)


def seg_agg_batch_blocks(sum_block, mm_block, ids, pred_cols, bounds,
                         num_groups: int, impl: str | None = None,
                         rect_idx=None):
    """One launch for a whole shared-scan group: the fused SUM/COUNT/AVG
    block plus the (optional) fused MIN/MAX block, sharing the per-signature
    masks and rect gathers between the two reduces instead of rebuilding
    them per block.  This is the service miss planner's entry point — a
    dashboard refresh is one call here, whatever its measure mix.

    Returns ``(sums (S, G, 1+Ms), mm (S, G, Mm) | None)``; MAX columns are
    pre-negated by the caller so the mm reduce is always a min.  On the
    xla+rect path both blocks genuinely share one jitted computation (one
    recorded launch); the pallas/interpret and scatter fallbacks dispatch
    one kernel per block and record launches accordingly.
    """
    impl = impl or kernel_impl()
    _record_launch("seg_agg_batch_blocks")
    pred_cols = jnp.asarray(pred_cols, jnp.float32)
    b = jnp.asarray(bounds, jnp.float32)
    if impl == "xla" and rect_idx is not None:
        if mm_block is None:
            return _batch_rect_jit(sum_block, pred_cols, b, rect_idx, "sum"), None
        return _batch_blocks_rect_jit(sum_block, mm_block, pred_cols, b, rect_idx)
    sums = _batch_jit(sum_block, ids, pred_cols, b, num_groups, "sum", impl)
    mm = None
    if mm_block is not None:
        # second kernel dispatch on the per-block fallback
        _record_launch("seg_agg_batch_blocks")
        mm = _batch_jit(mm_block, ids, pred_cols, b, num_groups, "min", impl)
    return sums, mm
