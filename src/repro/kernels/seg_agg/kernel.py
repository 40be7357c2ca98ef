"""Pallas TPU kernel: grouped aggregation as a one-hot MXU matmul.

GPU engines do group-by aggregation with hash tables + atomic scatter-adds.
TPU has no fast scatter, so we restructure for the memory hierarchy and the
systolic MXU.  Fact rows sit on the 128-wide lane axis: (M, TN) value tiles,
(1, TN) group-id and mask tiles and (P, TN) predicate tiles stream
HBM->VMEM, a (TG, TN) one-hot of group ids is built *in VMEM* from a 2-D
iota, and SUM accumulates ``values · onehotᵀ`` on the MXU into an (M, TG)
output block.  The output block stays resident in VMEM across the whole N
sweep (grid minor axis) and is written back once per group tile.

Rows-on-lanes matters for HBM: a measure block is (N, M) with M of 1-6, and
an (N, M) operand in the TPU's (8, 128) tiling pads M to 128 lanes, so every
copy of it costs N·128·4 bytes.  As (M, N) the same block costs N·M·4.

MIN/MAX select through the one-hot on the VPU and reduce over the lane axis
into rows of the output block.  N is never padded in HBM: the grid runs
``cdiv(N, TN)`` row tiles and a global row-index guard drops the rows past N
in the last, partial tile.

Each kernel is named after its op (``seg_agg_sum``, ``seg_agg_min``,
``seg_agg_fused_sum``, ...): the name becomes the custom call's HLO
instruction name, which is how a profiler trace names the op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import IDENTITY

DEFAULT_TN = 1024  # fact rows per tile (lanes)
DEFAULT_TG = 512  # groups per tile; one-hot tile = TG*TN*4B = 2 MiB VMEM
LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tiles(n: int, num_groups: int, tn: int, tg: int) -> tuple[int, int, int]:
    """Row tile, group tile and padded group count: both tiles are lane
    multiples; a row tile never exceeds N rounded up to a lane multiple."""
    tn = min(_round_up(tn, LANES), _round_up(n, LANES))
    tg = min(_round_up(tg, LANES), _round_up(num_groups, LANES))
    return tn, tg, _round_up(num_groups, tg)


def _nt_dot(a, b):
    """(R, TN) x (TG, TN) -> (R, TG), contracting the lane axis, in full
    f32 precision on the MXU (sums must match a float64 oracle)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _lane_reduce_row(x, op: str):
    """(TG, TN) -> (1, TG): fold 128-lane slabs elementwise, transpose the
    (TG, 128) partial and finish over sublanes, so the result lies along
    lanes like a row of the output block."""
    comb = jnp.minimum if op == "min" else jnp.maximum
    part = x[:, :LANES]
    for c in range(LANES, x.shape[1], LANES):
        part = comb(part, x[:, c:c + LANES])
    part = part.T
    return (jnp.min if op == "min" else jnp.max)(part, axis=0, keepdims=True)


def _accumulate(out_ref, values, ids, mask, *, op: str, tg: int):
    """Fold one row tile into the resident (M, TG) output block.

    values (M, TN) f32, ids (1, TN) int32, mask (1, TN) bool.  Masked-out
    rows contribute the op identity.  SUM is NaN-safe: a NaN would poison
    every group of the tile through 0 * NaN in the matmul, so cleaned values
    and NaN indicators are reduced side by side, and only groups whose
    qualifying rows carry a NaN become NaN.  MIN/MAX select through the
    one-hot, so NaNs stay in their own group."""
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, IDENTITY[op], jnp.float32)

    tn = values.shape[1]
    local = ids - pl.program_id(0) * tg
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (tg, tn), 0) == local) & mask
    if op == "sum":
        nan = jnp.isnan(values)
        oh = onehot.astype(jnp.float32)
        acc = _nt_dot(jnp.where(mask & ~nan, values, 0.0), oh)
        hits = _nt_dot((mask & nan).astype(jnp.float32), oh)
        out_ref[...] += acc + jnp.where(hits > 0, jnp.nan, 0.0)
        return
    comb = jnp.minimum if op == "min" else jnp.maximum
    for j in range(values.shape[0]):
        vj = jnp.where(onehot, values[j:j + 1, :], IDENTITY[op])  # (TG, TN)
        out_ref[j:j + 1, :] = comb(out_ref[j:j + 1, :], _lane_reduce_row(vj, op))


def _row_guard(tn: int, n: int):
    """(1, TN) validity of this tile's rows: False past row N."""
    rows = pl.program_id(1) * tn + jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
    return rows < n


def _seg_agg_kernel(values_ref, ids_ref, *rest, op: str, tg: int, n: int,
                    has_mask: bool):
    out_ref = rest[-1]
    tn = values_ref.shape[1]
    mask = _row_guard(tn, n)
    if has_mask:
        mask = mask & (rest[0][...] > 0.5)
    _accumulate(out_ref, values_ref[...].astype(jnp.float32), ids_ref[...],
                mask, op=op, tg=tg)


def _row_pad(x, n: int, tn: int):
    """Pad the lane (row) axis up to one row tile, only when N < TN."""
    return jnp.pad(x, ((0, 0), (0, tn - n))) if n < tn else x


def seg_agg_lanes(vt, ids, mask, num_groups: int, op: str = "sum",
                  tn: int = DEFAULT_TN, tg: int = DEFAULT_TG,
                  interpret: bool = False):
    """Lane-major core: vt (M, N), ids (N,) int32, mask (N,) or None ->
    (M, num_groups) f32.  Rows with mask <= 0.5 contribute the op identity
    (``mask=None`` keeps every row); NaN handling as in ``_accumulate``.
    Callers that build their value block inside a jitted program (the
    shared-scan batch) call this directly, in the layout the kernel reads."""
    m, n = vt.shape
    tn, tg, gp = _tiles(n, num_groups, tn, tg)
    operands = [_row_pad(jnp.asarray(vt, jnp.float32), n, tn),
                _row_pad(jnp.asarray(ids, jnp.int32)[None, :], n, tn)]
    if mask is not None:
        operands.append(_row_pad(jnp.asarray(mask, jnp.float32)[None, :], n, tn))
    row_spec = functools.partial(pl.BlockSpec, index_map=lambda gb, nb: (0, nb))
    out = pl.pallas_call(
        functools.partial(_seg_agg_kernel, op=op, tg=tg, n=n,
                          has_mask=mask is not None),
        grid=(gp // tg, pl.cdiv(operands[0].shape[1], tn)),
        in_specs=[row_spec((m, tn))] + [row_spec((1, tn))] * (len(operands) - 1),
        out_specs=pl.BlockSpec((m, tg), lambda gb, nb: (0, gb)),
        out_shape=jax.ShapeDtypeStruct((m, gp), jnp.float32),
        interpret=interpret,
        name=f"seg_agg_{op}",
    )(*operands)
    return out[:, :num_groups]


@functools.partial(jax.jit, static_argnames=("num_groups", "op", "tn", "tg", "interpret"))
def seg_agg_pallas(
    values,
    ids,
    mask,
    num_groups: int,
    op: str = "sum",
    tn: int = DEFAULT_TN,
    tg: int = DEFAULT_TG,
    interpret: bool = False,
):
    """values (N, M), ids (N,) int32, mask (N,) or None -> (num_groups, M)
    f32; see ``seg_agg_lanes``."""
    return seg_agg_lanes(jnp.asarray(values, jnp.float32).T, ids, mask,
                         num_groups, op, tn, tg, interpret).T


# ------------------------------------------------------------- filter-fused


def _seg_agg_fused_kernel(bounds_ref, values_ref, ids_ref, pred_ref, out_ref,
                          *, op: str, tg: int, nk: int, n: int):
    tn = values_ref.shape[1]
    pred = pred_ref[...]  # (P, TN)
    # build the predicate mask inside the tile (no HBM mask round-trip):
    # AND over predicates of OR over that predicate's [lo, hi] ranges, the
    # bounds read as SMEM scalars (NaN-sentinel ranges match NaN values, see
    # ref.bounds_mask_ref).  Static unrolled loops — P and K are small
    # (dashboard filters).  Rows past N are cut by the row-index guard.
    mask = _row_guard(tn, n)
    for j in range(pred.shape[0]):
        x = pred[j:j + 1, :]
        x_nan = jnp.isnan(x)
        mj = None
        for k in range(nk):
            lo = bounds_ref[j * 2 * nk + k]
            hi = bounds_ref[j * 2 * nk + nk + k]
            lo_v = jnp.full(x.shape, lo, jnp.float32)
            within = ((x >= lo_v) & (x <= hi)) | (x_nan & jnp.isnan(lo_v))
            mj = within if mj is None else (mj | within)
        mask = mask & mj
    _accumulate(out_ref, values_ref[...].astype(jnp.float32), ids_ref[...],
                mask, op=op, tg=tg)


@functools.partial(jax.jit, static_argnames=("num_groups", "op", "tn", "tg", "interpret"))
def seg_agg_fused_pallas(
    values,
    ids,
    pred_cols,
    bounds,
    num_groups: int,
    op: str = "sum",
    tn: int = DEFAULT_TN,
    tg: int = DEFAULT_TG,
    interpret: bool = False,
):
    """Filter-fused grouped aggregation.

    values (N, M) f32, ids (N,) int32, pred_cols (N, P) f32 with P >= 1,
    bounds (P, 2K) f32 ([:, :K] lo / [:, K:] hi inclusive range pairs, OR
    within a predicate, AND across predicates) -> (num_groups, M) f32.

    The predicate mask is built inside the Pallas tile from the encoded
    bounds, so no (N,) mask is ever materialized in HBM.  Validated against
    ``ref.bounds_mask_ref`` + ``ref.seg_agg_fused_ref`` in interpret mode.
    """
    n, m = values.shape
    p = pred_cols.shape[1]
    nk = bounds.shape[1] // 2
    tn, tg, gp = _tiles(n, num_groups, tn, tg)
    vt = _row_pad(jnp.asarray(values, jnp.float32).T, n, tn)
    ids2 = _row_pad(jnp.asarray(ids, jnp.int32)[None, :], n, tn)
    pt = _row_pad(jnp.asarray(pred_cols, jnp.float32).T, n, tn)
    flat = jnp.asarray(bounds, jnp.float32).reshape(p * 2 * nk)
    out = pl.pallas_call(
        functools.partial(_seg_agg_fused_kernel, op=op, tg=tg, nk=nk, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(gp // tg, pl.cdiv(vt.shape[1], tn)),
            in_specs=[
                pl.BlockSpec((m, tn), lambda gb, nb, b: (0, nb)),
                pl.BlockSpec((1, tn), lambda gb, nb, b: (0, nb)),
                pl.BlockSpec((p, tn), lambda gb, nb, b: (0, nb)),
            ],
            out_specs=pl.BlockSpec((m, tg), lambda gb, nb, b: (0, gb)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, gp), jnp.float32),
        interpret=interpret,
        name=f"seg_agg_fused_{op}",
    )(flat, vt, ids2, pt)
    return out[:, :num_groups].T
