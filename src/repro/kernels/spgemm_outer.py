"""OuterSPACE-like outer-product SpGEMM Pallas kernel: (U_K C_M, U_K C_N) —
paper Fig 2d / Fig 3d.

Two bodies (DESIGN.md §7):

``method="sparse"`` (the interpreter's default while the tables fit VMEM) —
the sparsity-proportional body. Both operands are K-major compressed
fibers, so the whole matrices scatter into resident dense tables — A into
``(M, K)``, B into ``(N, K)`` VMEM scratch (coordinate-major, the fastest
scatter layout) — ONCE at the first grid step (cost ∝ the two nonzero
counts; this is the "linked-list merge" of OuterSPACE collapsed into a
single scatter because the accumulator is dense). Every output tile is then
one MXU dot contracting K between table row slices: no expansion, no K grid
dimension, no per-step accumulator traffic. Per-tile ``pl.when`` skips
(driven by the scalar-prefetched per-window nonzero counts from
:func:`repro.formats.ell.block_window_nnz`) write zeros for tiles whose M
or N window holds no nonzeros. The resident tables bound the method:
``spgemm_outer_pallas`` auto-falls back to the reference body when
``4·K·(M+N)`` bytes exceed :data:`OUTER_TABLE_BYTES_MAX`.

``method="reference"`` — under Mosaic (the default there)
:func:`repro.kernels.expand.expansion_gemm`; under the interpreter the PR-1
body, kept as the parity oracle: per (M, N, K-block) step, one-hot expand
both operands' fiber blocks to dense (bk, bm)/(bk, bn) tiles and apply a
rank-bk MXU update to an output-stationary accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.formats.ell import EllMatrix, block_window_nnz
from repro.kernels.expand import expand_minor, expansion_gemm
from repro.kernels.sparse_gather import (
    check_sparse_lowers,
    fit_block,
    scatter_table,
)

#: Resident-table budget of the sparse body: A's (M, K) plus B's (N, K)
#: f32 tables must fit alongside the operand blocks in VMEM.
OUTER_TABLE_BYTES_MAX = 8 << 20


# ------------------------------------------------------------ reference body
def _outer_reference_kernel(
    av_ref, ai_ref, bv_ref, bi_ref, o_ref, acc_ref,
    *, bm: int, bn: int, k_steps: int,
):
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Expand this K block's fibers against the (i, j) output partition.
    ea = expand_minor(ai_ref[...], av_ref[...], i * bm, bm, jnp.float32,
                      method="gather")  # (bk, bm)
    eb = expand_minor(bi_ref[...], bv_ref[...], j * bn, bn, jnp.float32,
                      method="gather")  # (bk, bn)
    # Σ_k outer(ea[k], eb[k]) == eaᵀ @ eb : one MXU rank-bk update.
    acc_ref[...] += jax.lax.dot_general(
        ea, eb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(kk == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _outer_reference(a, b, *, bm, bn, bk, interpret):
    m, k = a.shape
    n = b.shape[1]
    k_steps = k // bk
    out_dtype = jnp.result_type(a.vals.dtype, b.vals.dtype)

    kernel = functools.partial(_outer_reference_kernel, bm=bm, bn=bn,
                               k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bk, a.cap), lambda i, j, kk: (kk, 0)),  # A vals (K-major)
            pl.BlockSpec((bk, a.cap), lambda i, j, kk: (kk, 0)),  # A ids -> M
            pl.BlockSpec((bk, b.cap), lambda i, j, kk: (kk, 0)),  # B vals (K-major)
            pl.BlockSpec((bk, b.cap), lambda i, j, kk: (kk, 0)),  # B ids -> N
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a.vals, a.ids, b.vals, b.ids)


# --------------------------------------------------------------- sparse body
def _outer_sparse_kernel(
    awin_ref, bwin_ref, flag_ref,    # scalar-prefetch window counts (SMEM)
    av_ref, ai_ref, bv_ref, bi_ref,
    o_ref, ta, tb,
    *, bm: int, bn: int,
):
    i, j = pl.program_id(0), pl.program_id(1)

    # Build both resident tables once (transposed, coordinate-major: the
    # column-scatter layout is the fastest construction primitive in
    # interpret mode); either operand all-zero means every output tile is
    # zero, so construction is skipped wholesale.
    @pl.when((i == 0) & (j == 0) & (flag_ref[0] > 0))
    def _construct():
        ta[...] = scatter_table(ai_ref[...], av_ref[...], ta.shape[0])
        tb[...] = scatter_table(bi_ref[...], bv_ref[...], tb.shape[0])

    live = (awin_ref[i] > 0) & (bwin_ref[j] > 0)

    @pl.when(live)
    def _compute():
        o_ref[...] = jax.lax.dot_general(
            ta[pl.ds(i * bm, bm), :], tb[pl.ds(j * bn, bn), :],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


def _outer_sparse(a, b, *, bm, bn, interpret):
    m, k = a.shape
    n = b.shape[1]
    awin = block_window_nnz(a, bm)             # nnz per M window of A
    bwin = block_window_nnz(b, bn)             # nnz per N window of B
    flag = ((awin.sum() > 0) & (bwin.sum() > 0)).astype(jnp.int32).reshape(1)
    out_dtype = jnp.result_type(a.vals.dtype, b.vals.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((k, a.cap), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((k, a.cap), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((k, b.cap), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((k, b.cap), lambda i, j, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((m, k), jnp.float32),   # resident A table (M-major)
            pltpu.VMEM((n, k), jnp.float32),   # resident B table (N-major)
        ],
    )
    kernel = functools.partial(_outer_sparse_kernel, bm=bm, bn=bn)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(awin, bwin, flag, a.vals, a.ids, b.vals, b.ids)


# -------------------------------------------------------------- entry point
def spgemm_outer_pallas(
    a: EllMatrix,
    b: EllMatrix,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
    method: str = "auto",
) -> jnp.ndarray:
    """A (K column-fibers, ids->M) × B (K row-fibers, ids->N) -> (M, N).

    ``method``: ``"sparse"`` (resident scatter tables, construction ∝ nnz;
    interpreter only), ``"reference"`` (expansion body), or ``"auto"`` —
    under the interpreter sparse while both resident tables fit the
    :data:`OUTER_TABLE_BYTES_MAX` VMEM budget; under Mosaic always the
    expansion body, lowered as :func:`~repro.kernels.expand.expansion_gemm`.
    Blocks auto-shrink to divide ragged shapes (``bk`` only tiles the
    reference body).
    """
    assert a.major_axis == 1 and b.major_axis == 0
    m, k = a.shape
    kb, n = b.shape
    assert k == kb, (a.shape, b.shape)
    bm = fit_block(m, bm)
    bn = fit_block(n, bn)
    if method == "auto":
        fits = 4 * k * (m + n) <= OUTER_TABLE_BYTES_MAX
        method = "sparse" if interpret and fits else "reference"
    if method == "reference":
        if not interpret:
            return expansion_gemm(a, b, bm=bm, bn=bn, bk=bk)
        return _outer_reference(a, b, bm=bm, bn=bn, bk=fit_block(k, bk),
                                interpret=interpret)
    if method == "sparse":
        check_sparse_lowers(interpret, "spgemm_outer", "a scatter-add")
        return _outer_sparse(a, b, bm=bm, bn=bn, interpret=interpret)
    raise ValueError(f"unknown spgemm_outer method: {method!r}")
