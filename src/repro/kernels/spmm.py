"""EIE-like SpMM Pallas kernel: (U_M U_K, U_N C_K) — paper Fig 2b / Fig 3b.

Two bodies (DESIGN.md §7):

``method="sparse"`` (the interpreter's default) — the sparsity-proportional
body. The grid runs the N blocks *outermost*; at the first M step of each N
block the kernel scatter-constructs B's dense ``(K, bn)`` column table ONCE
into persistent VMEM scratch and amortizes it across every M block. The
fiber chunks stream HBM→VMEM through double-buffered ``make_async_copy``
DMAs (fetch chunk ``c+1`` while chunk ``c`` scatters), the trip count is
the scalar-prefetched live-chunk bound from
:func:`repro.formats.ell.block_chunk_counts` (dead chunks are never
fetched), and an all-empty fiber block skips construction *and* the MXU
contraction entirely (``pl.when``), writing zeros. Construction cost is
proportional to the nonzeros; the per-tile contraction is the same single
MXU dot the expansion path pays — but paid once per tile instead of
expansion-plus-dot.

``method="reference"`` — under Mosaic (the default there)
:func:`repro.kernels.expand.expansion_gemm`; under the interpreter the PR-1
one-hot/gather expansion body, kept verbatim as the interpret-mode parity
oracle: it re-expands B's fibers to a dense ``(bn, K)`` tile for EVERY
output tile, burning O(bn × K) per tile regardless of sparsity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.formats.ell import EllMatrix, block_chunk_counts, pad_capacity
from repro.kernels.expand import expand_minor, expansion_gemm
from repro.kernels.sparse_gather import (
    check_sparse_lowers,
    fit_block,
    scatter_table,
)

#: Capacity-chunk width of the double-buffered fiber DMA.
SPMM_FIBER_CHUNK = 64


# ------------------------------------------------------------ reference body
def _spmm_reference_kernel(a_ref, bv_ref, bi_ref, o_ref, *, k_size: int):
    # Expand B's (bn, cap) compressed fibers into dense (bn, K) in one shot.
    eb = expand_minor(bi_ref[...], bv_ref[...], 0, k_size, jnp.float32,
                      method="gather")
    # Single MXU contraction over K: (bm, K) · (bn, K)ᵀ — no transpose
    # materialised, dot_general contracts the shared K axis directly.
    o_ref[...] = jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), eb,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _spmm_reference(a, b, *, bm, bn, interpret):
    m, k = a.shape
    n = b.shape[1]
    cap = b.cap
    out_dtype = jnp.result_type(a.dtype, b.vals.dtype)
    kernel = functools.partial(_spmm_reference_kernel, k_size=k)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),      # A row block, full K
            pl.BlockSpec((bn, cap), lambda i, j: (j, 0)),    # B vals
            pl.BlockSpec((bn, cap), lambda i, j: (j, 0)),    # B ids
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(a, b.vals, b.ids)


# --------------------------------------------------------------- sparse body
def _spmm_sparse_kernel(cnt_ref,                     # scalar-prefetch (SMEM)
                        a_ref, bv_hbm, bi_hbm,       # A block; B fibers (ANY)
                        o_ref,
                        table, fv, fi, sems,         # VMEM scratch + DMA sems
                        *, bn: int, fc: int):
    j, i = pl.program_id(0), pl.program_id(1)
    nlive = cnt_ref[j]

    @pl.when((i == 0) & (nlive > 0))
    def _construct():
        table[...] = jnp.zeros_like(table)

        def dma(slot, cc, start):
            for src, dst in ((bv_hbm, fv), (bi_hbm, fi)):
                cp = pltpu.make_async_copy(
                    src.at[pl.ds(j * bn, bn), pl.ds(cc * fc, fc)],
                    dst.at[slot], sems.at[slot])
                cp.start() if start else cp.wait()

        dma(0, 0, True)                        # warm-up fetch of chunk 0

        def body(cc, _):
            slot = jax.lax.rem(cc, 2)

            @pl.when(cc + 1 < nlive)           # prefetch next while we work
            def _():
                dma(1 - slot, cc + 1, True)

            dma(slot, cc, False)               # wait for this chunk
            # Chunks of one fiber never collide (ids unique per fiber), and
            # distinct fibers own distinct columns, so chunk scatters sum.
            table[...] += scatter_table(fi[slot], fv[slot], table.shape[0])
            return 0

        jax.lax.fori_loop(0, nlive, body, 0)

    @pl.when(nlive > 0)
    def _compute():
        o_ref[...] = jax.lax.dot_general(
            a_ref[...].astype(jnp.float32), table[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)

    @pl.when(nlive == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


def _spmm_sparse(a, b, *, bm, bn, fc, interpret):
    m, k = a.shape
    n = b.shape[1]
    chunks = -(-b.cap // fc)
    if chunks * fc != b.cap:
        b = pad_capacity(b, chunks * fc)
    counts = block_chunk_counts(b, bn, fc)     # live chunks per N block
    out_dtype = jnp.result_type(a.dtype, b.vals.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // bn, m // bm),               # N outermost: table amortized
        in_specs=[
            pl.BlockSpec((bm, k), lambda j, i, cnt: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),   # B vals stay in HBM,
            pl.BlockSpec(memory_space=pltpu.ANY),   # chunks DMA'd on demand
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, cnt: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((k, bn), jnp.float32),       # persistent column table
            pltpu.VMEM((2, bn, fc), b.vals.dtype),  # double-buffered vals
            pltpu.VMEM((2, bn, fc), jnp.int32),     # double-buffered ids
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_spmm_sparse_kernel, bn=bn, fc=fc)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(counts, a, b.vals, b.ids)


# -------------------------------------------------------------- entry point
def spmm_pallas(
    a: jnp.ndarray,
    b: EllMatrix,
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
    method: str = "auto",
) -> jnp.ndarray:
    """Dense ``a (M, K)`` × compressed ``b`` (column fibers, ids->K) -> (M, N).

    ``method``: ``"sparse"`` (proportional body, interpreter only),
    ``"reference"`` (expansion body), or ``"auto"``. Under the interpreter
    ``auto`` is sparse unless the fibers are so dense (``cap > K/2``) that
    scatter construction costs more than the expansion it replaces; under
    Mosaic it is always the expansion body, lowered as
    :func:`~repro.kernels.expand.expansion_gemm`. Blocks auto-shrink to
    divide ragged shapes.
    """
    assert b.major_axis == 1, "spmm expects B in U_N C_K (column fibers)"
    m, k = a.shape
    kb, n = b.shape
    assert k == kb, (a.shape, b.shape)
    bm = fit_block(m, bm)
    bn = fit_block(n, bn)
    if method == "auto":
        sparse = interpret and 2 * b.cap <= k
        method = "sparse" if sparse else "reference"
    if method == "reference":
        if not interpret:
            return expansion_gemm(a, b, bm=bm, bn=bn)
        return _spmm_reference(a, b, bm=bm, bn=bn, interpret=interpret)
    if method == "sparse":
        check_sparse_lowers(interpret, "spmm", "a scatter-add")
        fc = min(SPMM_FIBER_CHUNK, b.cap)
        return _spmm_sparse(a, b, bm=bm, bn=bn, fc=fc, interpret=interpret)
    raise ValueError(f"unknown spmm method: {method!r}")
