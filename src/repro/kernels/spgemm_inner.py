"""ExTensor-like inner-product SpGEMM Pallas kernel: (U_M C_K, U_N C_K) —
paper Fig 2c / Fig 3c.

Two bodies (DESIGN.md §7):

``method="sparse"`` (the interpreter's default) — the sparsity-proportional
body. The grid runs N blocks outermost; at the first M step of each N block
the kernel scatter-constructs B's dense ``(K, bn)`` column table once into
persistent VMEM scratch (cost ∝ B's nonzeros) and amortizes it over every M
block. The contraction never touches dense K: A's compressed row fibers are
processed in capacity chunks — gather the table rows named by ``a.ids``,
batch-dot against ``a.vals`` over the chunk, accumulate **in register**
(the ``fori_loop`` carry) across the fiber dimension. The trip count is the
scalar-prefetched live-chunk bound
(:func:`repro.formats.ell.block_chunk_counts`), so contraction FLOPs and
gather volume scale with A's nonzeros — ExTensor's intersection where the
short operand's coordinates *drive* the walk. Blocks either operand proves
empty skip construction/compute and write zeros.

``method="reference"`` — under Mosaic (the default there)
:func:`repro.kernels.expand.expansion_gemm`; under the interpreter the PR-1
body, kept as the parity oracle: one-hot expansion of BOTH operands' fibers
to dense (bm, bk)/(bn, bk) tiles per (M, N, K) step, with the
scalar-prefetch occupancy skip (hierarchical intersection) it introduced.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.formats.ell import (
    EllMatrix,
    block_chunk_counts,
    pad_capacity,
    tile_occupancy,
)
from repro.kernels.expand import expand_minor, expansion_gemm
from repro.kernels.sparse_gather import (
    check_sparse_lowers,
    chunked_gather_contract,
    fit_block,
)

#: Capacity-chunk width of the gather contraction (finer = tighter skipping,
#: more loop iterations; 16 balances the two in interpret mode).
INNER_FIBER_CHUNK = 16


# ------------------------------------------------------------ reference body
def _inner_reference_kernel(
    a_occ_ref, b_occ_ref,           # scalar-prefetch occupancy (SMEM)
    av_ref, ai_ref, bv_ref, bi_ref, # VMEM operand blocks
    o_ref, acc_ref,
    *, bk: int, k_steps: int,
):
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Hierarchical intersection: only touch tiles where *both* operands have
    # nonzeros in this K range (ExTensor's coordinate-hierarchy skip).
    @pl.when((a_occ_ref[i, kk] > 0) & (b_occ_ref[j, kk] > 0))
    def _compute():
        k0 = kk * bk
        ea = expand_minor(ai_ref[...], av_ref[...], k0, bk, jnp.float32,
                          method="gather")  # (bm, bk)
        eb = expand_minor(bi_ref[...], bv_ref[...], k0, bk, jnp.float32,
                          method="gather")  # (bn, bk)
        acc_ref[...] += jax.lax.dot_general(
            ea, eb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kk == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _inner_reference(a, b, *, bm, bn, bk, interpret):
    m, k = a.shape
    n = b.shape[1]
    k_steps = k // bk
    out_dtype = jnp.result_type(a.vals.dtype, b.vals.dtype)

    # Block-level occupancy: sum per-fiber tile counts over fiber blocks.
    a_occ = tile_occupancy(a, bk).reshape(m // bm, bm, k_steps).sum(1)
    b_occ = tile_occupancy(b, bk).reshape(n // bn, bn, k_steps).sum(1)

    kernel = functools.partial(_inner_reference_kernel, bk=bk,
                               k_steps=k_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, a.cap), lambda i, j, kk, *_: (i, 0)),
            pl.BlockSpec((bm, a.cap), lambda i, j, kk, *_: (i, 0)),
            pl.BlockSpec((bn, b.cap), lambda i, j, kk, *_: (j, 0)),
            pl.BlockSpec((bn, b.cap), lambda i, j, kk, *_: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(a_occ, b_occ, a.vals, a.ids, b.vals, b.ids)


# --------------------------------------------------------------- sparse body
def _inner_sparse_kernel(
    acnt_ref, bnz_ref,              # scalar-prefetch counts (SMEM)
    av_ref, ai_ref, bv_ref, bi_ref,
    o_ref, table,
    *, fc: int,
):
    j, i = pl.program_id(0), pl.program_id(1)

    # Construction = the expansion primitive over full K (its sorted-fiber
    # gather lowering beats a capacity-slot scatter-add), transposed into
    # the K-major layout the gather contraction indexes by coordinate.
    @pl.when((i == 0) & (bnz_ref[j] > 0))
    def _construct():
        table[...] = expand_minor(bi_ref[...], bv_ref[...], 0,
                                  table.shape[0], jnp.float32,
                                  method="gather").T

    # In-register accumulation over A's live capacity chunks; zero trips
    # (either operand block empty) leaves the zeros initializer -> zero tile.
    nlive = acnt_ref[i] * (bnz_ref[j] > 0)
    o_ref[...] = chunked_gather_contract(
        table[...], ai_ref, av_ref, nlive, fc, o_ref.shape[0],
    ).astype(o_ref.dtype)


def _inner_sparse(a, b, *, bm, bn, fc, interpret):
    m, k = a.shape
    n = b.shape[1]
    chunks = -(-a.cap // fc)
    if chunks * fc != a.cap:
        a = pad_capacity(a, chunks * fc)
    acnt = block_chunk_counts(a, bm, fc)           # live A chunks per M block
    bnz = block_chunk_counts(b, bn)                # B-block emptiness flags
    out_dtype = jnp.result_type(a.vals.dtype, b.vals.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // bn, m // bm),                   # N outermost: table amortized
        in_specs=[
            pl.BlockSpec((bm, a.cap), lambda j, i, *_: (i, 0)),
            pl.BlockSpec((bm, a.cap), lambda j, i, *_: (i, 0)),
            pl.BlockSpec((bn, b.cap), lambda j, i, *_: (j, 0)),
            pl.BlockSpec((bn, b.cap), lambda j, i, *_: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((k, bn), jnp.float32)],
    )
    kernel = functools.partial(_inner_sparse_kernel, fc=fc)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(acnt, bnz, a.vals, a.ids, b.vals, b.ids)


# -------------------------------------------------------------- entry point
def spgemm_inner_pallas(
    a: EllMatrix,
    b: EllMatrix,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
    method: str = "auto",
) -> jnp.ndarray:
    """A (M row-fibers, ids->K) × B (N column-fibers, ids->K) -> (M, N).

    ``method``: ``"sparse"`` (gather contraction, FLOPs ∝ A's nonzeros;
    interpreter only), ``"reference"`` (expansion body), or ``"auto"`` —
    under the interpreter sparse while the gather volume (∝ ``cap_a``)
    undercuts the dense-K expansion it replaces (``cap_a <= K/4``); under
    Mosaic always the expansion body, lowered as
    :func:`~repro.kernels.expand.expansion_gemm`. Blocks auto-shrink to
    divide ragged shapes (``bk`` only tiles the reference body).
    """
    assert a.major_axis == 0 and b.major_axis == 1
    m, k = a.shape
    kb, n = b.shape
    assert k == kb, (a.shape, b.shape)
    bm = fit_block(m, bm)
    bn = fit_block(n, bn)
    if method == "auto":
        method = "sparse" if interpret and 4 * a.cap <= k else "reference"
    if method == "reference":
        if not interpret:
            return expansion_gemm(a, b, bm=bm, bn=bn, bk=bk)
        return _inner_reference(a, b, bm=bm, bn=bn, bk=fit_block(k, bk),
                                interpret=interpret)
    if method == "sparse":
        check_sparse_lowers(interpret, "spgemm_inner", "an in-kernel gather")
        fc = min(INNER_FIBER_CHUNK, a.cap)
        return _inner_sparse(a, b, bm=bm, bn=bn, fc=fc, interpret=interpret)
    raise ValueError(f"unknown spgemm_inner method: {method!r}")
