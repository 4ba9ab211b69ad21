"""MatRaptor-like Gustavson (column-wise product) SpGEMM Pallas kernel:
(U_K C_M, U_N C_K) — paper Fig 2e / Fig 3e.

Two bodies (DESIGN.md §7):

``method="sparse"`` (the interpreter's default) — the sparsity-proportional
body. The grid walks M blocks outermost; at the first N step of each M
block the kernel scatter-constructs A's windowed dense ``(K, bm)`` table
(only coordinates inside the M window land; cost ∝ A's in-window nonzeros)
into persistent VMEM scratch and amortizes it across every N block. B's
column fibers then *drive* the contraction exactly as in MatRaptor: each
nonzero ``B[k, n]`` names table row ``k``; the kernel gathers those rows in
capacity chunks and batch-dots them against ``b.vals``, accumulating in
register across the fiber dimension — per-column work ∝ that column's
nonzeros. Trip counts come from the scalar-prefetched live-chunk bounds
(:func:`repro.formats.ell.block_chunk_counts`); M windows that
:func:`~repro.formats.ell.block_window_nnz` proves empty of A nonzeros skip
construction and every tile that would read them.

``method="reference"`` — under Mosaic (the default there)
:func:`repro.kernels.expand.expansion_gemm`; under the interpreter the PR-1
body, kept as the parity oracle: both operands one-hot expanded to dense
(bn, bk)/(bk, bm) tiles per (N, M, K-block) step, contracted on the MXU.
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
    block_window_nnz,
    pad_capacity,
)
from repro.kernels.expand import expand_minor, expansion_gemm
from repro.kernels.sparse_gather import (
    check_sparse_lowers,
    chunked_gather_contract,
    fit_block,
)

#: Capacity-chunk width of the gather contraction over B's column fibers.
GUSTAVSON_FIBER_CHUNK = 16


# ------------------------------------------------------------ reference body
def _gustavson_reference_kernel(
    av_ref, ai_ref, bv_ref, bi_ref, o_ref, acc_ref,
    *, bm: int, bk: int, k_steps: int,
):
    j, i, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k0 = kk * bk
    # B column fibers (bn, cap_b) -> dense (bn, bk) for this K block: the
    # entries "scheduled" from the stream into the MAC queue.
    sb = expand_minor(bi_ref[...], bv_ref[...], k0, bk, jnp.float32,
                      method="gather")   # (bn, bk)
    # A K-major column fibers (bk, cap_a) -> dense (bk, bm) over the M block.
    ea = expand_minor(ai_ref[...], av_ref[...], i * bm, bm, jnp.float32,
                      method="gather")  # (bk, bm)
    # O[mblock, nblock] += ea(k,m)ᵀ·sb(n,k)ᵀ, contracted over k.
    acc_ref[...] += jax.lax.dot_general(
        ea, sb, (((0,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(kk == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gustavson_reference(a, b, *, bm, bn, bk, interpret):
    m, k = a.shape
    n = b.shape[1]
    k_steps = k // bk
    out_dtype = jnp.result_type(a.vals.dtype, b.vals.dtype)

    kernel = functools.partial(_gustavson_reference_kernel, bm=bm, bk=bk,
                               k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(n // bn, m // bm, k_steps),  # N outermost: column-wise walk
        in_specs=[
            pl.BlockSpec((bk, a.cap), lambda j, i, kk: (kk, 0)),  # A vals
            pl.BlockSpec((bk, a.cap), lambda j, i, kk: (kk, 0)),  # A ids -> M
            pl.BlockSpec((bn, b.cap), lambda j, i, kk: (j, 0)),   # B vals
            pl.BlockSpec((bn, b.cap), lambda j, i, kk: (j, 0)),   # B ids -> K
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a.vals, a.ids, b.vals, b.ids)


# --------------------------------------------------------------- sparse body
def _gustavson_sparse_kernel(
    awin_ref, bcnt_ref,              # scalar-prefetch counts (SMEM)
    av_ref, ai_ref, bv_ref, bi_ref,
    o_ref, table,
    *, bm: int, fc: int,
):
    i, j = pl.program_id(0), pl.program_id(1)

    # Windowed row-layout construction is the expansion primitive over the
    # M window; its sorted-fiber gather lowering beats a capacity-slot
    # scatter-add in interpret mode.
    @pl.when((j == 0) & (awin_ref[i] > 0))
    def _construct():
        table[...] = expand_minor(ai_ref[...], av_ref[...], i * bm, bm,
                                  jnp.float32, method="gather")

    # B's fibers drive: gather-contract accumulates (bn, bm) in register,
    # transposed on flush (the gather batches over B's column fibers).
    nlive = bcnt_ref[j] * (awin_ref[i] > 0)
    res = chunked_gather_contract(
        table[...], bi_ref, bv_ref, nlive, fc, o_ref.shape[1],
    )
    o_ref[...] = res.T.astype(o_ref.dtype)


def _gustavson_sparse(a, b, *, bm, bn, fc, interpret):
    m, k = a.shape
    n = b.shape[1]
    chunks = -(-b.cap // fc)
    if chunks * fc != b.cap:
        b = pad_capacity(b, chunks * fc)
    awin = block_window_nnz(a, bm)             # A nnz per M window
    bcnt = block_chunk_counts(b, bn, fc)       # live B chunks per N block
    out_dtype = jnp.result_type(a.vals.dtype, b.vals.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // bm, n // bn),               # M outermost: table amortized
        in_specs=[
            pl.BlockSpec((k, a.cap), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((k, a.cap), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((bn, b.cap), lambda i, j, *_: (j, 0)),
            pl.BlockSpec((bn, b.cap), lambda i, j, *_: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((k, bm), jnp.float32)],
    )
    kernel = functools.partial(_gustavson_sparse_kernel, bm=bm, fc=fc)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(awin, bcnt, a.vals, a.ids, b.vals, b.ids)


# -------------------------------------------------------------- entry point
def spgemm_gustavson_pallas(
    a: EllMatrix,
    b: EllMatrix,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
    method: str = "auto",
) -> jnp.ndarray:
    """A (K column-fibers, ids->M) × B (N column-fibers, ids->K) -> (M, N).

    ``method``: ``"sparse"`` (B-driven gather contraction, per-column work
    ∝ B's nonzeros; interpreter only), ``"reference"`` (expansion body), or
    ``"auto"`` — under the interpreter sparse while the gather volume
    (∝ ``cap_b``) undercuts the dense-K expansion it replaces
    (``cap_b <= K/4``); under Mosaic always the expansion body, lowered as
    :func:`~repro.kernels.expand.expansion_gemm`. Blocks auto-shrink to
    divide ragged shapes (``bk`` only tiles the reference body).
    """
    assert a.major_axis == 1 and b.major_axis == 1
    m, k = a.shape
    kb, n = b.shape
    assert k == kb, (a.shape, b.shape)
    bm = fit_block(m, bm)
    bn = fit_block(n, bn)
    if method == "auto":
        method = "sparse" if interpret and 4 * b.cap <= k else "reference"
    if method == "reference":
        if not interpret:
            return expansion_gemm(a, b, bm=bm, bn=bn, bk=bk)
        return _gustavson_reference(a, b, bm=bm, bn=bn, bk=fit_block(k, bk),
                                    interpret=interpret)
    if method == "sparse":
        check_sparse_lowers(interpret, "spgemm_gustavson",
                            "an in-kernel gather")
        fc = min(GUSTAVSON_FIBER_CHUNK, b.cap)
        return _gustavson_sparse(a, b, bm=bm, bn=bn, fc=fc,
                                 interpret=interpret)
    raise ValueError(f"unknown spgemm_gustavson method: {method!r}")
