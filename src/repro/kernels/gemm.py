"""TPU-like dense GEMM Pallas kernel (paper Fig 2a / Fig 3a).

Output-stationary: the (bm, bn) accumulator lives in VMEM scratch across the
K grid dimension — the Pallas analogue of the systolic array's local partial
sums. Block shapes are MXU-aligned (multiples of 128 on the minor dims).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_gather import fit_block


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # HIGHEST: f32 operands take the full-precision MXU passes on the TPU
    # (the default there is one bf16 pass); bf16 operands are unaffected.
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemm_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """``a (M, K) @ b (K, N)`` with explicit VMEM tiling.

    Blocks auto-shrink to divide ragged shapes (``ops.gemm`` pads to the
    requested blocks first, so there the shrink never fires).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = fit_block(m, bm), fit_block(n, bn), fit_block(k, bk)
    k_steps = k // bk
    out_dtype = jnp.result_type(a.dtype, b.dtype)

    kernel = functools.partial(_gemm_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
