"""Shared building blocks of the sparsity-proportional kernel bodies
(DESIGN.md §7).

The PR-1 kernels decompress every compressed operand *per output tile, per
K step* — the expansion work is O(fibers × width) no matter how sparse the
operand is, and it is repeated for every tile that touches the operand. The
sparsity-proportional bodies instead:

1. **construct** each compressed operand's dense tile ONCE per owning grid
   block into persistent VMEM scratch, by scatter (cost ∝ entries scanned,
   i.e. the nonzeros plus their chunk padding), and *amortize* it across
   the whole other grid dimension;
2. **contract** either through the MXU against the amortized table (dense
   dot, construction-proportional), or — when the compressed fiber is
   short relative to the dense bound — by *gathering* table rows at the
   fiber coordinates and batch-dotting over the capacity dimension, so the
   contraction FLOPs themselves scale with the nonzero count;
3. **skip** every chunk/tile the scalar-prefetched per-block counts
   (:func:`repro.formats.ell.block_chunk_counts` /
   :func:`~repro.formats.ell.block_window_nnz`) prove empty.

These helpers are the pieces the four kernel bodies share. They are traced
inside Pallas kernels, so everything is shape-static and returns values
(the kernel assigns them to refs).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def fit_block(dim: int, block: int) -> int:
    """Largest usable block size <= ``block`` that divides ``dim``.

    Relaxes the seed kernels' hard ``dim % block == 0`` asserts: ragged
    workload shapes (``core/workloads.py``) auto-shrink the block instead
    of requiring callers to pre-pad to 128. ``dim < block`` collapses to a
    single block; a non-dividing ``dim`` falls back to ``gcd(dim, block)``
    (possibly 1 — correct, if slow, which only direct ``*_pallas`` callers
    with unpadded odd shapes ever see; the ops wrappers pad first).
    """
    assert dim >= 1, dim
    if dim <= block:
        return dim
    if dim % block == 0:
        return block
    return math.gcd(dim, block)


def check_sparse_lowers(interpret: bool, kernel: str, primitive: str):
    """The sparse bodies run only under the Pallas interpreter: Mosaic
    (the TPU compiler) refuses ``primitive`` inside a kernel. Raise before
    lowering rather than fail inside it."""
    if not interpret:
        raise ValueError(
            f"{kernel}: method='sparse' uses {primitive} inside the kernel, "
            "which Mosaic does not lower; on the TPU use method='auto' or "
            "'reference' (the expansion body)")


def scatter_table(ids, vals, height: int):
    """Fibers -> transposed dense table ``(height, n_fibers)``.

    ``ids``/``vals`` are ``(f, cap)`` with ids indexing ``[0, height)``;
    entry ``c`` of fiber ``f`` lands at ``[ids[f, c], f]``. PAD_ID rows
    scatter into a discard row. One masked scatter-add — cost ∝ the
    entries scanned, not the dense table size. The transposed layout makes
    the table directly contractable (``A_tile @ table``) and gatherable by
    row (``table[id, :]``) without materialising a transpose.
    """
    f = ids.shape[0]
    safe = jnp.where(ids >= 0, ids, height)
    cols = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 0)
    full = jnp.zeros((height + 1, f), jnp.float32)
    full = full.at[safe.reshape(-1), cols.reshape(-1)].add(
        vals.astype(jnp.float32).reshape(-1))
    return full[:height]


def scatter_rows(ids, vals, base, width: int):
    """Fibers -> dense ``(n_fibers, width)`` rows over the minor window
    ``[base, base + width)``; coordinates outside the window (including
    PAD_ID) are discarded. The row-layout sibling of
    :func:`scatter_table`, used where fibers stay rows (the outer
    product's K-major tables, Gustavson's windowed A table)."""
    rel = ids - base
    ok = (ids >= 0) & (rel >= 0) & (rel < width)
    safe = jnp.where(ok, rel, width)
    rows = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 0)
    full = jnp.zeros((ids.shape[0], width + 1), jnp.float32)
    full = full.at[rows.reshape(-1), safe.reshape(-1)].add(
        jnp.where(ok, vals.astype(jnp.float32), 0).reshape(-1))
    return full[:, :width]


def gather_contract(table, ids, vals):
    """``out[f, :] = Σ_c vals[f, c] · table[ids[f, c], :]`` — gather table
    rows at the fiber coordinates, then contract the capacity chunk away in
    one batched MXU ``dot_general`` (batch = fibers, contract = cap chunk).

    This is the sparsity-proportional contraction: FLOPs and gather volume
    are ``f × cap_chunk × table_width`` — proportional to the (chunked)
    nonzero count, not the dense K bound. PAD_ID coordinates clamp to row 0
    and contribute nothing because their values are zero.
    """
    f, c = ids.shape
    g = jnp.take(table, jnp.maximum(ids, 0).reshape(-1), axis=0)
    g = g.reshape(f, c, table.shape[1])
    return jax.lax.dot_general(
        vals.astype(jnp.float32)[:, None, :], g,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )[:, 0]


def chunked_gather_contract(table, ids_ref, vals_ref, n_chunks, fc: int,
                            out_rows: int):
    """Accumulate :func:`gather_contract` over the live capacity chunks of
    a fiber block, **in register** (the ``fori_loop`` carry) — no scratch
    round trips, no grid dimension, and trip count = the scalar-prefetched
    live-chunk bound ``n_chunks`` (dynamic), so dead chunks cost nothing.
    """
    def body(cc, acc):
        ids = jax.lax.dynamic_slice(
            ids_ref[...], (0, cc * fc), (ids_ref.shape[0], fc))
        vals = jax.lax.dynamic_slice(
            vals_ref[...], (0, cc * fc), (vals_ref.shape[0], fc))
        return acc + gather_contract(table, ids, vals)

    return jax.lax.fori_loop(
        0, n_chunks, body,
        jnp.zeros((out_rows, table.shape[1]), jnp.float32))
