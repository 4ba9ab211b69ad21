"""Shared vectorized one-hot expansion — the single decompression primitive
behind every sparse dataflow kernel (DESIGN.md §2).

Every sub-accelerator class needs the same move: turn ``(fibers, cap)``
compressed coordinates/values into a dense ``(fibers, width)`` tile
restricted to a minor-coordinate window ``[base, base + width)``, so the MXU
can contract it. The seed kernels each re-implemented this as a
``jax.lax.fori_loop`` over ``cap`` — O(cap) *sequential* VPU steps per tile.

Two vectorized lowerings, both loop-free:

* ``method="dot"`` — the Mosaic/TPU idiom: build the 3-D windowed one-hot
  mask ``onehot[f, c, w] = (ids[f, c] - base == w)`` and contract it with
  the values along ``c`` in a single batched ``dot_general``. TPUs have no
  scatter datapath, so the MXU performs the scatter. For large caps the
  mask would be (fibers × cap × width) floats of VMEM, so it is chunked
  (``chunk``, default :data:`DEFAULT_CHUNK`) and statically unrolled: each
  chunk is still a full-width contraction — bounded memory, no per-nonzero
  loop.
* ``method="gather"`` — the interpreter/CPU lowering: ELL ids are sorted
  within each fiber, so a batched binary search (``searchsorted``) finds,
  for every output column, the position of its (unique) source nonzero;
  one ``take_along_axis`` gather plus a hit mask finishes the job. No
  scatter (XLA CPU scatters serially), no 3-D mask — every op is a wide
  vectorized primitive. Mosaic cannot lower it, CPUs love it.
* ``method="scatter"`` — one masked ``scatter-add`` of the values at their
  windowed coordinates; kept as the reference lowering for backends where
  neither of the above wins.

``method="auto"`` picks per backend (TPU -> dot, else gather). All
lowerings are bit-identical: coordinates are unique within a fiber, so
every output element receives at most one contribution, and padded ids
(``PAD_ID``) never match the window — the "invalid computation never
scheduled" property of the index-match hardware being modelled.

These lowerings serve the per-tile expansion bodies, which only the
interpreter runs. Under Mosaic the expansion body is
:func:`expansion_gemm`: each compressed operand is expanded once into a
dense HBM operand by the :func:`expand_table_pallas` kernel, then
contracted by the gemm kernel (DESIGN.md §7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.formats.ell import PAD_ID, EllMatrix, pad_capacity
from repro.kernels.gemm import gemm_pallas

#: Max one-hot contraction depth per dot_general (method="dot"). Bounds the
#: 3-D mask to (fibers × DEFAULT_CHUNK × width) elements of VMEM.
DEFAULT_CHUNK = 128


def _expand_dot_chunk(ids, vals, base, width: int, out_dtype):
    """One fully-vectorized MXU contraction over a whole cap chunk."""
    rel = ids - base                                      # (f, c) window coords
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, width), 2)
    onehot = (rel[:, :, None] == iota).astype(out_dtype)  # (f, c, width)
    # out[f, w] = Σ_c vals[f, c] · onehot[f, c, w]: batched over f, the MXU
    # contracts away cap in one shot.
    out = jax.lax.dot_general(
        vals.astype(out_dtype)[:, None, :], onehot,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=out_dtype,
    )
    return out[:, 0, :]


def _expand_dot(ids, vals, base, width: int, out_dtype, chunk: int):
    cap = ids.shape[1]
    if cap <= chunk:
        return _expand_dot_chunk(ids, vals, base, width, out_dtype)
    # Static unroll over cap chunks: bounded VMEM, still no sequential
    # per-nonzero loop.
    out = _expand_dot_chunk(ids[:, :chunk], vals[:, :chunk], base, width,
                            out_dtype)
    for c0 in range(chunk, cap, chunk):
        out = out + _expand_dot_chunk(ids[:, c0:c0 + chunk],
                                      vals[:, c0:c0 + chunk],
                                      base, width, out_dtype)
    return out


def _expand_gather(ids, vals, base, width: int, out_dtype):
    """Batched binary search + gather — the CPU/interpreter lowering.

    Relies on the EllMatrix invariant that each fiber's live ids are
    strictly ascending with PAD_ID (-1) padding at the tail; remapping
    pads to int32::max keeps the whole row sorted.
    """
    nf, cap = ids.shape
    big = jnp.iinfo(jnp.int32).max
    sorted_ids = jnp.where(ids < 0, big, ids)
    targets = base + jax.lax.broadcasted_iota(jnp.int32, (nf, width), 1)
    pos = jax.vmap(jnp.searchsorted)(sorted_ids, targets)
    pos = jnp.minimum(pos, cap - 1)
    hit = jnp.take_along_axis(sorted_ids, pos, axis=1) == targets
    gathered = jnp.take_along_axis(vals, pos, axis=1)
    return jnp.where(hit, gathered, 0).astype(out_dtype)


def _expand_scatter(ids, vals, base, width: int, out_dtype):
    """One masked scatter-add — the CPU/interpreter lowering."""
    nf = ids.shape[0]
    rel = ids - base
    in_window = (rel >= 0) & (rel < width)
    safe = jnp.where(in_window, rel, width)     # out-of-window -> discard col
    rows = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 0)
    out = jnp.zeros((nf, width + 1), out_dtype)
    out = out.at[rows, safe].add(
        jnp.where(in_window, vals, 0).astype(out_dtype))
    return out[:, :width]


def expand_minor(ids, vals, base, width: int, out_dtype=jnp.float32,
                 *, chunk: int = DEFAULT_CHUNK, method: str = "auto"):
    """Expand ``(f, cap)`` compressed fibers to a dense ``(f, width)`` tile
    over minor coordinates ``[base, base + width)``.

    ``base`` may be traced (e.g. ``program_id * block``); ``width``, ``cap``
    and ``chunk`` are static. Coordinates outside the window — including
    ``PAD_ID`` padding — contribute nothing. ``method`` selects the
    lowering (module docstring); ``"auto"`` uses the MXU one-hot
    contraction on TPU and the gather lowering everywhere else. NOTE:
    ``"gather"`` requires each fiber's live ids to be strictly ascending
    (the :class:`~repro.formats.ell.EllMatrix` invariant); for hand-built,
    possibly unsorted ids use ``"dot"`` or ``"scatter"``, which accept any
    order.
    """
    assert ids.ndim == 2 and vals.shape == ids.shape, (ids.shape, vals.shape)
    if method == "auto":
        method = "dot" if jax.default_backend() == "tpu" else "gather"
    if method == "dot":
        return _expand_dot(ids, vals, base, width, out_dtype, chunk)
    if method == "gather":
        return _expand_gather(ids, vals, base, width, out_dtype)
    if method == "scatter":
        return _expand_scatter(ids, vals, base, width, out_dtype)
    raise ValueError(f"unknown expansion method: {method!r}")


def expand_major(ids, vals, base, height: int, out_dtype=jnp.float32,
                 *, chunk: int = DEFAULT_CHUNK, method: str = "auto"):
    """Like :func:`expand_minor` but returns the transposed ``(height, f)``
    layout — fibers become columns (the SpMM weight-tile orientation)."""
    return expand_minor(ids, vals, base, height, out_dtype,
                        chunk=chunk, method=method).T


# ------------------------------------------------ Mosaic expansion body
#: Tiles of :func:`expand_table_pallas`: output ``(EXPAND_BLOCK, 128)``
#: (minor coordinates × fibers), capacity slots per grid step.
EXPAND_BLOCK = 128
EXPAND_FIBERS = 128
EXPAND_SLOTS = 256


def _expand_table_kernel(ids_ref, vals_ref, o_ref, *, bw: int, cc: int):
    w, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Live ids ascend within a fiber from slot 0, so slot s holds an id
    # >= s: capacity chunks starting past this window's end are empty here.
    @pl.when(c * cc < (w + 1) * bw)
    def _accumulate():
        rows = w * bw + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)

        def group(g, acc):
            s0 = pl.multiple_of(g * 8, 8)
            ids8 = ids_ref[pl.ds(s0, 8), :]
            vals8 = vals_ref[pl.ds(s0, 8), :].astype(jnp.float32)
            for r in range(8):   # one capacity slot per (bw, bf) select
                acc = acc + jnp.where(rows == ids8[r:r + 1, :],
                                      vals8[r:r + 1, :], 0.0)
            return acc

        o_ref[...] += jax.lax.fori_loop(
            0, cc // 8, group, jnp.zeros(o_ref.shape, jnp.float32))


def expand_table_pallas(e, *, rows: int, cols: int,
                        interpret: bool = False) -> jnp.ndarray:
    """Expand ``e``'s fibers into the f32 table ``(rows, cols)``: entry
    ``[id, f]`` holds fiber ``f``'s value at minor coordinate ``id``, and
    the padding beyond ``e.minor_size`` × ``e.n_fibers`` is zero. For column
    fibers that table is the dense matrix, for row fibers its transpose.

    The Mosaic lowering of the expansion bodies (DESIGN.md §7): no scatter
    and no gather — every capacity slot is one vector compare-and-select
    over a ``(EXPAND_BLOCK, 128)`` output tile, accumulated in VMEM over a
    capacity grid axis. ``rows`` and ``cols`` must be multiples of
    :data:`EXPAND_BLOCK` and :data:`EXPAND_FIBERS`.
    """
    bw, bf = EXPAND_BLOCK, EXPAND_FIBERS
    nf = e.n_fibers
    assert rows % bw == 0 and cols % bf == 0, (rows, cols)
    assert rows >= e.minor_size and cols >= nf, (rows, cols, e.shape)
    cc = min(EXPAND_SLOTS, pl.cdiv(e.cap, 8) * 8)
    e = pad_capacity(e, pl.cdiv(e.cap, cc) * cc)
    # Capacity-major layout: one slot across 128 fibers is one lane row.
    ids = jnp.pad(e.ids.T, ((0, 0), (0, cols - nf)), constant_values=PAD_ID)
    vals = jnp.pad(e.vals.T, ((0, 0), (0, cols - nf)))
    kernel = functools.partial(_expand_table_kernel, bw=bw, cc=cc)
    return pl.pallas_call(
        kernel,
        grid=(rows // bw, cols // bf, e.cap // cc),
        in_specs=[
            pl.BlockSpec((cc, bf), lambda w, f, c: (c, f)),
            pl.BlockSpec((cc, bf), lambda w, f, c: (c, f)),
        ],
        out_specs=pl.BlockSpec((bw, bf), lambda w, f, c: (w, f)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        interpret=interpret,
        name="expand_table",
    )(ids, vals)


def _dense_operand(x, rows: int, cols: int, interpret: bool):
    """``x`` (dense or :class:`EllMatrix`) as a zero-padded dense
    ``(rows, cols)`` array in its own dtype."""
    if not isinstance(x, EllMatrix):
        return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))
    if x.major_axis == 1:
        t = expand_table_pallas(x, rows=rows, cols=cols, interpret=interpret)
    else:
        t = expand_table_pallas(x, rows=cols, cols=rows,
                                interpret=interpret).T
    return t.astype(x.vals.dtype)


def expansion_gemm(a, b, *, bm: int, bn: int, bk: int = EXPAND_BLOCK,
                   interpret: bool = False) -> jnp.ndarray:
    """``a @ b`` for any mix of dense and compressed operands through the
    expansion body as Mosaic runs it: each compressed operand is expanded
    ONCE by :func:`expand_table_pallas`, then the output-stationary
    :func:`repro.kernels.gemm.gemm_pallas` contracts the two on the MXU.

    This is the body ``method="auto"`` takes on the TPU for every sparse
    class. The per-tile expansion bodies of the class kernels re-expand an
    operand for every output tile and hold whole fibers in VMEM, which the
    published Table-I sizes overflow; here VMEM holds fixed tiles and the
    expansion cost is paid once per call.
    """
    m, k = a.shape
    n = b.shape[1]
    mp, kp, np_ = (pl.cdiv(d, EXPAND_BLOCK) * EXPAND_BLOCK
                   for d in (m, k, n))
    ad = _dense_operand(a, mp, kp, interpret)
    bd = _dense_operand(b, kp, np_, interpret)
    # Padded dims are multiples of EXPAND_BLOCK: blocks below it would only
    # multiply grid steps (and break Mosaic's 128-lane tiling).
    bm, bn, bk = (max(x, EXPAND_BLOCK) for x in (bm, bn, bk))
    out = gemm_pallas(ad, bd, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]
