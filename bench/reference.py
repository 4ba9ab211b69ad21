"""The plain reference and the comparison that decides ``correct``.

Every configuration states float32 matrix products at full float32
precision (``C = A @ B``, Table I of arXiv 2201.08916). The reference is
``jnp.matmul`` at ``Precision.HIGHEST`` on the benchmark's own operands;
it imports nothing of the program.

The number compared is the widest gap, over every element of every output
compared, between the served output and the reference, in units of
float32 epsilon times ``(|A| @ |B|)`` at that element:

    gap_eps = max |out - ref| / (eps * (|A| @ |B|))

Float32 accumulation in any order keeps this near 1 (each product is
rounded once and partial sums add a few roundings). An element whose
``|A| @ |B|`` is 0 must be exactly 0 in both; any difference there reads
as infinite. The control computes the same product from three bfloat16
passes (``Precision.HIGH``, written out so that it runs the same on any
backend): it drops the low-by-low term and the bits below the second
bfloat16, about 2^-16 of each product, which reads some tens of epsilon.

Outputs are compared in blocks of rows so that the reference fits beside
what the run kept.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EPS = float(jnp.finfo(jnp.float32).eps)
HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 2048


@jax.jit
def _block_gap(a_blk, b, out_blk):
    ref = jnp.matmul(a_blk, b, precision=HI)
    scale = EPS * jnp.matmul(jnp.abs(a_blk), jnp.abs(b), precision=HI)
    diff = jnp.abs(out_blk.astype(jnp.float32) - ref)
    gap = jnp.where(scale > 0, diff / jnp.where(scale > 0, scale, 1.0),
                    jnp.where(diff == 0, 0.0, jnp.inf))
    return jnp.max(gap)


def gap_eps(out, a, b, device=None) -> float:
    """Widest gap of ``out`` from ``a @ b``, in units of ``eps * |A||B|``
    (module docstring). ``a``, ``b`` and ``out`` may live on the host or
    any device; they are moved to ``device`` (default: the first)."""
    device = device or jax.devices()[0]
    if out is None:
        return float("inf")
    if tuple(out.shape) != (a.shape[0], b.shape[1]):
        return float("inf")
    out = jax.device_put(out, device)
    b_d = jax.device_put(b, device)
    worst = 0.0
    for r0 in range(0, a.shape[0], ROW_BLOCK):
        r1 = min(r0 + ROW_BLOCK, a.shape[0])
        a_blk = jax.device_put(a[r0:r1], device)
        worst = max(worst, float(_block_gap(a_blk, b_d, out[r0:r1])))
    return worst


def _split_bf16(x):
    """``x`` as two bfloat16 terms, ``hi + lo``. ``reduce_precision``
    rounds in float32 and XLA keeps it, where a round trip through
    ``astype`` may be folded away as excess precision."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


@jax.jit
def _control_block(a_blk, b):
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    a_hi, a_lo = _split_bf16(a_blk)
    b_hi, b_lo = _split_bf16(b)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def control_product(a, b, device=None):
    """The reference one precision step down: three bfloat16 passes with
    float32 accumulation (what ``Precision.HIGH`` computes on a TPU)."""
    device = device or jax.devices()[0]
    b_d = jax.device_put(b, device)
    blocks = [_control_block(jax.device_put(a[r0:r0 + ROW_BLOCK], device),
                             b_d)
              for r0 in range(0, a.shape[0], ROW_BLOCK)]
    return jnp.concatenate(blocks, axis=0)
