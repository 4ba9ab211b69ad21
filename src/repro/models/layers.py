"""Model building blocks: norms, RoPE, GQA attention (chunked-flash,
sliding-window, decode, context-parallel decode), MLPs, embeddings.

Everything is functional: ``init_*`` returns param dicts, ``*_apply`` maps
(params, activations) -> activations. Sharding is expressed with
``with_sharding_constraint`` against logical axes carried by :class:`Axes`;
with ``axes=None`` (CPU unit tests) models run unconstrained.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------- sharding
@dataclasses.dataclass(frozen=True)
class Axes:
    """Logical -> physical mesh axis mapping (DESIGN.md §6)."""

    batch: Tuple[str, ...] = ("data",)    # ("pod", "data") on multi-pod
    model: str = "model"                  # TP / EP / vocab axis
    fsdp: str = "data"                    # param/optimizer shard axis
    seq: Optional[str] = None             # context-parallel axis for caches
    sizes: Optional[Tuple[Tuple[str, int], ...]] = None   # mesh axis sizes

    def tp(self, dim: int) -> Optional[str]:
        """'model' iff dim divides the TP degree (sharding/specs.py rule)."""
        size = dict(self.sizes or ()).get(self.model, 1)
        return self.model if size > 1 and dim % size == 0 else None


def sc(x, axes: Optional[Axes], *spec):
    """Sharding constraint when running under a mesh; no-op otherwise."""
    if axes is None:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


import functools

from jax.ad_checkpoint import checkpoint_name as _checkpoint_name


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _uw_vjp(w, use_spec: P, stored_spec: P):
    return jax.lax.with_sharding_constraint(w, use_spec)


def _uw_fwd(w, use_spec, stored_spec):
    return jax.lax.with_sharding_constraint(w, use_spec), None


def _uw_bwd(use_spec, stored_spec, _, g):
    # Constrain the weight cotangent straight to the STORED (fsdp-sharded)
    # layout: SPMD then emits a reduce-scatter for the gradient instead of
    # a full all-reduce followed by a slice (§Perf hillclimb).
    return (jax.lax.with_sharding_constraint(g, stored_spec),)


_uw_vjp.defvjp(_uw_fwd, _uw_bwd)


def uw(w, axes: Optional[Axes], *spec, fsdp_dim: Optional[int] = None):
    """Unshard-at-use for an FSDP-stored weight (EXPERIMENTS.md §Perf
    hillclimb): weights live sharded over the fsdp axis, but a contraction
    against a weight dim sharded over `data` makes SPMD partial-sum the
    *activations* (huge all-reduces). Constraining the weight to its
    TP-only layout right before use forces the canonical cheap weight
    all-gather instead; the custom VJP routes the weight gradient back as
    a reduce-scatter onto the stored layout."""
    if axes is None:
        return w
    use_spec = P(*spec)
    if fsdp_dim is None:
        return jax.lax.with_sharding_constraint(w, use_spec)
    fsize = dict(axes.sizes or ()).get(axes.fsdp, 1)
    stored = list(spec) + [None] * (w.ndim - len(spec))
    if fsize > 1 and w.shape[fsdp_dim] % fsize == 0 \
            and stored[fsdp_dim] is None:
        stored[fsdp_dim] = axes.fsdp
    return _uw_vjp(w, use_spec, P(*stored))


def batch_spec(axes: Optional[Axes]):
    return axes.batch if axes else None


# ------------------------------------------------------------------- utils
def dense_init(key, in_dim: int, out_dims, dtype) -> jnp.ndarray:
    shape = (in_dim, *out_dims) if isinstance(out_dims, tuple) else (in_dim, out_dims)
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def rmsnorm_init(dim: int, dtype) -> jnp.ndarray:
    return jnp.ones((dim,), dtype)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


# -------------------------------------------------------------------- RoPE
def rope_angles(positions: jnp.ndarray, d_head: int, theta: float):
    """positions (...,) -> (cos, sin) of shape (..., d_head/2)."""
    half = d_head // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x (..., S, H, dh); cos/sin (..., S, dh/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# --------------------------------------------------------------- attention
def init_attention(key, cfg, dtype) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (h, dh), dtype),
        "wk": dense_init(ks[1], d, (kv, dh), dtype),
        "wv": dense_init(ks[2], d, (kv, dh), dtype),
        "wo": dense_init(ks[3], h * dh, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((kv, dh), dtype)
        p["bv"] = jnp.zeros((kv, dh), dtype)
    return p


def qkv_project(p: dict, x: jnp.ndarray, cfg, axes: Optional[Axes]):
    """x (B, S, D) -> q (B, S, H, dh), k/v (B, S, KV, dh)."""
    h_ax = axes.tp(cfg.n_heads) if axes else None
    kv_ax = axes.tp(cfg.n_kv_heads) if axes else None
    q = jnp.einsum("bsd,dhe->bshe", x, uw(p["wq"], axes, None, h_ax, None, fsdp_dim=0))
    k = jnp.einsum("bsd,dhe->bshe", x, uw(p["wk"], axes, None, kv_ax, None, fsdp_dim=0))
    v = jnp.einsum("bsd,dhe->bshe", x, uw(p["wv"], axes, None, kv_ax, None, fsdp_dim=0))
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if axes:
        q = sc(q, axes, axes.batch, None, h_ax, None)
    return q, k, v


def _pick_chunk(sk: int, want: int) -> Optional[int]:
    """Largest power-of-two-ish divisor of sk ≤ want (flash needs even
    chunking); None if sk has no usable divisor."""
    c = min(want, sk)
    while c > 1 and sk % c:
        c //= 2
    return c if sk % c == 0 else None


def _flash_chunked(q, k, v, mask_fn, chunk: int, softmax_scale: float):
    """Flash attention via lax.scan over KV chunks (never materialises the
    full S×S score matrix — required for prefill_32k memory feasibility).

    q: (B, Sq, KV, G, dh) grouped queries; k/v: (B, Sk, KV, dh).
    mask_fn(q_pos (Sq,), k_pos (Ck,)) -> bool (Sq, Ck) additive mask.
    """
    b, sq, kvh, g, dh = q.shape
    sk = k.shape[1]
    chunk = min(chunk, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, kvh, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kvh, dh).transpose(1, 0, 2, 3, 4)
    q32 = q.astype(jnp.float32) * softmax_scale
    q_pos = jnp.arange(sq)

    def body(carry, inp):
        m, l, o = carry
        ci, k_i, v_i = inp
        k_pos = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgd,bckd->bqkgc", q32, k_i.astype(jnp.float32))
        mask = mask_fn(q_pos, k_pos)                        # (Sq, Ck)
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + pexp.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", pexp, v_i.astype(jnp.float32))
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, sq, kvh, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, sq, kvh, g), jnp.float32)
    o0 = jnp.zeros((b, sq, kvh, g, dh), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        body, (m0, l0, o0), (jnp.arange(n_chunks), kc, vc))
    return o / jnp.maximum(l, 1e-30)[..., None]


def attention(
    p: dict,
    x: jnp.ndarray,
    cfg,
    axes: Optional[Axes],
    *,
    positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    window: Optional[int] = None,
    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> jnp.ndarray:
    """Multi-head GQA attention over a full sequence (train / prefill).

    ``window`` enables sliding-window masking (local layers);
    ``kv_override`` supplies external K/V (cross-attention) — no RoPE is
    applied to overridden KV and causality is disabled.
    """
    b, s, d = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q, k, v = qkv_project(p, x, cfg, axes)
    if kv_override is None:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        cos, sin = rope_angles(positions, dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    else:
        k, v = kv_override
        causal = False
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, dh)

    # Sequence-sharded attention for archs whose head count doesn't divide
    # the TP degree (gemma3 4H, llama 24H, ...): the model axis carries the
    # query-sequence dim instead. Entering costs nothing (q is replicated
    # over 'model' here — the constraint is a local slice); leaving costs
    # one (B,S,D) all-gather at the output projection. Without this, SPMD
    # either replicates attention over 'model' (16× compute/memory) or
    # shards the contraction dim and all-reduces every score tensor
    # (§Perf hillclimb, gemma3 iteration 2).
    h_ax = axes.tp(h) if axes else None
    tp_size = dict(axes.sizes or ()).get(axes.model, 1) if axes else 1
    seq_shard = (axes is not None and h_ax is None and tp_size > 1
                 and s % tp_size == 0)
    if seq_shard:
        qg = sc(qg, axes, axes.batch, axes.model, None, None, None)

    sk = k.shape[1]
    chunk = _pick_chunk(sk, cfg.attn_chunk)
    if cfg.attn_impl == "flash_vjp" and chunk is not None:
        from repro.models.flash import flash_attention

        o = flash_attention(qg, k, v, causal, window, chunk,
                            1.0 / math.sqrt(dh))
        o = o.astype(jnp.float32)
    else:
        def mask_fn(q_pos, k_pos):
            ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
            if causal:
                ok &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                ok &= q_pos[:, None] - k_pos[None, :] < window
            return ok

        o = _flash_chunked(qg, k, v, mask_fn, cfg.attn_chunk,
                           1.0 / math.sqrt(dh))
    o = o.reshape(b, s, h, dh).astype(x.dtype)
    h_ax = axes.tp(h) if axes else None
    wo = uw(p["wo"], axes, h_ax, None, fsdp_dim=1).reshape(h, dh, d)
    out = jnp.einsum("bshe,hed->bsd", o, wo)
    out = sc(out, axes, axes.batch if axes else None, None, None)
    # Named so remat="block_save" keeps this post-all-gather tensor instead
    # of re-running the attention (and its seq-shard exit AG) in backward.
    return _checkpoint_name(out, "attn_out")


def decode_attention(
    p: dict,
    x: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,
    cfg,
    axes: Optional[Axes],
    *,
    window: Optional[int] = None,
    cross: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token attention against a KV cache.

    x (B, 1, D); caches (B, S_max, KV, dh); pos (B,) current positions.
    Returns (out, new_k_cache, new_v_cache). With ``axes.seq`` set, the
    cache is sequence-sharded and the softmax is combined across the
    context-parallel axis with an exact flash merge (DESIGN.md §6).
    """
    b, _, d = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    s_max = k_cache.shape[1]
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    if not cross:
        k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
        v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
        if cfg.qkv_bias:
            k, v = k + p["bk"], v + p["bv"]
        cos, sin = rope_angles(pos[:, None], dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        k_cache = _cache_insert(k_cache, k, pos)
        v_cache = _cache_insert(v_cache, v, pos)
    g = h // kvh
    qg = q.reshape(b, kvh, g, dh)

    # Cross-attention reads the whole (prefilled) encoder cache.
    attend_pos = jnp.full_like(pos, s_max) if cross else pos
    if axes is not None and axes.seq is not None and not cross:
        out = _cp_decode_attend(qg, k_cache, v_cache, attend_pos, window, dh,
                                axes)
    else:
        out = _decode_attend(qg, k_cache, v_cache, attend_pos, window, dh,
                             jnp.arange(s_max))
    o = out.reshape(b, 1, h * dh).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", o, p["wo"].reshape(h * dh, d)), k_cache, v_cache


def _cache_insert(cache: jnp.ndarray, kv: jnp.ndarray, pos: jnp.ndarray):
    """Insert (B, 1, KV, dh) at per-batch position ``pos`` (B,) via a
    batched dynamic-update-slice (touches one row, not the whole cache)."""
    return jax.vmap(
        lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0, 0))
    )(cache, kv.astype(cache.dtype), pos)


def _decode_attend(qg, k_cache, v_cache, pos, window, dh, k_positions):
    """qg (B, KV, G, dh) vs cache (B, S, KV, dh) -> (B, KV, G, dh)."""
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32) * scale,
                   k_cache.astype(jnp.float32))
    valid = k_positions[None, :] <= pos[:, None]
    if window is not None:
        valid &= k_positions[None, :] > pos[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p_ = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgs,bskd->bkgd", p_, v_cache.astype(jnp.float32))


def _cp_decode_attend(qg, k_cache, v_cache, pos, window, dh, axes: Axes):
    """Context-parallel decode: cache sequence dim sharded over axes.seq;
    exact softmax via (max, sum) psum flash-combine."""
    seq_ax = axes.seq
    mesh = jax.sharding.get_abstract_mesh()
    n_shards = mesh.shape[seq_ax]
    s_shard = k_cache.shape[1] // n_shards
    scale = 1.0 / math.sqrt(dh)

    def local(qg_, kc, vc, pos_):
        idx = jax.lax.axis_index(seq_ax)
        k_positions = idx * s_shard + jnp.arange(s_shard)
        s = jnp.einsum("bkgd,bskd->bkgs", qg_.astype(jnp.float32) * scale,
                       kc.astype(jnp.float32))
        valid = k_positions[None, :] <= pos_[:, None]
        if window is not None:
            valid &= k_positions[None, :] > pos_[:, None] - window
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        m_loc = s.max(axis=-1)
        p_ = jnp.exp(s - m_loc[..., None])
        l_loc = p_.sum(axis=-1)
        o_loc = jnp.einsum("bkgs,bskd->bkgd", p_, vc.astype(jnp.float32))
        m = jax.lax.pmax(m_loc, seq_ax)
        corr = jnp.exp(m_loc - m)
        l = jax.lax.psum(l_loc * corr, seq_ax)
        o = jax.lax.psum(o_loc * corr[..., None], seq_ax)
        return o / jnp.maximum(l, 1e-30)[..., None]

    spec_cache = P(None, seq_ax, None, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), spec_cache, spec_cache, P()),
        out_specs=P(),
        check_vma=False,
    )(qg, k_cache, v_cache, pos)


# -------------------------------------------------------------------- MLP
def init_mlp(key, cfg, dtype, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "silu":
        return {
            "wi": dense_init(ks[0], d, f, dtype),
            "wg": dense_init(ks[1], d, f, dtype),
            "wo": dense_init(ks[2], f, d, dtype),
        }
    return {
        "wi": dense_init(ks[0], d, f, dtype),
        "wo": dense_init(ks[2], f, d, dtype),
    }


def mlp(p: dict, x: jnp.ndarray, cfg, axes: Optional[Axes]) -> jnp.ndarray:
    f_ax = axes.tp(p["wi"].shape[-1]) if axes else None
    h = jnp.einsum("bsd,df->bsf", x, uw(p["wi"], axes, None, f_ax, fsdp_dim=0))
    if cfg.act == "silu":
        g = jnp.einsum("bsd,df->bsf", x, uw(p["wg"], axes, None, f_ax, fsdp_dim=0))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = sc(h, axes, axes.batch if axes else None, None, f_ax)
    return jnp.einsum("bsf,fd->bsd", h, uw(p["wo"], axes, f_ax, None, fsdp_dim=1))


# -------------------------------------------------------------- embeddings
def init_embedding(key, cfg, dtype) -> dict:
    p = {"tok": (jax.random.normal(key, (cfg.vocab_size, cfg.d_model)) * 0.02
                 ).astype(dtype)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(jax.random.fold_in(key, 1), cfg.d_model,
                               cfg.vocab_size, dtype)
    return p


def embed(p: dict, tokens: jnp.ndarray, cfg, axes: Optional[Axes]):
    x = p["tok"][tokens] * math.sqrt(cfg.d_model)
    return sc(x, axes, axes.batch if axes else None, None, None)


def logits(p: dict, x: jnp.ndarray, cfg, axes: Optional[Axes]):
    table = p["tok"] if cfg.tie_embeddings else p["head"].T
    v_ax = axes.tp(table.shape[0]) if axes else None
    table = uw(table, axes, v_ax, None, fsdp_dim=1)
    out = jnp.einsum("bsd,vd->bsv", x, table.astype(x.dtype))
    return sc(out, axes, axes.batch if axes else None, None, v_ax)
