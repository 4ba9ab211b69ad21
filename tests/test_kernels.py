"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp ref oracles,
shape/dtype sweeps, and hypothesis properties. The oracles themselves are
cross-checked against plain dense matmul first.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev extra; stub keeps property tests running
    from _hypothesis_compat import given, settings, strategies as st

from repro import formats as F
from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def random_sparse(rng, m, n, density, dtype=np.float32):
    d = rng.standard_normal((m, n)).astype(np.float32)
    mask = rng.random((m, n)) < density
    return (d * mask).astype(dtype)


def make_operands(rng, m, k, n, da, db, dtype=np.float32):
    a = random_sparse(rng, m, k, da, dtype)
    b = random_sparse(rng, k, n, db, dtype)
    return jnp.asarray(a), jnp.asarray(b)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- oracle self-checks
@pytest.mark.parametrize("da,db", [(1.0, 1.0), (0.3, 1.0), (0.3, 0.4), (0.05, 0.05)])
def test_refs_agree_with_dense_matmul(da, db):
    rng = np.random.default_rng(0)
    a, b = make_operands(rng, 24, 40, 32, da, db)
    want = np.asarray(a) @ np.asarray(b)

    a_umck = F.dense_to_ell(a, 0, 40)
    a_ukcm = F.dense_to_ell(a, 1, 24)
    b_unck = F.dense_to_ell(b, 1, 40)
    b_ukcn = F.dense_to_ell(b, 0, 32)

    np.testing.assert_allclose(ref.gemm_ref(a, b), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ref.spmm_ref(a, b_unck), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ref.spmm_mirror_ref(a_umck, b), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ref.spgemm_inner_ref(a_umck, b_unck), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ref.spgemm_outer_ref(a_ukcm, b_ukcn), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ref.spgemm_gustavson_ref(a_ukcm, b_unck), want, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ pallas kernels
SHAPES = [
    (128, 128, 128),   # single block
    (256, 128, 384),   # multi-block in M and K
    (100, 90, 70),     # ragged: exercises padding
    (128, 300, 256),   # ragged K
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_gemm_pallas(shape, dtype):
    m, k, n = shape
    rng = np.random.default_rng(1)
    a, b = make_operands(rng, m, k, n, 1.0, 1.0, dtype)
    got = ops.gemm(a, b, interpret=True)
    want = ref.gemm_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_spmm_pallas(shape, dtype):
    m, k, n = shape
    rng = np.random.default_rng(2)
    a, b = make_operands(rng, m, k, n, 1.0, 0.25, dtype)
    b_ell = F.dense_to_ell(b, 1, F.required_capacity(b, 1))
    got = ops.spmm(a, b_ell, interpret=True)
    want = ref.spmm_ref(a, b_ell)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
def test_spmm_mirror_pallas(dtype):
    rng = np.random.default_rng(3)
    a, b = make_operands(rng, 96, 128, 64, 0.3, 1.0, dtype)
    a_ell = F.dense_to_ell(a, 0, F.required_capacity(a, 0))
    got = ops.spmm_mirror(a_ell, b, interpret=True)
    want = ref.spmm_mirror_ref(a_ell, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_spgemm_inner_pallas(shape, dtype):
    m, k, n = shape
    rng = np.random.default_rng(4)
    a, b = make_operands(rng, m, k, n, 0.2, 0.3, dtype)
    a_ell = F.dense_to_ell(a, 0, F.required_capacity(a, 0))
    b_ell = F.dense_to_ell(b, 1, F.required_capacity(b, 1))
    got = ops.spgemm_inner(a_ell, b_ell, interpret=True)
    want = ref.spgemm_inner_ref(a_ell, b_ell)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_spgemm_outer_pallas(shape, dtype):
    m, k, n = shape
    rng = np.random.default_rng(5)
    a, b = make_operands(rng, m, k, n, 0.2, 0.3, dtype)
    a_ell = F.dense_to_ell(a, 1, F.required_capacity(a, 1))
    b_ell = F.dense_to_ell(b, 0, F.required_capacity(b, 0))
    got = ops.spgemm_outer(a_ell, b_ell, interpret=True)
    want = ref.spgemm_outer_ref(a_ell, b_ell)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_spgemm_gustavson_pallas(shape, dtype):
    m, k, n = shape
    rng = np.random.default_rng(6)
    a, b = make_operands(rng, m, k, n, 0.2, 0.3, dtype)
    a_ell = F.dense_to_ell(a, 1, F.required_capacity(a, 1))
    b_ell = F.dense_to_ell(b, 1, F.required_capacity(b, 1))
    got = ops.spgemm_gustavson(a_ell, b_ell, interpret=True)
    want = ref.spgemm_gustavson_ref(a_ell, b_ell)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# ------------------------------------------------------------ degenerate
def test_all_kernels_zero_matrices():
    z = jnp.zeros((128, 128), jnp.float32)
    ze_r = F.dense_to_ell(z, 0, 8)
    ze_c = F.dense_to_ell(z, 1, 8)
    assert not np.asarray(ops.gemm(z, z, interpret=True)).any()
    assert not np.asarray(ops.spmm(z, ze_c, interpret=True)).any()
    assert not np.asarray(ops.spgemm_inner(ze_r, ze_c, interpret=True)).any()
    assert not np.asarray(ops.spgemm_outer(ze_c, ze_r, interpret=True)).any()
    assert not np.asarray(ops.spgemm_gustavson(ze_c, ze_c, interpret=True)).any()


def test_dispatch_table_covers_all_classes():
    assert set(ops.DISPATCH) == set(F.DataflowClass)


# ------------------------------------------------------------ property
@settings(max_examples=8, deadline=None)
@given(
    m=st.sampled_from([64, 128]),
    k=st.sampled_from([64, 128, 200]),
    n=st.sampled_from([64, 128]),
    da=st.floats(0.05, 0.9),
    db=st.floats(0.05, 0.9),
    seed=st.integers(0, 2**16),
)
def test_prop_spgemm_kernels_match_dense(m, k, n, da, db, seed):
    """Property: every sparse dataflow class computes the same matmul."""
    rng = np.random.default_rng(seed)
    a, b = make_operands(rng, m, k, n, da, db)
    want = np.asarray(a) @ np.asarray(b)
    a_umck = F.dense_to_ell(a, 0, F.required_capacity(a, 0))
    a_ukcm = F.dense_to_ell(a, 1, F.required_capacity(a, 1))
    b_unck = F.dense_to_ell(b, 1, F.required_capacity(b, 1))
    b_ukcn = F.dense_to_ell(b, 0, F.required_capacity(b, 0))
    kw = dict(interpret=True)
    for got in [
        ops.spmm(a, b_unck, **kw),
        ops.spgemm_inner(a_umck, b_unck, **kw),
        ops.spgemm_outer(a_ukcm, b_ukcn, **kw),
        ops.spgemm_gustavson(a_ukcm, b_unck, **kw),
    ]:
        np.testing.assert_allclose(np.asarray(got), want, rtol=5e-4, atol=5e-4)


# ----------------------------------------- sparse-vs-reference parity sweep
# The sparsity-proportional bodies must be interchangeable with the PR-1
# expansion bodies they replace: same result (allclose) for every op, dtype
# and density — including density 0 (all-skip path: every block count is 0).
SWEEP_DENSITIES = [0.0, 0.05, 0.3]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("density", SWEEP_DENSITIES)
def test_sparse_matches_reference_body(dtype, density):
    m, k, n = 128, 256, 128
    rng = np.random.default_rng(7)
    a, b = make_operands(rng, m, k, n, density, density, dtype)
    a_umck = F.dense_to_ell(a, 0, F.bucket_capacity(
        F.required_capacity(a, 0), max_cap=k))
    a_ukcm = F.dense_to_ell(a, 1, F.bucket_capacity(
        F.required_capacity(a, 1), max_cap=m))
    b_unck = F.dense_to_ell(b, 1, F.bucket_capacity(
        F.required_capacity(b, 1), max_cap=k))
    b_ukcn = F.dense_to_ell(b, 0, F.bucket_capacity(
        F.required_capacity(b, 0), max_cap=n))
    cases = [
        ("spmm", lambda mth: ops.spmm(a, b_unck, interpret=True, method=mth)),
        ("spmm_mirror",
         lambda mth: ops.spmm_mirror(a_umck, b, interpret=True, method=mth)),
        ("inner", lambda mth: ops.spgemm_inner(a_umck, b_unck,
                                               interpret=True, method=mth)),
        ("outer", lambda mth: ops.spgemm_outer(a_ukcm, b_ukcn,
                                               interpret=True, method=mth)),
        ("gustavson",
         lambda mth: ops.spgemm_gustavson(a_ukcm, b_unck,
                                          interpret=True, method=mth)),
    ]
    for name, run in cases:
        want = np.asarray(run("reference"), np.float32)
        got = np.asarray(run("sparse"), np.float32)
        np.testing.assert_allclose(got, want, err_msg=name, **tol(dtype))


# The expansion body as Mosaic runs it (kernels/expand.expansion_gemm: each
# compressed operand expanded once, then the gemm kernel), driven through
# the interpreter: every class's operand formats, ragged shapes, a fully
# dense operand (cap == minor size) and an all-zero one.
EXPANSION_FORMATS = {           # class -> (A major axis, B major axis)
    "spmm": (None, 1), "spmm_mirror": (0, None), "inner": (0, 1),
    "outer": (1, 0), "gustavson": (1, 1),
}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("cls", sorted(EXPANSION_FORMATS))
def test_mosaic_expansion_body_matches_dense(cls, dtype):
    from repro.kernels.expand import expansion_gemm

    rng = np.random.default_rng(13)
    for (m, k, n), da, db in [((100, 300, 140), 0.05, 1.0),
                              ((130, 70, 260), 1.0, 0.0)]:
        a, b = make_operands(rng, m, k, n, da, db, dtype)
        want = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
        ops_ = [x if ax is None else F.dense_to_ell(
            x, ax, F.required_capacity(x, ax), strict=True)
            for x, ax in zip((a, b), EXPANSION_FORMATS[cls])]
        got = expansion_gemm(*ops_, bm=128, bn=128, bk=128, interpret=True)
        assert got.shape == (m, n)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   **tol(dtype))


def test_sparse_kernels_fiber_at_exact_capacity():
    """A fiber holding exactly ``cap`` nonzeros fills every capacity chunk:
    the live-chunk bound equals the chunk count and nothing is skipped."""
    m, k, n = 64, 256, 64
    rng = np.random.default_rng(11)
    a = jnp.asarray(random_sparse(rng, m, k, 0.1))
    bd = np.zeros((k, n), np.float32)
    cap = 64
    rows = rng.choice(k, size=cap, replace=False)       # column 3: cap nnz
    bd[rows, 3] = rng.standard_normal(cap)
    bd[rng.choice(k, size=5, replace=False), 17] = 1.0  # a sparse column too
    b = jnp.asarray(bd)
    want = np.asarray(a) @ bd
    b_unck = F.dense_to_ell(b, 1, cap, strict=True)
    assert int(jax.device_get(b_unck.lens.max())) == cap
    got = ops.spmm(a, b_unck, interpret=True, method="sparse")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    a_umck = F.dense_to_ell(a, 0, F.required_capacity(a, 0), strict=True)
    got = ops.spgemm_inner(a_umck, b_unck, interpret=True, method="sparse")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    a_ukcm = F.dense_to_ell(a, 1, F.required_capacity(a, 1), strict=True)
    got = ops.spgemm_gustavson(a_ukcm, b_unck, interpret=True,
                               method="sparse")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_method_auto_routing():
    """`auto` picks the sparse body for sparse operands and falls back to
    the reference body when the compressed fibers approach the dense bound
    (where gather/scatter volume would exceed the expansion it replaces)."""
    from repro.kernels import spmm as spmm_mod

    m, k, n = 64, 256, 64
    rng = np.random.default_rng(3)
    dense_b = jnp.asarray(random_sparse(rng, k, n, 0.9))
    sparse_b = jnp.asarray(random_sparse(rng, k, n, 0.05))
    a = jnp.asarray(random_sparse(rng, m, k, 0.5))
    for bd in (dense_b, sparse_b):
        e = F.dense_to_ell(bd, 1, F.required_capacity(bd, 1))
        want = np.asarray(a) @ np.asarray(bd)
        got = ops.spmm(a, e, interpret=True, method="auto")
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-4)
    # Routing thresholds, checked at the entry-point level.
    dense_e = F.dense_to_ell(dense_b, 1, F.required_capacity(dense_b, 1))
    assert 2 * dense_e.cap > k          # auto -> reference for dense fibers
    sparse_e = F.dense_to_ell(sparse_b, 1, F.required_capacity(sparse_b, 1))
    assert 2 * sparse_e.cap <= k        # auto -> sparse for sparse fibers
    # Cost model mirrors the same routing (achieved-intensity hook).
    c_dense = ops.op_cost(F.DataflowClass.SPMM, a, dense_e)
    c_sparse = ops.op_cost(F.DataflowClass.SPMM, a, sparse_e)
    assert c_dense.method == "reference" and c_sparse.method == "sparse"
    assert c_sparse.flops < c_dense.flops
    assert c_sparse.intensity > 0


def test_execute_schedule_cost_sink():
    """The executor's achieved-intensity hook: one SwKernelCost per
    dispatched partition, matching the partition count and carrying
    nnz-proportional FLOPs."""
    from repro.core import costmodel as cm
    from repro.core.hetero_matmul import execute_schedule
    from repro.core.scheduler import schedule_single_kernel
    from repro.core.workloads import Workload

    rng = np.random.default_rng(5)
    m = k = n = 128
    a = jnp.asarray(random_sparse(rng, m, k, 0.1))
    b = jnp.asarray(random_sparse(rng, k, n, 0.1))
    config = cm.homogeneous_hybrid()
    sched = schedule_single_kernel(
        config, Workload("t", "test", m, k, n, 0.1, 0.1))
    sink = []
    out = execute_schedule(a, b, sched, interpret=True, cost_sink=sink)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)
    live = [p for p in sched.partitions if not p.region.empty]
    assert len(sink) == len(live)
    for c in sink:
        assert isinstance(c, cm.SwKernelCost)
        assert c.flops > 0 and c.bytes > 0 and c.mac_eq > 0
