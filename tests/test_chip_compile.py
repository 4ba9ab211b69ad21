"""Compile the serving path's kernels for a TPU v5e that is described, not
attached: the TPU compiler is installed, so Mosaic refuses here what it
would refuse on the chip (primitives it cannot lower, kernels that
overflow VMEM) without a chip.

The shapes and ELL capacities are the partitions ``chip_smoke.py``
dispatches when it serves the Table-I requests at their published sizes
(capacities as measured on the chip). The topology is described inside a
module fixture, never at import: only one process may load the TPU
library, and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.formats.ell import EllMatrix
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _dense(shape, sharding=None):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _ell(shape, major_axis, cap, sharding=None):
    nf = shape[major_axis]
    return EllMatrix(
        vals=jax.ShapeDtypeStruct((nf, cap), jnp.float32, sharding=sharding),
        ids=jax.ShapeDtypeStruct((nf, cap), jnp.int32, sharding=sharding),
        lens=jax.ShapeDtypeStruct((nf,), jnp.int32, sharding=sharding),
        shape=shape, major_axis=major_axis)


# (request: partition, op, A spec, B spec). Specs are (shape,) for dense
# operands and (shape, major_axis, cap) for compressed ones.
SERVED = [
    ("chem97ZtZ:inner", ops.spgemm_inner,
     ((2500, 2500), 0, 16), ((2500, 1200), 1, 2504)),
    ("journals:gemm", ops.gemm, ((124, 124),), ((124, 62),)),
    ("m3plates:inner", ops.spgemm_inner,
     ((11000, 11000), 0, 8), ((11000, 5500), 1, 11000)),
    ("synthetic_dense:gemm", ops.gemm, ((5000, 4375),), ((4375, 2500),)),
    ("synthetic_dense:gustavson", ops.spgemm_gustavson,
     ((5000, 625), 1, 5000), ((625, 2500), 1, 632)),
    ("speech:spmm_mirror", ops.spmm_mirror,
     ((7700, 1950), 0, 256), ((1950, 488),)),
    ("speech:inner", ops.spgemm_inner,
     ((7700, 1950), 0, 256), ((1950, 812), 1, 1952)),
    ("speech:gustavson", ops.spgemm_gustavson,
     ((7700, 650), 1, 512), ((650, 1300), 1, 656)),
    ("gnmt:spmm", ops.spmm, ((400, 750),), ((750, 36000), 1, 512)),
    ("gnmt:inner", ops.spgemm_inner,
     ((1200, 750), 0, 512), ((750, 36000), 1, 512)),
    ("gnmt:gustavson", ops.spgemm_gustavson,
     ((1600, 250), 1, 1024), ((250, 36000), 1, 128)),
    ("transformer:spmm", ops.spmm, ((4000, 84),), ((84, 1000), 1, 64)),
    ("transformer:inner", ops.spgemm_inner,
     ((28000, 84), 0, 64), ((84, 1000), 1, 64)),
    ("citeseer:inner", ops.spgemm_inner,
     ((3300, 3300), 0, 16), ((3300, 3700), 1, 64)),
    # aespa_opt has no outer-product cluster; its body at citeseer's size.
    ("citeseer:outer", ops.spgemm_outer,
     ((3300, 3300), 1, 16), ((3300, 3700), 0, 64)),
]


def _spec(spec, sharding):
    return _dense(spec[0], sharding) if len(spec) == 1 else _ell(
        *spec, sharding=sharding)


def _blocks(op):
    if op in (ops.spmm, ops.spmm_mirror):
        return dict(bm=128, bn=128)
    return dict(bm=128, bn=128, bk=128)


@pytest.mark.parametrize("name,op,a_spec,b_spec", SERVED,
                         ids=[s[0] for s in SERVED])
def test_served_partition_compiles_for_v5e(one_chip, name, op, a_spec,
                                           b_spec):
    a, b = _spec(a_spec, one_chip), _spec(b_spec, one_chip)
    kw = dict(_blocks(op), interpret=False)
    if op is not ops.gemm:
        # Under Mosaic ``auto`` is the expansion body, nothing else (both
        # lowered from one call site: source locations are in the text).
        auto, ref = (op.lower(a, b, method=m, **kw)
                     for m in ("auto", "reference"))
        assert auto.as_text() == ref.as_text()
        lowered = auto
    else:
        lowered = op.lower(a, b, **kw)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


@pytest.mark.parametrize("op,a_spec,b_spec", [
    (ops.spmm, ((256, 256),), ((256, 256), 1, 32)),
    (ops.spgemm_inner, ((256, 256), 0, 32), ((256, 256), 1, 32)),
    (ops.spgemm_outer, ((256, 256), 1, 32), ((256, 256), 0, 32)),
    (ops.spgemm_gustavson, ((256, 256), 1, 32), ((256, 256), 1, 32)),
], ids=["spmm", "inner", "outer", "gustavson"])
def test_sparse_body_refused_without_interpreter(op, a_spec, b_spec):
    a, b = _spec(a_spec, None), _spec(b_spec, None)
    with pytest.raises(ValueError, match="Mosaic does not lower"):
        jax.eval_shape(lambda x, y: op(x, y, interpret=False,
                                       method="sparse"), a, b)
