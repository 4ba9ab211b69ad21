"""HLO analysis: loop multipliers, dot flops, collective parsing — validated
against a ground-truth scanned matmul lowered for a real (host-device) mesh
in a subprocess (device count is locked at jax init, so multi-device tests
fork)."""
import json
import subprocess
import sys
import textwrap

import pytest

from repro.launch import hlo_analysis as H

SYNTH = """\
%body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %ag = f32[8,32]{1,0} all-gather(%g1), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}
  %dot.5 = f32[8,8]{1,0} dot(%g1, %g1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%g0, %dot.5)
}

%cond.1 (p2: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p2), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %tup = (s32[], f32[8,8]{1,0}) tuple(%z, %a)
  %w = (s32[], f32[8,8]{1,0}) while(%tup), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_shape_bytes():
    assert H.shape_bytes("f32[4,8]{1,0}") == 128
    assert H.shape_bytes("bf16[2,3]") == 12
    assert H.shape_bytes("(f32[4], s32[2])") == 24
    assert H.shape_bytes("pred[]") == 1


def test_split_computations_synthetic():
    comps = H.split_computations(SYNTH)
    assert set(comps) == {"body.1", "cond.1", "main"}


def test_loop_multipliers_synthetic():
    mults = H.loop_multipliers(SYNTH)
    assert mults["main"] == 1
    assert mults["body.1"] == 5
    assert mults["cond.1"] == 6


def test_dot_flops_synthetic():
    # one 8x8x8 dot per iteration, 5 iterations: 2*8*8*8*5 = 5120
    assert H.dot_flops(SYNTH) == 5120.0


def test_collectives_loop_corrected():
    st = H.collective_stats(SYNTH, 8)
    assert st.ops["all-gather"] == 5
    # result 8x32 f32 = 1024B, group 4 -> (3/4)*1024 per iter * 5
    assert st.ici_bytes_per_chip == pytest.approx(5 * 1024 * 3 / 4)


def test_group_size_formats():
    line_iota = "x = f32[8]{0} all-gather(%y), replica_groups=[2,4]<=[8]"
    line_expl = "x = f32[8]{0} all-gather(%y), replica_groups={{0,1,2,3},{4,5,6,7}}"
    assert H._group_size(line_iota, 99) == 4
    assert H._group_size(line_expl, 99) == 4
    assert H._group_size("no groups here", 7) == 7


def test_roofline_terms_and_dominance():
    rl = H.roofline_terms(197e12, 819e9 * 2, 50e9 * 0.5)
    assert rl.compute_s == pytest.approx(1.0)
    assert rl.memory_s == pytest.approx(2.0)
    assert rl.collective_s == pytest.approx(0.5)
    assert rl.dominant == "memory"
    assert rl.bound_s == pytest.approx(2.0)


def test_model_flops():
    assert H.model_flops(10, 5, "train") == 300
    assert H.model_flops(10, 5, "serve") == 100
    assert H.model_flops(10, 5, "train", active_param_count=2) == 60


GROUND_TRUTH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "/root/repo/src")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch import hlo_analysis as H

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
L, D, B = 7, 256, 64

def f(ws, x):
    def body(c, w):
        c = jax.lax.with_sharding_constraint(c @ w, P("data", "model"))
        return c, ()
    y, _ = jax.lax.scan(body, x, ws)
    return y.sum()

ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
x = jax.ShapeDtypeStruct((B, D), jnp.float32)
with mesh:
    co = jax.jit(f, in_shardings=(
        NamedSharding(mesh, P(None, "data", "model")),
        NamedSharding(mesh, P("data", "model")))).lower(ws, x).compile()
hlo = co.as_text()
flops = H.dot_flops(hlo)
true_per_dev = L * 2 * B * D * D / 8
cs = H.collective_stats(hlo, 8)
print(json.dumps({"flops": flops, "true": true_per_dev,
                  "ag": cs.ops["all-gather"],
                  "mem": H.memory_bytes(hlo)}))
"""


def test_ground_truth_scanned_matmul():
    out = subprocess.run([sys.executable, "-c", GROUND_TRUTH],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] == pytest.approx(rec["true"], rel=1e-6)
    assert rec["ag"] == 2 * 7          # two all-gathers per scan iteration
    # memory model: ≥ the pure matmul operand traffic, ≤ 10x of it
    matmul_traffic = 7 * (64 * 256 + 256 * 256 / 4 + 64 * 256) * 4
    assert rec["mem"] >= matmul_traffic * 0.5
    assert rec["mem"] <= matmul_traffic * 20
