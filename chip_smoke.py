"""Serve the Table-I requests at their published sizes on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: sharded vs one-device

One chip: each Table-I workload (``core/workloads.py``) becomes one
request at its published dims, with operands synthesised from the request
seed (``serve.cluster.request_operands``). Each request is served by
``ClusterServer(dse.aespa_opt(), policy="optimized").run_trace`` on the
normal path — ``mesh=None``, ``interpret`` left to
``kernels.ops.default_interpret()`` (Mosaic on the TPU) — twice: the first
call compiles, the second is the warm repeat. Wall times cover operand
transfer, format conversion, the kernels and the merge, ending in
``block_until_ready``; operand synthesis on the host is outside them.

Four chips: the same eight requests arrive together as one trace, served
once with ``mesh=make_mesh((4,), ("model",))`` (the cluster-submesh
executor: ``aespa_opt`` has four clusters, one per chip) and once on one
device; the outputs must agree. Nothing else runs in that mode.

Correctness: every output is compared with ``jnp.matmul(a, b,
precision=HIGHEST)``. Both sides accumulate float32 products over K, so by
the standard bound for recursive summation each lies within
``K·u·(|A|·|B|)`` of the exact product (u = 2⁻²⁴, the float32 unit
roundoff), and they may differ by twice that. The check is therefore
elementwise ``|out - ref| <= 2·K·eps·(|A|·|B|)`` with eps = 2⁻²³ = 2u:
it holds for any summation order, and a single bf16 MXU pass (relative
error 2⁻⁹ per product) breaks it.

bibd_81_3 is left out: its dense B alone is 85,000 × 43,000 float32 =
14.6 GB, and with A and C it exceeds the chip's 16 GB of HBM.

The last line of standard output is one JSON object naming the device;
the script prints it only if every request passed, and exits non-zero
without it when JAX finds no TPU, when a check fails or when any phase
raises.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

#: Operand budget for ``request_operands``: m3plates' A is 11,000² =
#: 1.21e8 elements, so anything below 1 << 27 would refuse it.
MAX_ELEMS = 1 << 27

SKIPPED = {
    "bibd_81_3": ("dense B is 85,000 x 43,000 float32 = 14.6 GB; with A "
                  "and C it exceeds the 16 GB of HBM of one chip"),
}


def table_i_requests():
    """One request per Table-I workload (minus :data:`SKIPPED`), all
    arriving at t=0, seeded by their Table-I index."""
    from repro.core.workloads import TABLE_I
    from repro.serve.cluster import Request

    return [Request(request_id=w.name, tenant=w.application, workload=w,
                    arrival_cycles=0.0, seed=i)
            for i, w in enumerate(TABLE_I) if w.name not in SKIPPED]


def reference(a, b):
    """HIGHEST-precision product and the elementwise tolerance
    ``2·K·eps·(|A|·|B|)`` (module docstring)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    a, b = jnp.asarray(a), jnp.asarray(b)
    ref = jnp.matmul(a, b, precision=hi)
    eps = float(jnp.finfo(jnp.float32).eps)
    tol = 2.0 * a.shape[1] * eps * jnp.matmul(jnp.abs(a), jnp.abs(b),
                                              precision=hi)
    return ref, tol


def compare(out, ref, tol):
    """``(max |out - ref|, every element within tol)`` as host values."""
    import jax
    import jax.numpy as jnp

    err = jnp.abs(out.astype(jnp.float32) - ref)
    max_err, ok = jax.device_get((jnp.max(err), jnp.all(err <= tol)))
    return float(max_err), bool(ok)


def partition_lines(served, a, b):
    """One line per executed partition: class, orientation, cluster,
    region, the static ELL capacities the executor derived, and the body
    ``method="auto"`` takes for it on the TPU."""
    import jax.numpy as jnp

    from repro.core.hetero_matmul import prepare_partitions
    from repro.formats.taxonomy import DataflowClass

    parts = [pp.partition for pp in served.assignment.placed
             if not pp.partition.region.empty]
    prepared = prepare_partitions([(jnp.asarray(a), jnp.asarray(b), parts)])
    lines = []
    for p, _, _, caps in prepared[0]:
        r = p.region
        # Under Mosaic every sparse class's auto route is the expansion body
        # (kernels/*: ``auto`` is sparse only when interpreting).
        body = "gemm" if p.cls == DataflowClass.GEMM else "expansion"
        lines.append(
            f"    {p.cls.value}{' mirror' if p.mirror else ''} "
            f"cluster={p.cluster} rows={r.m0}:{r.m1} k={r.k0}:{r.k1} "
            f"cols={r.n0}:{r.n1} caps={list(caps)} body={body}")
    return lines


def one_chip(cfg) -> bool:
    import jax

    from repro.serve.cluster import ClusterServer, request_operands

    all_ok = True
    for req in table_i_requests():
        w = req.workload
        a, b = request_operands(req, max_elems=MAX_ELEMS)
        walls, outs = [], []
        for _ in range(2):                     # first call, warm repeat
            server = ClusterServer(cfg, policy="optimized")
            t0 = time.perf_counter()
            served = server.run_trace(
                [req], execute=True,
                operands={req.request_id: (a, b)}).results[0]
            outs.append(jax.block_until_ready(served.output))
            walls.append(time.perf_counter() - t0)
        ref, tol = reference(a, b)
        checks = [compare(out, ref, tol) for out in outs]
        del ref, tol, outs
        ok = all(good for _, good in checks)
        all_ok &= ok
        print(f"{w.name}: dims={w.dims} density=({w.d_mk}, {w.d_kn}) "
              f"first_s={walls[0]:.3f} warm_s={walls[1]:.3f} "
              f"max_abs_err={max(e for e, _ in checks):.3e} "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        for line in partition_lines(served, a, b):
            print(line, flush=True)
    return all_ok


def four_chips(cfg) -> bool:
    import jax

    from repro.core.hetero_matmul import cluster_submeshes
    from repro.core.sharded_exec import device_for_partition
    from repro.launch.mesh import make_mesh
    from repro.serve.cluster import ClusterServer, request_operands

    n_dev = len(jax.devices())
    if n_dev != 4:
        raise SystemExit(f"chip_smoke --four-chips: needs 4 devices, "
                         f"JAX found {n_dev}")
    trace = table_i_requests()
    operands = {r.request_id: request_operands(r, max_elems=MAX_ELEMS)
                for r in trace}
    mesh = make_mesh((n_dev,), ("model",))
    runs = {}
    for name, kw in (("one_device", {}), ("sharded", {"mesh": mesh})):
        t0 = time.perf_counter()
        sr = ClusterServer(cfg, policy="optimized").run_trace(
            trace, execute=True, operands=operands, **kw)
        outs = {res.request.request_id: res.output for res in sr.results}
        jax.block_until_ready(list(outs.values()))
        print(f"{name}: served {len(outs)} requests in "
              f"{time.perf_counter() - t0:.3f}s", flush=True)
        runs[name] = (sr, outs)

    # Each device's share of the packed operand payload: the §6 rule puts
    # every partition's slices on one device of its cluster's span.
    sr = runs["sharded"][0]
    spans = cluster_submeshes(n_dev, cfg)
    counters: dict = {}
    payload = [0] * n_dev
    for res in sr.results:
        for pp in res.assignment.placed:
            r = pp.partition.region
            if r.empty:
                continue
            d = device_for_partition(spans, counters, pp.partition.cluster)
            payload[d] += 4 * ((r.m1 - r.m0) * (r.k1 - r.k0)
                               + (r.k1 - r.k0) * (r.n1 - r.n0))
    total = sum(payload)
    print("payload share per device: "
          + " ".join(f"dev{d}={payload[d] / total:.3f}"
                     for d in range(n_dev)), flush=True)

    all_ok = all(p > 0 for p in payload)   # every chip gets work
    for req in trace:
        a, b = operands[req.request_id]
        _, tol = reference(a, b)
        # Both outputs onto device 0 (the sharded one is replicated).
        seq, shd = (jax.device_put(runs[k][1][req.request_id],
                                   jax.devices()[0])
                    for k in ("one_device", "sharded"))
        err, ok = compare(shd, seq, tol)
        all_ok &= ok
        clusters = sorted({pp.partition.cluster for pp in next(
            res for res in sr.results
            if res.request.request_id == req.request_id).assignment.placed})
        print(f"{req.request_id}: dims={req.workload.dims} "
              f"clusters={clusters} max_abs_diff_sharded_vs_one_device="
              f"{err:.3e} {'ok' if ok else 'FAILED'}", flush=True)
    return all_ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve the trace on a 4-chip mesh and compare it "
                         "with the one-device serve (nothing else runs)")
    args = ap.parse_args()

    import jax

    from repro.common.compile_cache import use_compile_cache
    from repro.core import dse

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r}")
    use_compile_cache(REPO / ".jax_cache")
    for name, why in SKIPPED.items():
        print(f"skipped {name}: {why}")
    cfg = dse.aespa_opt()
    ok = four_chips(cfg) if args.four_chips else one_chip(cfg)
    if not ok:
        raise SystemExit("chip_smoke: a check failed (lines above)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
