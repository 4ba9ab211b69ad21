"""Multi-tenant serving on the searched AESPA-opt design, end to end.

Replays a 24-request, 3-tenant JSON trace through the online request
engine (``serve.cluster.ClusterServer``): event-driven admission over the
incremental scheduler, dispatch through the ``optimized`` policy, numeric
execution of every placement on the Pallas dataflow kernels, and telemetry
(p50/p99 waits, per-cluster utilization, SLA misses, tenant fairness).
Checks, like the paper's fig 12/13 story demands:

* every served response matches the dense reference ``A @ B``;
* the server's p99 wait and per-cluster utilization equal an offline
  ``schedule_many_kernels`` run on the same trace (admission only delays
  release times — with a zero batch window it delays nothing);
* ``deploy_from_dse`` turns a design × policy co-search result straight
  into a running server.

Run:  PYTHONPATH=src python examples/serve_cluster.py
Pass ``--trace-out serve.json`` to also export the served timeline as a
Perfetto-loadable Chrome trace (DESIGN.md §8).
"""
import argparse
import dataclasses
import math
import pathlib
import tempfile

import numpy as np

from repro.common.compile_cache import use_compile_cache
from repro.core import dse
from repro.core.scheduler import available_policies, schedule_many_kernels
from repro.serve.cluster import (
    ClusterServer,
    deploy_from_dse,
    generate_trace,
    load_trace,
    request_operands,
    save_trace,
    serve_result_to_json,
)

N_REQUESTS = 24
GAP_FACTOR = 0.25   # fig12's online construction: arrivals outpace service


def build_trace(config):
    """24 executable requests, arrivals staggered at GAP_FACTOR × the mean
    per-task share of the design's own LPT makespan, SLA = arrival + half
    that makespan."""
    reqs = generate_trace(N_REQUESTS, seed=11, mean_gap_cycles=1.0)
    base = schedule_many_kernels(config, [r.workload for r in reqs])
    gap = base.makespan_cycles / len(reqs) * GAP_FACTOR
    slack = base.makespan_cycles * 0.5
    return [dataclasses.replace(r, arrival_cycles=i * gap,
                                deadline_cycles=i * gap + slack)
            for i, r in enumerate(reqs)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="export the served timeline as a Chrome trace "
                         "JSON (open in https://ui.perfetto.dev)")
    args = ap.parse_args()
    use_compile_cache(pathlib.Path(__file__).resolve().parents[1]
                      / ".jax_cache")

    print("searching the serving design (AESPA-opt, memoized)...")
    config = dse.aespa_opt()
    print(f"config: {config.total_pes} PEs "
          f"({', '.join(c.name for c in config.clusters)})\n")

    trace = build_trace(config)
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        path = f.name
    save_trace(path, trace)
    replayed = load_trace(path)
    assert replayed == trace
    print(f"trace: {len(replayed)} requests, "
          f"{len({r.tenant for r in replayed})} tenants "
          f"(JSON round-trip via {path})")

    server = ClusterServer(config, policy="optimized")
    sr = server.run_trace(replayed, execute=True, block=64)

    worst = 0.0
    for res in sr.results:
        a, b = request_operands(res.request)
        err = float(np.abs(np.asarray(res.output) - a @ b).max())
        worst = max(worst, err)
        assert err < 1e-2, (res.request.request_id, err)
    print(f"every response matches the dense reference "
          f"(max |err| = {worst:.2e})")

    rep = sr.report
    s = rep.stats
    print(f"\n=== telemetry ({rep.policy} policy) ===")
    print(f"  makespan      {rep.makespan_cycles:.3e} cycles "
          f"({rep.makespan_s * 1e3:.3f} ms) -> "
          f"{rep.throughput_rps:.0f} req/s")
    print(f"  waits         p50={s.p50_wait_cycles:.3e} "
          f"p99={s.p99_wait_cycles:.3e} max={s.max_wait_cycles:.3e}")
    print(f"  utilization   {s.utilization:.3f} "
          f"(per cluster: {', '.join(f'{f:.2f}' for f in s.busy_fraction)})")
    print(f"  SLA           {s.deadline_misses}/{s.deadline_total} missed")
    print(f"  tenants       fairness={rep.fairness_index:.3f}")
    for t in rep.per_tenant:
        print(f"    {t.tenant:10s} n={t.n_requests:2d} "
              f"mean_wait={t.mean_wait_cycles:.3e} "
              f"misses={t.deadline_misses}")

    # The serving schedule IS the offline schedule on this trace.
    offline = schedule_many_kernels(
        config, [r.workload for r in replayed], policy="optimized",
        arrivals=[r.arrival_cycles for r in replayed])
    assert s.p99_wait_cycles == offline.stats.p99_wait_cycles
    assert s.busy_fraction == offline.stats.busy_fraction
    assert sr.schedule.makespan_cycles == offline.makespan_cycles
    print("\np99 wait and per-cluster utilization consistent with the "
          "offline schedule_many_kernels run")

    print("\n=== policy comparison (same trace, telemetry only) ===")
    for pol in sorted(available_policies()):
        r2 = ClusterServer(config, policy=pol).run_trace(
            replayed, execute=False).report
        print(f"  {pol:10s} makespan={r2.makespan_cycles:.3e} "
              f"p99_wait={r2.stats.p99_wait_cycles:.3e} "
              f"util={r2.stats.utilization:.3f} "
              f"sla_miss={r2.stats.deadline_misses}")

    print("\n=== deploy_from_dse: co-searched design × policy -> server ===")
    co = dse.co_search(
        tasks=sorted({r.workload for r in replayed},
                     key=lambda w: w.name),
        hbm_bw=math.inf, step=0.5, objective="makespan")
    deployed = deploy_from_dse(co)
    fr = {c.value: round(f, 3) for c, f in co.fractions.items()}
    print(f"  co-DSE winner: {fr} × {co.policy}")
    r3 = deployed.run_trace(replayed, execute=False).report
    print(f"  deployed server: config={r3.config_name} policy={r3.policy} "
          f"makespan={r3.makespan_cycles:.3e} "
          f"p99_wait={r3.stats.p99_wait_cycles:.3e}")

    payload = serve_result_to_json(sr)
    print(f"\nserve_result_to_json: {len(payload['results'])} request "
          f"records + report (replayable trace out)")

    if args.trace_out:
        out = sr.export_chrome_trace(args.trace_out)
        print(f"chrome trace: {out} (open in https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
