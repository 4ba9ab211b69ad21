"""Fleet serving with fault injection, end to end (DESIGN.md §9).

Launches a 3-replica :class:`repro.launch.fleet.FleetServer` on the small
AESPA config, routes an 18-request, 6-tenant trace through the
consistent-hash router, kills one replica mid-batch, and lets failover
requeue its unfinished work onto the survivors. Checks:

* exactly-once: every request of the trace is served exactly once despite
  the death — and every response numerically matches a single-server run
  of the same trace (the ``affinity`` policy breaks equal-cycle placement
  ties by cluster load, so a sharded fleet may legally pick a different
  but equally-fast cluster; outputs then agree to float32 tolerance);
* SLA misses caused by the failover are charged to the fleet, not the
  tenant;
* per-replica metrics snapshots ship to the router and aggregate
  fleet-wide;
* under a priority-preemption front-end, low-priority requests yield at
  contended admission events.

Run:  PYTHONPATH=src python examples/fleet_serve.py
Pass ``--trace-out fleet.json`` to export the fleet timeline as a
Perfetto-loadable Chrome trace (one process row per replica).
"""
import argparse
import dataclasses
import math

import numpy as np

from repro.core import costmodel as cm
from repro.formats.taxonomy import DataflowClass as D
from repro.launch.fleet import Autoscaler, FaultPlan, FleetServer
from repro.serve.cluster import ClusterServer, generate_trace

N_REQUESTS = 18
TENANTS = tuple(f"tenant_{c}" for c in "abcdef")


def small_aespa():
    return cm.AcceleratorConfig(
        "aespa_small",
        tuple(cm.basic_cluster(c, 64) for c in
              (D.GEMM, D.SPMM, D.SPGEMM_INNER, D.SPGEMM_OUTER,
               D.SPGEMM_GUSTAVSON)),
        math.inf,
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="export the fleet timeline as a Chrome trace")
    args = ap.parse_args()

    cfg = small_aespa()
    trace = generate_trace(N_REQUESTS, tenants=TENANTS, seed=13,
                           mean_gap_cycles=1500.0,
                           deadline_slack_cycles=60_000.0)

    # -- single server as the ground truth ------------------------------
    single = ClusterServer(cfg, policy="affinity").run_trace(
        trace, block=64)

    # -- 3-replica fleet, one replica killed mid-batch ------------------
    fleet = FleetServer(cfg, n_replicas=3, policy="affinity",
                        fault_plan=FaultPlan.kill_mid_batch(0, batch=0),
                        failover_detect_cycles=1000.0)
    fr = fleet.run_trace(trace, block=64)

    print(f"fleet: {fr.report.n_replicas_live}/"
          f"{fr.report.n_replicas_launched} replicas live, "
          f"{fr.report.n_requests} requests served, "
          f"{fr.report.requeued_requests} requeued by failover")
    for f in fr.fault_log:
        print(f"  fault: {f.kind} on {f.replica} at {f.cycles:.3e} cyc "
              f"(requeued {f.n_requeued})")

    by_id = {r.request.request_id: r for r in single.results}
    assert sorted(r.request.request_id for r in fr.records) == sorted(
        r.request_id for r in trace)
    for rec in fr.records:
        np.testing.assert_allclose(
            np.asarray(rec.output),
            np.asarray(by_id[rec.request.request_id].output),
            rtol=1e-4, atol=1e-5)
    print("exactly-once, and every response matches the single-server "
          "run to float32 tolerance (affinity placement)")

    print(f"aggregate p99 wait {fr.report.stats.p99_wait_cycles:.3e} cyc, "
          f"fairness {fr.report.fairness_index:.3f}, SLA misses "
          f"{fr.report.sla_misses_failover} failover-attributed / "
          f"{fr.report.sla_misses_tenant} tenant-attributed")

    agg = fr.aggregate_metrics()
    print(f"router aggregated {agg['n_replicas']} replica snapshots: "
          f"admitted={agg['counters']['replica.admitted']:.0f}, "
          f"requeued_in={agg['counters']['replica.requeued_in']:.0f}")

    # -- priority preemption under contention ---------------------------
    prio = [dataclasses.replace(r, priority=i % 2,
                                arrival_cycles=r.arrival_cycles / 8)
            for i, r in enumerate(trace)]
    fp = FleetServer(cfg, n_replicas=1, batch_window_cycles=800.0,
                     preempt_depth=2).run_trace(prio, execute=False)
    deferred = [ev for ev in fp.admission_log if ev.deferred]
    assert deferred and all(
        min(p for _, p in ev.admitted) >= max(p for _, p in ev.deferred)
        for ev in deferred)
    print(f"preemption: {fp.report.preempted_deferrals} low-priority "
          f"deferrals across {len(deferred)} contended admission events")

    # -- queue-depth autoscaling ----------------------------------------
    fa = FleetServer(cfg, n_replicas=1, batch_window_cycles=800.0,
                     autoscaler=Autoscaler(high_water=3, low_water=0,
                                           max_replicas=4)
                     ).run_trace(prio, execute=False)
    ups = [s for s in fa.scale_log if s.action == "up"]
    print(f"autoscaler: {fa.report.n_replicas_launched} replicas launched "
          f"({len(ups)} scale-ups at depth >= 3)")

    if args.trace_out:
        path = fr.export_chrome_trace(args.trace_out)
        print(f"fleet Chrome trace written to {path} "
              f"(one process row per replica + router)")


if __name__ == "__main__":
    main()
