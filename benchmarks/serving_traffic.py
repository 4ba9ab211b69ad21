"""Serving-traffic benchmark — the online request engine (DESIGN.md §5)
over the fig 12 staggered-arrival construction: a doubled Table I queue
whose arrivals come 4× faster than the clusters drain it, replayed through
``serve.cluster.ClusterServer`` per scheduling policy on AESPA-equal5.

Rows report serve() wall time plus makespan / p99 wait / utilization /
SLA-miss telemetry per policy, a claim row checking the paper's ordering
(the ``optimized`` straggler-splitting strategy beats plain ``lpt`` on
makespan or p99 for the staggered trace), an admission-front-end row
(batch window + queue-depth gate) showing the batching/back-pressure
trade-off on the same trace, and the sustained-throughput row: measured
requests/sec over a 10×-length staggered trace served end-to-end on 8
forced host devices (subprocess, same trick as tests/test_sharded_exec),
comparing the pipelined operand-sharded executor against the unpipelined
replicated one — the ISSUE 7 acceptance artifact. The pipelined path must
sustain >= ``BENCH_SUSTAINED_MIN`` (default 1.3×) the replicated
throughput or the run fails.
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import subprocess
import sys
from typing import List

from benchmarks.common import Row, timeit
from repro.core import dse
from repro.core.scheduler import available_policies, schedule_many_kernels
from repro.core.workloads import TABLE_I
from repro.serve.cluster import ClusterServer, Request

TENANTS = ("tenant_a", "tenant_b", "tenant_c")
GAP_FACTOR = 0.25           # fig12's online construction
DEADLINE_SLACK = 0.5        # × the LPT makespan
SUSTAINED_SCALE = 10        # × the fig12 doubled-queue length
SUSTAINED_DEPTH = 4         # pipeline_depth of the pipelined contender
_SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

# The sustained-throughput child: jax locks the device count at init, so
# the 8-device serve runs fork a fresh process (the tests' trick). Both
# contenders are fully warmed (compile caches) before timing.
_SUSTAINED_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, math, statistics, sys, time
sys.path.insert(0, __SRC__)
from repro.core import dse
from repro.core.scheduler import schedule_many_kernels
from repro.core.workloads import TABLE_I, Workload, synthesize
from repro.launch.mesh import make_mesh
from repro.serve.cluster import ClusterServer, Request

SCALE, DEPTH, GAP_FACTOR, SLACK = __PARAMS__
TENANTS = ("tenant_a", "tenant_b", "tenant_c")

cfg = dse.aespa_equal5(math.inf)
templates = []
for i, w0 in enumerate(TABLE_I):
    _, _, (m, k, n) = synthesize(w0, seed=50 + i, max_elems=1 << 13)
    templates.append(Workload(w0.name, w0.application, m, k, n,
                              w0.d_mk, w0.d_kn))
base = schedule_many_kernels(cfg, templates)
tasks = templates * (2 * SCALE)      # 10x the fig12 doubled queue
gap = base.makespan_cycles / (2 * len(templates)) * GAP_FACTOR
slack = base.makespan_cycles * SLACK
trace = [Request(f"req{i:04d}", TENANTS[i % len(TENANTS)], w,
                 arrival_cycles=i * gap, deadline_cycles=i * gap + slack)
         for i, w in enumerate(tasks)]
window = gap * 3                     # small multi-request admitted batches
MESH = make_mesh((8,), ("model",))


def run_once(depth, shard_operands, measure=False):
    srv = ClusterServer(cfg, policy="optimized",
                        batch_window_cycles=window)
    t0 = time.perf_counter()
    sr = srv.run_trace(trace, interpret=True, block=32, mesh=MESH,
                       pipeline_depth=depth, shard_operands=shard_operands,
                       measure=measure)
    return time.perf_counter() - t0, sr


run_once(1, False)                   # warm: replicated program cache
run_once(DEPTH, True)                # warm: packed program cache
rep_s = statistics.median(run_once(1, False)[0] for _ in range(5))
pipe_s = statistics.median(run_once(DEPTH, True)[0] for _ in range(5))
_, rep = run_once(1, False)
_, pipe = run_once(DEPTH, True)
# Measured spatial speedup at depth 1: span windows are stamped from
# batch dispatch, so a deeper pipeline would fold queueing time into
# them — depth 1 attributes the observed overlap to spatial concurrency
# alone (DESIGN.md §6).
_, meas = run_once(1, True, measure=True)
st = meas.report.stats
print(json.dumps({
    "n_requests": len(trace),
    "n_batches": pipe.report.n_batches,
    "replicated_s": rep_s,
    "pipelined_s": pipe_s,
    "measured_spatial_speedup": st.measured_spatial_speedup,
    "modelled_spatial_speedup": st.spatial_speedup,
    "same_p99": rep.report.stats.p99_wait_cycles
                == pipe.report.stats.p99_wait_cycles,
}))
"""


def sustained_throughput_row() -> Row:
    """Measured requests/sec over the 10×-length staggered trace: the
    pipelined operand-sharded path vs the unpipelined replicated one,
    plus the measured-vs-modelled spatial speedup from the same run. Row
    value is µs/request of the pipelined path (lower is better, so the
    standard regression gate applies); fails if the pipeline speedup
    drops below BENCH_SUSTAINED_MIN (default 1.3)."""
    min_speedup = float(os.environ.get("BENCH_SUSTAINED_MIN", "1.3"))
    src = _SUSTAINED_CHILD.replace("__SRC__", repr(_SRC)).replace(
        "__PARAMS__", repr((SUSTAINED_SCALE, SUSTAINED_DEPTH,
                            GAP_FACTOR, DEADLINE_SLACK)))
    # The child runs on 8 forced host devices by construction; on a TPU
    # host the parent may already hold the chip, which the child could
    # not open.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=1800, env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"sustained-throughput child failed:\n{out.stderr[-3000:]}")
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    n = rec["n_requests"]
    rps_pipe = n / rec["pipelined_s"]
    rps_rep = n / rec["replicated_s"]
    speedup = rps_pipe / rps_rep
    row: Row = (
        "serving/sustained_throughput", rec["pipelined_s"] / n * 1e6,
        f"requests={n};batches={rec['n_batches']};"
        f"rps_pipelined={rps_pipe:.1f};rps_replicated={rps_rep:.1f};"
        f"pipeline_speedup={speedup:.2f}x;"
        f"measured_spatial_speedup={rec['measured_spatial_speedup']:.2f}x;"
        f"modelled_spatial_speedup={rec['modelled_spatial_speedup']:.2f}x;"
        f"min_speedup={min_speedup:.2f}x",
    )
    if not rec["same_p99"]:
        raise AssertionError(
            "pipelined and replicated serve runs disagree on p99 wait — "
            "execution mode must not change telemetry")
    if speedup < min_speedup:
        raise AssertionError(
            f"pipelined sharded serving sustains only {speedup:.2f}x the "
            f"replicated path (gate: {min_speedup:.2f}x; loosen via "
            "BENCH_SUSTAINED_MIN for slow hosted runners)")
    return row


def staggered_trace(config) -> List[Request]:
    """Doubled Table I queue, arrivals staggered at GAP_FACTOR × the mean
    per-task share of the design's own LPT makespan, round-robin tenants,
    SLA deadline = arrival + half that makespan."""
    base = schedule_many_kernels(config, TABLE_I)
    tasks = list(TABLE_I) * 2
    gap = base.makespan_cycles / len(tasks) * GAP_FACTOR
    slack = base.makespan_cycles * DEADLINE_SLACK
    return [
        Request(f"req{i:03d}", TENANTS[i % len(TENANTS)], w,
                arrival_cycles=i * gap, deadline_cycles=i * gap + slack)
        for i, w in enumerate(tasks)
    ]


def run() -> List[Row]:
    cfg = dse.aespa_equal5(math.inf)
    trace = staggered_trace(cfg)

    rows: List[Row] = []
    reports = {}
    for pol in sorted(available_policies()):
        server = ClusterServer(cfg, policy=pol)
        sr = server.run_trace(trace, execute=False)       # warm caches
        reports[pol] = sr.report
        us = timeit(lambda pol=pol: ClusterServer(cfg, policy=pol)
                    .run_trace(trace, execute=False), repeats=5)
        s = sr.report.stats
        rows.append((
            f"serving/{pol}", us,
            f"requests={sr.report.n_requests};"
            f"makespan_cycles={sr.report.makespan_cycles:.3e};"
            f"p99_wait={s.p99_wait_cycles:.3e};"
            f"util={s.utilization:.3f};"
            f"sla_miss={s.deadline_misses}/{s.deadline_total};"
            f"fairness={sr.report.fairness_index:.3f}",
        ))

    # Spatial overlap under the server (DESIGN.md §6): how much the
    # sharded cluster-submesh path (serve(mesh=...), clusters running
    # their shares concurrently) buys over one-device serialisation.
    s_opt = reports["optimized"].stats
    rows.append((
        "serving/spatial_overlap", 0.0,
        f"concurrent_cycles={s_opt.concurrent_makespan_cycles:.3e};"
        f"sequential_cycles={s_opt.sequential_makespan_cycles:.3e};"
        f"spatial_speedup={s_opt.spatial_speedup:.2f}x",
    ))

    lpt, opt = reports["lpt"], reports["optimized"]
    mk_ratio = lpt.makespan_cycles / max(opt.makespan_cycles, 1e-12)
    p99_ratio = (lpt.stats.p99_wait_cycles
                 / max(opt.stats.p99_wait_cycles, 1e-12))
    beats = mk_ratio > 1.0 + 1e-9 or p99_ratio > 1.0 + 1e-9
    rows.append((
        "serving/claim_optimized_vs_lpt", 0.0,
        f"paper=optimized_best;makespan_ratio={mk_ratio:.3f}x;"
        f"p99_ratio={p99_ratio:.3f}x;beats={int(beats)}",
    ))
    if not beats:
        raise AssertionError(
            "optimized no longer beats lpt on the staggered serving trace "
            f"(makespan ratio {mk_ratio:.3f}, p99 ratio {p99_ratio:.3f})")

    # Admission front-end: batch window + queue-depth back-pressure on the
    # same trace (waits absorb the admission delay; batches shrink the
    # scheduler invocation count).
    base = schedule_many_kernels(cfg, TABLE_I)
    window = base.makespan_cycles / len(trace)
    gated = ClusterServer(cfg, policy="optimized",
                          batch_window_cycles=window,
                          max_queue_depth=6).run_trace(trace, execute=False)
    g = gated.report
    rows.append((
        "serving/admission_windowed", 0.0,
        f"batches={g.n_batches};window_cycles={window:.3e};"
        f"mean_wait={g.stats.mean_wait_cycles:.3e};"
        f"p99_wait={g.stats.p99_wait_cycles:.3e};"
        f"makespan_cycles={g.makespan_cycles:.3e}",
    ))

    rows.append(sustained_throughput_row())
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    emit(run())
