"""Observability layer (DESIGN.md §8): tracer schema round-trip, metrics
registry, cache-counter reset satellites, serve-trace consistency against
``ServerReport``/``BatchTimeline``, bit-identical-when-disabled, and the
disabled-overhead gate."""
import json
import math
import time

import numpy as np
import pytest

from repro import obs
from repro.core import costmodel as cm
from repro.core.costmodel import cycles_to_us
from repro.core.workloads import TABLE_I, Workload
from repro.formats.taxonomy import DataflowClass as D
from repro.serve import cluster as sc


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with tracing off and an empty buffer."""
    obs.disable()
    obs.TRACE.reset()
    yield
    obs.disable()
    obs.TRACE.reset()


def _config():
    return cm.aespa_from_fractions(
        {D.GEMM: 0.5, D.SPMM: 0.3, D.SPGEMM_INNER: 0.2}, name="obs_test")


def _requests(n=8, window=2e4):
    reqs = []
    for i, w in enumerate((list(TABLE_I) * 2)[:n]):
        reqs.append(sc.Request(
            f"r{i:02d}", f"tenant{i % 3}", w, arrival_cycles=i * window,
            deadline_cycles=(i * window + 5e7 if i % 2 else None), seed=i))
    return reqs


# ------------------------------------------------------------------ tracer
def test_tracer_schema_roundtrip(tmp_path):
    tr = obs.Tracer(capacity=100)
    prev = obs.enable()
    try:
        tr.complete("span_a", 10.0, 5.0, pid=obs.PID_VIRTUAL,
                    tid="rowB", cat="test", k=1)
        tr.complete("span_b", 0.0, 2.0, pid=obs.PID_VIRTUAL, tid="rowA")
        tr.instant("mark", 3.0, pid=obs.PID_VIRTUAL, tid="rowA", note="x")
        tr.counter("depth", 2.0, 4.0, pid=obs.PID_VIRTUAL, tid="rowA")
        with tr.span("wall", pid=obs.PID_HOST, tid=0, arg="y"):
            time.sleep(0.001)
    finally:
        obs.enable(prev)
    p = tr.export_chrome_trace(tmp_path / "t.json")
    doc = json.loads(p.read_text())
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"M", "X", "i", "C"}
    for e in evs:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # process metadata for every pid, thread names for the string tids
    pids = {e["pid"] for e in evs if e["ph"] != "M"}
    named = {(e["pid"], e["args"]["name"]) for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {p for p, _ in named} == pids
    tnames = [e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"]
    assert "rowA" in tnames and "rowB" in tnames
    # events sorted by (pid, tid, ts); string-tid mapping is stable
    body = [e for e in evs if e["ph"] != "M"]
    keys = [(e["pid"], e["tid"], e["ts"]) for e in body]
    assert keys == sorted(keys)
    again = tr.chrome_trace()["traceEvents"]
    tid_of = lambda d: {e["name"]: e["tid"] for e in d  # noqa: E731
                        if e["ph"] in ("X", "i", "C")}
    assert tid_of(evs) == tid_of(again)
    # wall span landed with a positive measured duration
    wall = [e for e in body if e["name"] == "wall"]
    assert wall and wall[0]["dur"] >= 1000.0  # slept 1ms


def test_tracer_disabled_is_inert():
    tr = obs.Tracer()
    assert not obs.enabled()
    tr.complete("x", 0.0, 1.0)
    tr.instant("y")
    tr.counter("z", 1.0)
    s = tr.span("w")
    with s:
        pass
    assert s is tr.span("w2")  # shared no-op singleton: zero allocation
    assert tr.events() == []


def test_tracer_ring_buffer_caps_and_counts_drops():
    tr = obs.Tracer(capacity=10)
    prev = obs.enable()
    try:
        for i in range(25):
            tr.instant("e", float(i))
    finally:
        obs.enable(prev)
    evs = tr.events()
    assert len(evs) == 10
    assert tr.dropped == 15
    assert evs[0]["ts"] == 15.0  # oldest dropped first
    tr.reset()
    assert tr.events() == [] and tr.dropped == 0


@pytest.mark.parametrize("enabled", [False, True])
def test_span_lands_nested_in_the_profiler_trace(tmp_path, enabled):
    """With a profiler session live, ``Tracer.span`` is also a
    ``TraceAnnotation``: named, nested in its parent on one thread, its
    arguments as stats. The ring buffer records only while enabled, and
    once the session ends a disabled span is the no-op singleton again."""
    import glob

    import jax

    tr = obs.Tracer()
    obs.enable(enabled)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("serve.schedule", requests=2):
            with tr.span("exec.ell", cls="spmm", operand="b"):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    assert [e["name"] for e in tr.events()] == (
        ["exec.ell", "serve.schedule"] if enabled else [])
    assert tr.span("w") is tr.span("w2")

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("serve.schedule", "exec.ell"):
                    found[e.name] = (line.name, e.start_ns, e.end_ns,
                                     dict(e.stats))
    outer, inner = found["serve.schedule"], found["exec.ell"]
    assert outer[0] == inner[0]                      # one thread
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert inner[2] - inner[1] >= 1e6                # slept 1 ms
    assert inner[3] == {"cls": "spmm", "operand": "b"}
    assert outer[3] == {"requests": 2}


# ----------------------------------------------------------------- metrics
def test_metrics_registry_snapshot_reset_and_callbacks():
    reg = obs.MetricsRegistry()
    c = reg.counter("c")
    g = reg.gauge("g")
    c.inc()
    c.inc(2.5)
    g.set(7)
    g.dec(3)
    reg.register_callback("ext", lambda: {"k": 42})
    reg.register_callback("broken", lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 4.0
    assert snap["derived"]["ext"] == {"k": 42}
    assert "error" in snap["derived"]["broken"]
    assert reg.counter("c") is c  # get-or-create returns the live object
    reg.reset()
    snap2 = reg.snapshot()
    assert snap2["counters"]["c"] == 0.0
    assert snap2["gauges"]["g"] == 0.0
    json.dumps(snap)  # snapshot is JSON-serialisable as-is


def test_metrics_export_json(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("a").inc(3)
    p = reg.export_json(tmp_path / "m.json")
    assert json.loads(p.read_text())["counters"]["a"] == 3.0


# ------------------------------------------- cache-counter reset satellites
def test_program_cache_reset_zeroes_counters():
    from repro.core import sharded_exec as sx

    sx.program_cache_reset()
    assert sx.program_cache_info() == {"hits": 0, "misses": 0, "size": 0}
    sx._cached_program(("obs-test-key",), lambda: "prog")
    sx._cached_program(("obs-test-key",), lambda: "prog")
    info = sx.program_cache_info()
    assert info["misses"] == 1 and info["hits"] == 1 and info["size"] == 1
    snap = obs.METRICS.snapshot()
    assert snap["derived"]["executor.program_cache"]["hits"] == 1
    sx.program_cache_reset()
    assert sx.program_cache_info() == {"hits": 0, "misses": 0, "size": 0}
    assert (obs.METRICS.snapshot()["derived"]["executor.program_cache"]
            == {"hits": 0, "misses": 0, "size": 0})


def test_schedule_cache_info_exposed():
    from repro.core import scheduler as sched

    sched.clear_schedule_cache()
    cfg = _config()
    w = Workload("obs", "test", 64, 64, 64, 0.3, 0.3)
    sched.schedule_single_kernel(cfg, w, memo=True)
    sched.schedule_single_kernel(cfg, w, memo=True)
    info = sched.schedule_cache_info()
    assert info["single_kernel_memo"]["misses"] >= 1
    assert info["single_kernel_memo"]["hits"] >= 1
    assert info["best_on_cluster"]["currsize"] >= 0
    assert (obs.METRICS.snapshot()["derived"]["scheduler.caches"]
            ["single_kernel_memo"]["hits"] >= 1)


# -------------------------------------------------------- serve-trace rows
def test_serve_trace_consistency(tmp_path):
    server = sc.ClusterServer(_config(), policy="optimized",
                              batch_window_cycles=5e4, max_queue_depth=4)
    sr = server.run_trace(_requests(), execute=False)
    p = sr.export_chrome_trace(tmp_path / "serve.json")
    doc = json.loads(p.read_text())
    evs = doc["traceEvents"]

    # per-request phase spans reconcile with RequestResult / ServerReport
    def phases(rid):
        return [e for e in evs if e["ph"] == "X"
                and e.get("cat") == "request"
                and e["args"]["request_id"] == rid]

    waits, turns = [], []
    for res in sr.results:
        ph = {e["name"]: e for e in phases(res.request.request_id)}
        assert set(ph) == {"admit", "queue", "run"}
        total = sum(e["dur"] for e in ph.values())
        assert total == pytest.approx(
            cycles_to_us(res.turnaround_cycles), rel=1e-9, abs=1e-6)
        wait = ph["admit"]["dur"] + ph["queue"]["dur"]
        assert wait == pytest.approx(
            cycles_to_us(res.wait_cycles), rel=1e-9, abs=1e-6)
        waits.append(wait)
        turns.append(total)
    st = sr.report.stats
    assert np.mean(waits) == pytest.approx(
        cycles_to_us(st.mean_wait_cycles), rel=1e-6)
    assert np.mean(turns) == pytest.approx(
        cycles_to_us(st.mean_turnaround_cycles), rel=1e-6)

    # per-cluster rows reconcile with QueueStats.busy_cycles
    names = {e["tid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    busy_us = {}
    for e in evs:
        if e["ph"] == "X" and e.get("cat") == "task":
            row = names[e["tid"]]
            busy_us[row] = busy_us.get(row, 0.0) + e["dur"]
    for ci, busy in enumerate(st.busy_cycles):
        row = [n for n in busy_us if n.startswith(f"cluster{ci}:")]
        if busy > 0:
            assert len(row) == 1
            assert busy_us[row[0]] == pytest.approx(
                cycles_to_us(busy), rel=1e-9, abs=1e-6)

    # queue-depth counter track: starts +1, interleaves down to exactly 0
    depths = [e["args"]["queue_depth"] for e in evs
              if e["ph"] == "C" and e["name"] == "queue_depth"]
    assert len(depths) == 2 * len(sr.results)
    assert depths[-1] == 0.0
    assert max(depths) >= 1.0
    # one admission-window span per batch
    wins = [e for e in evs if e["ph"] == "X" and e.get("cat") == "serve"]
    assert len(wins) == sr.report.n_batches
    assert sum(w["args"]["n_requests"] for w in wins) == len(sr.results)


def test_serve_trace_measured_rows_reconcile(tmp_path):
    """Fast-tier measured run (1-cluster config on a 1-device mesh):
    the exported MEASURED rows must sum to the report's measured_busy_s
    and the modelled rows must still be present alongside."""
    from repro.launch.mesh import make_mesh

    cfg = cm.homogeneous_hybrid(math.inf)
    server = sc.ClusterServer(cfg, policy="lpt", batch_window_cycles=1e4)
    reqs = []
    for i in range(3):
        reqs.append(sc.Request(
            f"m{i}", "t0", Workload(f"w{i}", "serve", 32, 32, 32, 0.5, 0.5),
            arrival_cycles=i * 1e4, seed=i))
    sr = server.run_trace(reqs, execute=True, interpret=True, block=32,
                          mesh=make_mesh((1,), ("model",)),
                          pipeline_depth=2, measure=True)
    assert sr.timelines and sr.report.stats.measured_busy_s
    p = sr.export_chrome_trace(tmp_path / "measured.json")
    evs = json.loads(p.read_text())["traceEvents"]
    sub = [e for e in evs if e["ph"] == "X" and e.get("cat") == "submesh"]
    assert sub, "measured submesh rows missing"
    assert {e["pid"] for e in sub} == {obs.PID_MEASURED}
    total_busy_us = sum(e["dur"] for e in sub)
    assert total_busy_us == pytest.approx(
        sum(sr.report.stats.measured_busy_s) * 1e6, rel=1e-6)
    batches = [e for e in evs if e["ph"] == "X" and e.get("cat") == "batch"]
    assert len(batches) == len(sr.timelines)
    # virtual rows coexist on their own pid
    assert any(e["pid"] == obs.PID_VIRTUAL for e in evs
               if e["ph"] == "X")


def test_live_tracing_emits_executor_and_scheduler_events():
    """End-to-end live capture: serve on a mesh with tracing enabled and
    check the scheduler, admission, pipeline and measured re-emission all
    landed in the process tracer."""
    from repro.launch.mesh import make_mesh

    cfg = cm.homogeneous_hybrid(math.inf)
    server = sc.ClusterServer(cfg, policy="lpt", batch_window_cycles=1e4)
    obs.TRACE.reset()
    obs.enable()
    try:
        server.run_trace(
            [sc.Request(f"l{i}", "t0",
                        Workload(f"w{i}", "serve", 32, 32, 32, 1.0, 1.0),
                        arrival_cycles=i * 1e4, seed=i) for i in range(3)],
            execute=True, interpret=True, block=32,
            mesh=make_mesh((1,), ("model",)), pipeline_depth=2,
            measure=True)
    finally:
        obs.disable()
    evs = obs.TRACE.events()
    cats = {e.get("cat") for e in evs}
    assert {"scheduler", "task", "serve", "executor",
            "submesh"} <= cats
    names = {e["name"] for e in evs}
    assert {"offer", "dispatch", "queue_depth", "in_flight",
            "retire"} <= names
    doc = obs.TRACE.chrome_trace()
    json.dumps(doc)  # exportable
    # virtual and wall rows never share a pid (§8 timebase rule)
    by_pid = {e["pid"] for e in evs if e.get("cat") == "task"}
    assert by_pid == {obs.PID_VIRTUAL}
    assert {e["pid"] for e in evs if e.get("cat") == "executor"} \
        == {obs.PID_HOST}


# --------------------------------------------- disabled-path guarantees
def test_tracing_does_not_change_outputs():
    """Bit-identical contract: the same trace served with tracing on and
    off must produce identical schedules and reports."""
    def run():
        server = sc.ClusterServer(_config(), policy="optimized",
                                  batch_window_cycles=5e4,
                                  max_queue_depth=4)
        return server.run_trace(_requests(), execute=False)

    off = run()
    obs.enable()
    try:
        on = run()
    finally:
        obs.disable()
    assert sc.serve_result_to_json(off) == sc.serve_result_to_json(on)
    assert off.schedule.makespan_cycles == on.schedule.makespan_cycles
    for x, y in zip(off.schedule.assignments, on.schedule.assignments):
        assert (x.cluster, x.start_cycles, x.finish_cycles) \
            == (y.cluster, y.start_cycles, y.finish_cycles)


def test_disabled_overhead_within_factor_of_stubbed_baseline():
    """The scheduler hot loop with tracing disabled must stay within a
    small factor of a no-instrumentation baseline (hooks stubbed out).
    Generous bound: the CI gate proper lives in scripts/bench_check.py
    (obs/overhead row); this is the in-tree smoke of the same contract."""
    from repro.core import scheduler as sched

    cfg = _config()
    tasks = list(TABLE_I) * 2
    sched.schedule_many_kernels(cfg, tasks, policy="lpt")  # warm caches

    def drain():
        sched.schedule_many_kernels(cfg, tasks, policy="lpt")

    def median_us(fn, repeats=7):
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            ts.append((time.perf_counter() - t0) * 1e6)
        ts.sort()
        return ts[len(ts) // 2]

    hooks = ("_trace_offer", "_trace_place", "_trace_defer")
    saved = {h: getattr(sched, h) for h in hooks}
    try:
        for h in hooks:
            setattr(sched, h, lambda *a, **k: None)
        noop = median_us(drain)
    finally:
        for h in hooks:
            setattr(sched, h, saved[h])
    off = median_us(drain)
    assert not obs.enabled()
    assert off <= 3.0 * noop + 500.0, (
        f"tracing-disabled drain {off:.0f}us vs stubbed baseline "
        f"{noop:.0f}us")


# ------------------------------------------------- ELL conversion counters
def _ell_batch():
    """One 64x48x40 task split by hand over three partitions: an SpMM
    compressing B on the first 16 columns, and the other 24 columns
    K-split between an inner-product SpGEMM (A and B compressed) and a
    mirrored SpMM (A compressed)."""
    from repro.core.scheduler import (
        Partition, PlacedPartition, Region, TaskAssignment)

    w = Workload("hand", "test", 64, 48, 40, 0.2, 0.3)
    parts = (
        Partition(Region(0, 64, 0, 48, 0, 16), D.SPMM, 0),
        Partition(Region(0, 64, 0, 24, 16, 40), D.SPGEMM_INNER, 0),
        Partition(Region(0, 64, 24, 48, 16, 40), D.SPMM, 0, mirror=True),
    )
    asg = TaskAssignment(w, 0, D.SPMM, False, 0.0, 1.0, None, task_index=0,
                         placed=tuple(PlacedPartition(p, 0.0, 1.0)
                                      for p in parts))
    rng = np.random.default_rng(3)
    a = (rng.random((64, 48)) < 0.2) * rng.standard_normal((64, 48))
    b = (rng.random((48, 40)) < 0.3) * rng.standard_normal((48, 40))
    return asg, a.astype(np.float32), b.astype(np.float32)


@pytest.mark.parametrize("path", ["sequential", "sharded_packed",
                                  "sharded_replicated"])
def test_ell_counters_match_a_hand_count(path):
    """``executor.ell.conversions`` / ``executor.ell.dense_bytes`` count
    each conversion dispatched and the dense f32 slice it is handed: B's
    48x16, A's 64x24 and B's 24x24, A's 64x24 — 4 conversions, 17,664
    bytes — on the sequential path and on the sharded one (one device)."""
    from repro.core.hetero_matmul import execute_assignments
    from repro.launch.mesh import make_mesh

    asg, a, b = _ell_batch()
    kw = {}
    if path != "sequential":
        kw = dict(mesh=make_mesh((1,), ("model",)),
                  shard_operands=path == "sharded_packed")
    conv = obs.METRICS.counter("executor.ell.conversions")
    dense = obs.METRICS.counter("executor.ell.dense_bytes")
    c0, b0 = conv.value, dense.value
    out = execute_assignments([asg], {0: (a, b)},
                              cm.homogeneous_hybrid(math.inf),
                              interpret=True, block=16, **kw)[0]
    assert conv.value - c0 == 4
    assert dense.value - b0 == 4 * (48 * 16 + 64 * 24 + 24 * 24 + 64 * 24)
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-4)
