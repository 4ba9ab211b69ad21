"""Traces that carry the program's own spans (``serve.*``, ``exec.*``:
``repro.obs`` ``TRACE.span`` on the profiler's clock) next to the
benchmark's, as the trace reduction and the readers see them.

Two inputs: a hand-made serve call whose answers are worked out below,
and ``data/recorded_spans_trace.json``, recorded on a TPU v5 lite: the
batch of ``data/recorded_trace.json`` (chem97ZtZ, journals and citeseer
at published sizes) served three times by a program with its spans, as
:func:`trace_reduce.events_from_profile` extracts them, plus each
device's ``XLA Modules`` line under ``modules``, which the reduction
does not read yet.
"""
import json
import pathlib

import pytest

import harness
import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns
KERNEL = 'custom-call(x), custom_call_target="tpu_custom_call"'

# One serve call (times in ms) and its device operations; the host waits
# on the device inside ``exec.capacity_sync`` and after the merge.
BENCH_HOST = [(0, 20, "bench.serve")]
PROGRAM_HOST = [
    (0, 1, "serve.schedule"),
    (1, 10, "serve.execute"),
    (1, 10, "exec.request"),
    (1, 2, "exec.slice"),
    (1.1, 1.3, "PjitFunction(dynamic_slice)"),
    (2, 4, "exec.capacity_sync"),
    (4, 5, "exec.ell"),
    (4.1, 4.3, "PjitFunction(argsort)"),
    (5, 6, "exec.kernel"),
    (6, 7, "exec.merge"),
]
OPS = [(0.5, 1.5, "%slice = slice(x)"), (4.5, 6.0, "%sort = sort(x)"),
       (8.0, 12.0, "%gemm = " + KERNEL), (13.0, 19.0, "%add = add(x)")]


def _view(program=True):
    host = BENCH_HOST + (PROGRAM_HOST if program else [])
    return trace_reduce.TraceView(
        {"0": [[a * MS, b * MS, n] for a, b, n in OPS]},
        [[a * MS, b * MS, n, "main"] for a, b, n in host])


def _run(view, n=4):
    reqs = [harness.Served(i, "k", 0, 0.0, 1.0, 2.0, True, 10**9, 10**6)
            for i in range(n)]
    return harness.RunRecord(reqs, 0.02, 0, {"flops_per_s": 1e12,
                                             "hbm_bytes_per_s": 1e9},
                             1, view)


@pytest.mark.parametrize("metric", ["schedule_ms_per_call.lat",
                                    "schedule_ms_per_call.tput"])
def test_schedule_ms_per_call(metric):
    assert harness.load_reader(metric)(_run(_view())) == pytest.approx(1.0)


@pytest.mark.parametrize("view", [None, _view(program=False)],
                         ids=["untraced", "without_program_spans"])
def test_schedule_ms_per_call_reads_nothing_without_its_span(view):
    assert harness.load_reader("schedule_ms_per_call.lat")(_run(view)) \
        is None


@pytest.mark.parametrize("metric", [
    "kernel_ms_per_req.lat", "prep_ms_per_req.lat", "device_idle_frac.lat",
    "serve_roofline.lat", "compiles_in_window.lat", "queue_wait_ms.lat"])
def test_program_spans_leave_existing_metrics_as_they_were(metric):
    read = harness.load_reader(metric)
    assert read(_run(_view())) == read(_run(_view(program=False)))


def test_idle_gaps_name_the_innermost_program_span():
    gaps = dict(_view().breakdown()["idle_gaps"])
    # device idle: [0, 0.5), [1.5, 4.5), [6, 8), [12, 13), [19, 20) ms
    assert gaps == pytest.approx({
        "bench.serve > serve.schedule": 0.0005,
        "bench.serve > exec.slice": 0.0005,
        "bench.serve > exec.capacity_sync": 0.002,
        "bench.serve > exec.ell": 0.0003,
        "bench.serve > PjitFunction(argsort)": 0.0002,
        "bench.serve > exec.merge": 0.001,
        "bench.serve > exec.request": 0.001,
        "bench.serve": 0.002})


def test_recorded_spans_trace():
    ev = json.loads((DATA / "recorded_spans_trace.json").read_text())
    view = trace_reduce.TraceView(ev["ops"], ev["host"])
    calls = [h for h in ev["host"] if h[2] == "bench.serve"]
    schedule = [h[1] - h[0] for h in ev["host"] if h[2] == "serve.schedule"]
    assert len(calls) == len(schedule) == 3
    run = _run(view, n=9)            # three calls of three requests
    assert harness.load_reader("schedule_ms_per_call.lat")(run) \
        == pytest.approx(1e-6 * sum(schedule) / 3)
    gaps = dict(view.breakdown()["idle_gaps"])
    assert gaps["bench.serve > exec.ell"] > 0
