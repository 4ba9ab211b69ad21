"""The reduction from trace events to per-layer metrics.

Two inputs: a hand-made trace whose answers are worked out below, and a
small trace recorded on a TPU v5 lite (``data/recorded_trace.json``: two
``bench.serve`` calls of chem97ZtZ, journals and citeseer served as one
batch at published sizes, as :func:`trace_reduce.events_from_profile`
extracted them), whose numbers are checked against a brute-force count
over a 1 µs grid.
"""
import json
import math
import pathlib

import numpy as np
import pytest

import harness
import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns

KERNEL = 'custom-call(x), custom_call_target="tpu_custom_call"'
# Two devices; serve spans [0, 10) and [12, 20) ms, a wait in between.
HAND = {
    "ops": {
        "0": [[1 * MS, 3 * MS, "%f = fusion(x)"],
              [2 * MS, 4 * MS, "%expand = " + KERNEL],       # overlaps
              [13 * MS, 15 * MS, "%gemm = " + KERNEL]],
        "1": [[5 * MS, 6 * MS, "%sort.2 = sort(x)"]],
    },
    "host": [[0, 10 * MS, "bench.serve", "main"],
             [10 * MS, 12 * MS, "bench.wait_arrival", "main"],
             [12 * MS, 20 * MS, "bench.serve", "main"],
             [6 * MS, 9 * MS, "Execute", "main"]],
}


def _run(view, n=4, chips=2):
    reqs = [harness.Served(i, "k", 0, 0.0, 1.0, 2.0, True, 10**9, 10**6)
            for i in range(n)]
    return harness.RunRecord(reqs, 0.02, 0, {"flops_per_s": 1e12,
                                             "hbm_bytes_per_s": 1e9},
                             chips, view)


def test_hand_made_trace():
    view = trace_reduce.TraceView(HAND["ops"], HAND["host"])
    assert view.window_s == pytest.approx(0.020)
    # device 0 busy: [1,4) + [13,15) = 5 ms; device 1: 1 ms
    assert view.busy_seconds_per_device() == pytest.approx([0.005, 0.001])
    assert view.op_seconds("kernel") == pytest.approx(0.004)
    assert view.op_seconds("xla") == pytest.approx(0.003)
    # serving 18 ms; idle 13/18 on device 0, 17/18 on device 1
    assert view.idle_fraction_while_serving() == pytest.approx(
        (13 / 18 + 17 / 18) / 2)
    run = _run(view)
    read = lambda m: harness.load_reader(m)(run)  # noqa: E731
    assert read("kernel_ms_per_req.tput") == pytest.approx(1.0)
    assert read("prep_ms_per_req.lat") == pytest.approx(0.75)
    assert read("queue_wait_ms.lat") == pytest.approx(1e3)
    # least time per request: max(1e9/1e12, 1e6/1e9) = 1 ms; 4 of them
    # over 6 ms busy
    assert read("serve_roofline.tput") == pytest.approx(100 * 4 / 6)
    bd = view.breakdown()
    assert dict(bd["device_ops"]) == pytest.approx(
        {"%f = fusion(x)": 0.002, "%expand = " + KERNEL: 0.002,
         "%gemm = " + KERNEL: 0.002, "%sort.2 = sort(x)": 0.001})
    gaps = dict(bd["idle_gaps"])
    assert gaps["bench.serve > Execute"] == pytest.approx(0.003)
    assert gaps["bench.wait_arrival"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(0.015)


def test_no_device_ops_reads_nothing():
    view = trace_reduce.TraceView({"0": []}, HAND["host"])
    run = _run(view, chips=1)
    for m in ("kernel_ms_per_req.lat", "prep_ms_per_req.lat",
              "serve_roofline.lat", "device_idle_frac.lat"):
        assert harness.load_reader(m)(run) is None


def _grid_busy(ops, t0, t1):
    grid = np.zeros(int((t1 - t0) // 1000) + 1, bool)
    for s, e, *_ in ops:
        lo, hi = max(s, t0), min(e, t1)
        if hi > lo:
            grid[int((lo - t0) // 1000):int((hi - t0) // 1000)] = True
    return grid.sum() * 1e-6


@pytest.mark.skipif(not (DATA / "recorded_trace.json").exists(),
                    reason="no recorded trace committed")
def test_recorded_trace_against_brute_force():
    ev = json.loads((DATA / "recorded_trace.json").read_text())
    view = trace_reduce.TraceView(ev["ops"], ev["host"])
    assert view.has_device_ops
    for (dev, ops), busy in zip(ev["ops"].items(),
                                view.busy_seconds_per_device()):
        assert busy == pytest.approx(_grid_busy(ops, view.t0, view.t1),
                                     rel=0.02, abs=2e-5)
    kernels = view.op_seconds("kernel")
    others = view.op_seconds("xla")
    assert kernels > 0 and others > 0
    assert kernels + others >= view.busy_seconds_total() * 0.999
    idle = view.idle_fraction_while_serving()
    assert 0.0 <= idle < 1.0
    gaps = view.breakdown()["idle_gaps"]
    assert all(math.isfinite(v) and v > 0 for _, v in gaps)
