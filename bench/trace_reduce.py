"""Reduce a JAX profiler trace of the measured window to the quantities
the per-layer readers need.

The trace is the profiler's own ``.xplane.pb``, read with
``jax.profiler.ProfileData``. Two kinds of events matter:

* device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane, each named by its HLO instruction. An operation is a kernel when
  that instruction is a custom call to ``tpu_custom_call`` (how Mosaic
  kernels reach XLA), whatever the kernel is called;
* the benchmark's host spans (``bench.*`` ``TraceAnnotation`` events) on
  the host plane, on the same clock as the device events.

The traced window runs from the first benchmark span's start to the last
one's end. :func:`events_from_profile` turns a trace into plain lists,
which :class:`TraceView` reduces; the reduction is checked on a small
recorded trace in ``bench/tests``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import math
import os
import re
from typing import Dict, List, Sequence

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
SHORT_GAP_NS = 100_000
OP_LABEL_CHARS = 160


def is_kernel(op: str) -> bool:
    """Whether a device operation is a Mosaic kernel: its HLO instruction
    (the event's name in the trace) is a custom call to the TPU custom-call
    target, whatever the kernel is called."""
    return 'custom_call_target="tpu_custom_call"' in op


def events_from_profile(path: str, device_ids: Sequence[int]) -> Dict:
    """Plain lists from an ``.xplane.pb``: ``{"ops": {device: [[start_ns,
    end_ns, hlo_instruction], ...]}, "host": [[start_ns, end_ns, name,
    thread], ...]}``; host events are those of the threads that carry a
    benchmark span."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops: Dict[int, List] = {d: [] for d in device_ids}
    host: List = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in ops:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops[int(m.group(1))].append(
                        [float(e.start_ns), float(e.end_ns), e.name])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [[float(e.start_ns), float(e.end_ns), e.name, line.name]
                       for e in line.events]
                if any(e[2].startswith(SPAN_PREFIX) for e in evs):
                    host += evs
    return {"ops": {str(d): v for d, v in ops.items()}, "host": host}


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``[[start, end], ...]`` into disjoint sorted intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def _intersect_total(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint sorted sets."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            tot += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return tot


@dataclasses.dataclass
class TraceView:
    """The window's device operations and benchmark spans."""

    ops: Dict[str, List]       # device -> [[start_ns, end_ns, hlo_text]]
    host: List                 # [[start_ns, end_ns, name, thread]]

    def __post_init__(self):
        spans = [h for h in self.host if h[2].startswith(SPAN_PREFIX)]
        self.spans = spans
        if spans:
            self.t0 = min(s[0] for s in spans)
            self.t1 = max(s[1] for s in spans)
        else:
            self.t0 = self.t1 = 0.0
        self._busy = {
            d: _union(_clip(np.asarray([[o[0], o[1]] for o in v]
                                       or np.zeros((0, 2))), self.t0,
                            self.t1))
            for d, v in self.ops.items()}
        self._serving = _union(np.asarray(
            [[s[0], s[1]] for s in spans if s[2] == "bench.serve"]
            or np.zeros((0, 2))))

    @property
    def has_device_ops(self) -> bool:
        return any(len(v) for v in self.ops.values()) and self.t1 > self.t0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _in_window(self):
        for d, v in self.ops.items():
            for o in v:
                if o[0] >= self.t0 and o[1] <= self.t1:
                    yield d, o

    def op_seconds(self, kind: str) -> float:
        """Device seconds of ``kind`` ("kernel" or "xla") ops in the
        window, summed over devices."""
        want = kind == "kernel"
        return 1e-9 * sum(o[1] - o[0] for _, o in self._in_window()
                          if is_kernel(o[2]) == want)

    def busy_seconds_per_device(self) -> List[float]:
        return [1e-9 * float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0
                for iv in self._busy.values()]

    def busy_seconds_total(self) -> float:
        return sum(self.busy_seconds_per_device())

    def busy_s_mean(self) -> float:
        b = self.busy_seconds_per_device()
        return sum(b) / len(b)

    def idle_fraction_while_serving(self) -> float:
        serving = float(np.sum(self._serving[:, 1] - self._serving[:, 0]))
        if serving <= 0:
            return None
        fr = [1.0 - _intersect_total(iv, self._serving) / serving
              for iv in self._busy.values()]
        return sum(fr) / len(fr)

    def _host_segments(self):
        """What the host was doing, as change points ``(t, label)``: the
        outermost benchmark span and the innermost host event within it,
        on the thread that carries the benchmark's spans (events on one
        thread nest)."""
        threads = collections.Counter(s[3] for s in self.spans)
        if not threads:
            return [(self.t0, "outside any benchmark span")]
        thread = threads.most_common(1)[0][0]
        evs = sorted((h for h in self.host if h[3] == thread),
                     key=lambda h: (h[0], -h[1]))

        def label(stack):
            span = next((h[2] for h in stack
                         if h[2].startswith(SPAN_PREFIX)), None)
            if span is None:
                return "outside any benchmark span"
            inner = stack[-1][2]
            return span if inner.startswith(SPAN_PREFIX) else \
                f"{span} > {inner}"

        segs = [(-math.inf, "outside any benchmark span")]
        stack: List = []
        for e in evs:
            while stack and stack[-1][1] <= e[0]:
                t = stack.pop()[1]
                segs.append((t, label(stack)))
            stack.append(e)
            segs.append((e[0], label(stack)))
        while stack:
            t = stack.pop()[1]
            segs.append((t, label(stack)))
        return segs

    def breakdown(self, top: int = 10) -> Dict:
        """The device operations that took most time (summed over
        devices, by name) and the idle time between device operations
        on the first device, split by what the host was doing."""
        by_name: Dict[str, float] = collections.defaultdict(float)
        for _, o in self._in_window():
            by_name[o[2][:OP_LABEL_CHARS]] += 1e-9 * (o[1] - o[0])
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]

        segs = self._host_segments()
        times = np.asarray([t for t, _ in segs])
        gaps: Dict[str, float] = collections.defaultdict(float)
        first = next(iter(self._busy.values()), np.zeros((0, 2)))
        edges = [self.t0] + [x for iv in first for x in iv] + [self.t1]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi <= lo:
                continue
            if hi - lo < SHORT_GAP_NS:
                gaps["gaps under 100 us between device ops"] += \
                    1e-9 * (hi - lo)
                continue
            i = int(np.searchsorted(times, lo, side="right")) - 1
            t = lo
            while t < hi:
                nxt = times[i + 1] if i + 1 < len(times) else math.inf
                piece = min(nxt, hi) - t
                if piece > 0:
                    gaps[segs[i][1]] += 1e-9 * piece
                t = min(nxt, hi)
                i += 1
        idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def load(trace_dir: str, devices) -> TraceView:
    """The :class:`TraceView` of the one trace under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir},"
                                f" found {len(files)}")
    ev = events_from_profile(files[0], [d.id for d in devices])
    return TraceView(ev["ops"], ev["host"])

