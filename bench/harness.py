"""One benchmark run of one cell: resolve the cell's files by name, build
the accelerator and the operands from the seed, warm up, measure a
window, check the outputs against the plain reference, and report.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* configuration ``<c>``: the file its ``configs`` entry names;
* traffic ``<t>``: ``bench/traffic/<t>.json``, whose ``kind`` names the
  arrival process ``bench/traffic/<kind>.py`` that plans, warms up and
  drives the window (:mod:`arrivals`);
* per-layer metric ``<base>[.<suffix>]``: ``bench/metrics/<base>.py``,
  whose ``read(run)`` returns a number or ``None``.

The program under test is entered through
``ClusterServer(config, policy).run_trace(requests, operands=...,
execute=True, mesh=...)``; nothing else of it is called in the window.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import statistics
import sys
import tempfile
import time
import traceback
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
METRICS_DIR = BENCH / "metrics"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

import arrivals  # noqa: E402  (bench/ is on sys.path)
import work  # noqa: E402


# ------------------------------------------------------------ resolution
@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: Tuple[Dict, ...]
    per_layer: Tuple[Dict, ...]


def load_benchmark(path: pathlib.Path = REPO / "BENCHMARK.json") -> Dict:
    return json.loads(path.read_text())


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def reader_path(metric_name: str) -> pathlib.Path:
    """``bench/metrics/<base>.py`` for ``<base>[.<suffix>]``: one reader
    serves a quantity under every suffix that splits it by the end-to-end
    metric it moves."""
    return METRICS_DIR / f"{metric_name.split('.', 1)[0]}.py"


def resolve(cell: str, bench: Optional[Dict] = None) -> Cell:
    """The cell named ``cell``, with every file it needs read."""
    bench = load_benchmark() if bench is None else bench
    entries = [w for w in bench["workloads"] if w["name"] == cell]
    if len(entries) != 1:
        raise KeyError(f"BENCHMARK.json has no single cell {cell!r}; cells: "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = entries[0]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((REPO / cfg_entry["file"]).read_text())
    per_layer = tuple(m for m in bench["per_layer"] if _applies(m, cell))
    for m in per_layer:
        if not reader_path(m["name"]).is_file():
            raise FileNotFoundError(f"metric {m['name']}: no reader "
                                    f"{reader_path(m['name'])}")
    return Cell(cell, int(w["chips"]), config,
                arrivals.load_traffic(w["traffic"]),
                tuple(m for m in bench["end_to_end"] if _applies(m, cell)),
                per_layer)


def load_reader(metric_name: str) -> Callable:
    path = reader_path(metric_name)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------- the system
def accelerator(config: Dict):
    """The configuration's accelerator, built from its data (no search)."""
    from repro.core import costmodel as cm
    from repro.formats.taxonomy import DataflowClass

    acc = config["accelerator"]
    clusters = tuple(
        cm.ClusterSpec(c["name"],
                       tuple(DataflowClass(x) for x in c["classes"]),
                       int(c["pes"]), float(c["area_mm2_per_pe"]),
                       float(c["power_mw_per_pe"]))
        for c in acc["clusters"])
    return cm.AcceleratorConfig(acc["name"], clusters, float(acc["hbm_bw"]),
                                float(acc["scratchpad_bytes"]))


def workload(kernel: Dict):
    from repro.core.workloads import Workload

    return Workload(kernel["name"], kernel["application"], int(kernel["m"]),
                    int(kernel["k"]), int(kernel["n"]), float(kernel["d_mk"]),
                    float(kernel["d_kn"]))


def seed_key(seed: int):
    """A PRNG key that depends on every bit of ``seed`` (JAX's own seeding
    keeps only the low 32 bits of a large integer)."""
    import jax

    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


@dataclasses.dataclass
class Operands:
    """Every (kernel, operand set) pair, on the device, and its work."""

    arrays: Dict[Tuple[str, int], Tuple[object, object]]
    work: Dict[Tuple[str, int], Tuple[int, int]]   # (flops, bytes)


def make_operands(config: Dict, sets: int, seed: int, device) -> Operands:
    """Every operand of the pool in one jitted call on ``device``, where
    they stay: a request's operands wait in device memory until it is
    served, as the accelerator's operands wait in its HBM. (Waiting in
    host memory, the copy to the device moved a whole run's latency by
    several per cent from process to process.) The work counts come from
    the same call (nonzeros per column of A and per row of B).

    The values come from the seed. The sparsity pattern of each (kernel,
    operand set) comes from the configuration's ``pattern_seed``: Table
    I's matrices are fixed data sets, and the executor's ELL conversion
    takes a time that depends on the pattern, so a pattern drawn from the
    seed changed the work from seed to seed."""
    import jax
    import jax.numpy as jnp

    kernels = config["kernels"]
    pairs = [(k, s) for s in range(sets) for k in range(len(kernels))]

    def gen(key, pattern):
        out = []
        for i, (k, s) in enumerate(pairs):
            kd = kernels[k]
            m, kk, n = int(kd["m"]), int(kd["k"]), int(kd["n"])
            ka, kb = jax.random.split(jax.random.fold_in(key, i))
            ma, mb = jax.random.split(jax.random.fold_in(pattern, i))
            a = jax.random.normal(ka, (m, kk), jnp.float32) * (
                jax.random.uniform(ma, (m, kk)) < float(kd["d_mk"]))
            b = jax.random.normal(kb, (kk, n), jnp.float32) * (
                jax.random.uniform(mb, (kk, n)) < float(kd["d_kn"]))
            out.append((a, b, jnp.sum(a != 0, axis=0, dtype=jnp.int32),
                        jnp.sum(b != 0, axis=1, dtype=jnp.int32)))
        return out

    with jax.default_device(device):
        dev = jax.jit(gen)(seed_key(seed),
                           seed_key(int(config["pattern_seed"])))
        nnz = jax.device_get([(ca, rb) for _, _, ca, rb in dev])
    mats = [(a, b) for a, b, _, _ in dev]
    del dev
    arrays, counts = {}, {}
    for (k, s), (a, b), (ca, rb) in zip(pairs, mats, nnz):
        name = kernels[k]["name"]
        arrays[(name, s)] = (a, b)
        counts[(name, s)] = work.request_work(ca, rb, a.shape[0], b.shape[1])
    return Operands(arrays, counts)


# ------------------------------------------------------------- records
@dataclasses.dataclass
class Served:
    """One request's life in the window (seconds from the window's start,
    host clock)."""

    index: int
    kernel: str
    operand_set: int
    due_s: float
    start_s: float = math.nan
    done_s: float = math.nan
    answered: bool = False
    flops: int = 0
    bytes: int = 0


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader gets: the window's requests, its length,
    the compiles inside it, the device's peaks and, in a traced run, the
    reduced trace (:class:`trace_reduce.TraceView`)."""

    requests: List[Served]
    window_s: float
    compiles_in_window: int
    peaks: Dict
    chips: int
    trace: Optional[object] = None

    @property
    def completed(self) -> List[Served]:
        return [r for r in self.requests if r.answered]


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Server:
    """The client side of the program's entry: one ``ClusterServer``,
    fed one batch per call."""

    def __init__(self, config: Dict, operands: Operands, mesh):
        from repro.serve.cluster import ClusterServer

        self.server = ClusterServer(accelerator(config),
                                    policy=config["policy"])
        self.workloads = {k["name"]: workload(k) for k in config["kernels"]}
        self.operands = operands
        self.mesh = mesh

    def _requests(self, names: Sequence[str], ids: Sequence[int]):
        from repro.serve.cluster import Request

        return [Request(f"r{i:07d}", "bench", self.workloads[k], 0.0)
                for k, i in zip(names, ids)]

    def placement(self, names: Sequence[str]) -> List[Tuple]:
        """How the program places each request of a batch of ``names``,
        scheduled on the host only: ``(kernel, partitions)`` per request,
        each partition as (class, mirror, region)."""
        sr = self.server.run_trace(
            self._requests(names, range(len(names))), execute=False)
        out = []
        for res in sr.results:
            parts = sorted(
                (p.cls.value, p.mirror, (p.region.m0, p.region.m1,
                                         p.region.k0, p.region.k1,
                                         p.region.n0, p.region.n1))
                for p in (pp.partition for pp in res.assignment.placed)
                if not p.region.empty)
            out.append((res.request.workload.name, tuple(parts)))
        return out

    def serve(self, batch: Sequence[arrivals.Arrival]) -> Dict[int, object]:
        """Serve ``batch`` as one admitted batch; ``{index: output}``, the
        outputs as the program returns them (not waited for)."""
        reqs = self._requests([a.kernel for a in batch],
                              [a.index for a in batch])
        ops = {r.request_id: self.operands.arrays[(a.kernel, a.operand_set)]
               for r, a in zip(reqs, batch)}
        kw = {} if self.mesh is None else {"mesh": self.mesh}
        sr = self.server.run_trace(reqs, operands=ops, execute=True, **kw)
        return {int(res.request.request_id[1:]): res.output
                for res in sr.results}


def _answered(out, kernel: Dict) -> bool:
    return out is not None and tuple(out.shape) == (int(kernel["m"]),
                                                     int(kernel["n"]))


# ---------------------------------------------------------- the window
#: How often a batch's outputs are polled for readiness: well under the
#: host clock's own error of about half a millisecond.
POLL_S = 0.0002


class Window:
    """One measured window: the clock, the records, the sampled outputs.

    A traffic kind's ``drive`` calls :meth:`serve` for every batch it
    sends, and :meth:`annotate` around whatever else it waits for."""

    def __init__(self, server: Server, config: Dict, keep: Callable):
        self.server = server
        self.kernels = {k["name"]: k for k in config["kernels"]}
        self.keep = keep
        self.records: List[Served] = []
        self.kept: Dict[int, object] = {}
        self.last: Dict[int, object] = {}
        self.errors: List[str] = []
        self.batch_sizes: List[int] = []
        self.serving_s = 0.0
        self.t0 = time.perf_counter()

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    annotate = staticmethod(_annotate)

    def serve(self, batch: Sequence[arrivals.Arrival],
              due: Sequence[float]) -> None:
        """Serve ``batch`` in one call; each request is done when its own
        output is ready on the device, seen by polling every output of
        the batch (the program may run them in any order)."""
        import jax

        recs = [Served(a.index, a.kernel, a.operand_set, d)
                for a, d in zip(batch, due)]
        self.records += recs
        self.batch_sizes.append(len(batch))
        self.last = {}
        start = self.clock()
        with _annotate("bench.serve"):
            try:
                outs = self.server.serve(batch)
            except Exception:  # a failed serve call fails its requests
                self.errors.append(traceback.format_exc())
                outs = {}
            pending = []
            for a, r in zip(batch, recs):
                r.start_s = start
                pending.append((a, r, outs.get(a.index)))
            while pending:
                waiting = []
                for a, r, out in pending:
                    if out is not None and not out.is_ready():
                        waiting.append((a, r, out))
                        continue
                    if out is not None:
                        try:
                            jax.block_until_ready(out)
                        except Exception:
                            self.errors.append(traceback.format_exc())
                            out = None
                    r.done_s = self.clock()
                    r.answered = _answered(out, self.kernels[a.kernel])
                    if r.answered:
                        self.last[a.index] = out
                        if self.keep(a):
                            self.kept[a.index] = out
                pending = waiting
                if pending:
                    time.sleep(POLL_S)
        self.serving_s += self.clock() - start


# ------------------------------------------------------------ the run
def _percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile by linear interpolation between closest
    ranks (numpy's default), of every value given."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def end_to_end_values(names: Sequence[str], records: Sequence[Served],
                      window_s: float, setup_s: float) -> Dict[str, float]:
    lat_ms = [1e3 * (r.done_s - r.due_s) for r in records]
    done = sum(r.answered for r in records)
    table = {
        "setup_s": lambda: setup_s,
        "latency_p50_ms": lambda: _percentile(lat_ms, 50),
        "latency_p90_ms": lambda: _percentile(lat_ms, 90),
        "served_rps": lambda: done / window_s,
    }
    return {n: table[n]() for n in names}


#: Share of the window's requests whose outputs the check compares,
#: besides the first of each kernel and the last batch.
SAMPLE_FRACTION = 0.1


def keep_rule(seed: int) -> Callable:
    """Which outputs of the window are compared: a share drawn from the
    seed by each request's index, and the first request of each kernel
    (so the longest kernel is always among them)."""
    seen = set()

    def keep(a: arrivals.Arrival) -> bool:
        first = a.kernel not in seen
        seen.add(a.kernel)
        draw = np.random.default_rng([seed, 0x5A11, a.index]).random()
        return first or draw < SAMPLE_FRACTION
    return keep


def _device_record(devices, peak: int) -> Dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def check_outputs(kept: Dict[int, object], records: Sequence[Served],
                  operands: Operands, limit: float, device) -> Dict:
    """Compare every kept output with the plain reference; every request
    due in the window must have been answered."""
    import reference

    by_index = {r.index: r for r in records}
    gaps = {}
    for idx in sorted(kept):
        r = by_index[idx]
        a, b = operands.arrays[(r.kernel, r.operand_set)]
        gap = reference.gap_eps(kept[idx], a, b, device)
        gaps[r.kernel] = max(gaps.get(r.kernel, 0.0), gap)
    unanswered = sum(not r.answered for r in records)
    worst = max(gaps.values()) if gaps else math.inf
    return {"gap_eps": worst, "gap_eps_by_kernel": gaps,
            "compared": len(kept), "unanswered": unanswered,
            "correct": bool(unanswered == 0 and worst <= limit)}


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        process_start: float, devices, peaks: Dict, control: bool = False,
        err=sys.stderr) -> Dict:
    """One run of ``cell``; returns the result line's object.

    ``control`` (not reachable from the command line) also reads the
    control: the reference one precision step down put in the program's
    place for every output compared (``bench/readings.py``)."""
    import jax

    compiles: List[float] = []

    def on_duration(name, secs, **_):
        if name == BACKEND_COMPILE_EVENT:
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        return _run(cell, seed, seconds, trace, process_start, devices,
                    peaks, control, err, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _run(cell, seed, seconds, trace, process_start, devices, peaks,
         control, err, compiles) -> Dict:
    import jax

    config, traffic = cell.config, cell.traffic
    limit = config["check"]["gap_eps_limit"]
    kernel_names = [k["name"] for k in config["kernels"]]
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((cell.chips,), ("model",))

    operands = make_operands(config, int(traffic["operand_sets"]), seed,
                             devices[0])
    server = Server(config, operands, mesh)
    kind = arrivals.kind_module(traffic["kind"])
    cost = {k["name"]: float(k["m"]) * float(k["k"]) * float(k["n"])
            for k in config["kernels"]}
    warm = kind.warmup(kernel_names, traffic, server.placement, cost)
    for batch in warm:
        outs = server.serve(batch)
        jax.block_until_ready([o for o in outs.values() if o is not None])
    plan = kind.plan(kernel_names, traffic, seed, seconds, cost)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    n_compiles0 = len(compiles)
    window = Window(server, config, keep_rule(seed))
    setup_s = time.perf_counter() - process_start
    window_s = kind.drive(window, plan, seconds)
    window.kept.update(window.last)   # the last batch is compared too
    compiles_in_window = len(compiles) - n_compiles0
    if trace:
        jax.profiler.stop_trace()
    peak = _memory_peak(devices)
    del server, window.server     # the program's state goes before the check
    records = window.records
    for r in records:
        r.flops, r.bytes = operands.work[(r.kernel, r.operand_set)]
    checks = check_outputs(window.kept, records, operands, limit, devices[0])
    if control:
        import reference

        stand_in, made = {}, {}
        by_index = {r.index: r for r in records}
        for idx in window.kept:
            key = (by_index[idx].kernel, by_index[idx].operand_set)
            if key not in made:
                made[key] = reference.control_product(
                    *operands.arrays[key], devices[0])
            stand_in[idx] = made[key]
        checks["control_gap_eps"] = check_outputs(
            stand_in, records, operands, limit, devices[0])["gap_eps"]
    for e in window.errors:
        print(e, file=err)

    result = {"correct": checks["correct"], "attempted": len(records),
              "failed": checks["unanswered"]}
    device = _device_record(devices, peak)
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": v, "unit": m["unit"]}
            for m, v in zip(cell.end_to_end, end_to_end_values(
                [m["name"] for m in cell.end_to_end], records, window_s,
                setup_s).values())}
    else:
        import trace_reduce

        view = trace_reduce.load(trace_dir, devices)
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec = RunRecord(records, window_s, compiles_in_window, peaks,
                        cell.chips, view)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = view.busy_s_mean()
        device["window_s"] = view.window_s
        result["breakdown"] = view.breakdown()
    result["window"] = {"seconds": window_s, "compiles": compiles_in_window,
                        "setup_s": setup_s, "warm_batches": len(warm),
                        "serve_calls": len(window.batch_sizes),
                        "batch_max": max(window.batch_sizes, default=0),
                        "serving_s": window.serving_s,
                        "service_ms": service_ms(records)}
    result["device"] = device
    result["checks"] = {
        "gap_eps": {"value": _finite(checks["gap_eps"]), "limit": limit},
        "unanswered": {"value": checks["unanswered"], "limit": 0},
        "compared": checks["compared"],
        "gap_eps_by_kernel": {k: _finite(v) for k, v in
                              checks["gap_eps_by_kernel"].items()},
    }
    if control:
        result["checks"]["control_gap_eps"] = _finite(
            checks["control_gap_eps"])
    for name in ("gap_eps", "unanswered"):
        c = result["checks"][name]
        print(f"check {name}={c['value']} limit={c['limit']}", file=err)
    return result


def service_ms(records: Sequence[Served]) -> Dict[str, float]:
    """Median, per kernel, of serve-call start to output ready, in ms."""
    by: Dict[str, List[float]] = {}
    for r in records:
        if r.answered:
            by.setdefault(r.kernel, []).append(1e3 * (r.done_s - r.start_s))
    return {k: statistics.median(v) for k, v in sorted(by.items())}


def _finite(x: float):
    return x if math.isfinite(x) else "inf"
