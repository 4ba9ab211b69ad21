"""Sharded cluster-submesh executor (DESIGN.md §6).

The paper's core claim is *spatial* heterogeneity: AESPA's clusters are
independent blocks that run concurrently, each on its own slice of the
chip. On the JAX substrate that story maps onto a device mesh:
:func:`repro.core.hetero_matmul.cluster_submeshes` assigns every cluster a
contiguous sub-slice of the mesh "model" axis proportional to its PE
share, and this module drives ``shard_map`` SPMD programs in which each
device executes exactly the partition queue of the cluster that owns it —
clusters execute concurrently, the way the silicon would.

How the one-program-many-queues trick works (§6 contract):

* **Operand placement (default, ``shard_operands=True``).** Each job's
  operand slices are packed host-side into per-device flat payloads —
  every partition's ``a``/``b`` slice lands only in the payload row of the
  device that executes it (the owning cluster's span, §6 round-robin
  rule) — and the payload enters the program sharded along the mesh axis
  (``in_specs=P(axis)``), so a batch's resident working set per device is
  O(batch bytes / devices) instead of a full replica. Static capacities
  are derived on the HOST (numpy twin of ``prepare_partitions``, same
  strict cap >= measured-need contract), so dispatch never syncs on the
  device stream — the property the pipelined driver below depends on.
* **Legacy replicated mode (``shard_operands=False``).** Operands enter
  replicated (``in_specs=P()``) and each branch slices regions from the
  full operands — the pre-pipelining PR-5 program, kept as the benchmark
  baseline and bit-compatible fallback.
* Each device's work is selected with ``lax.switch`` on
  ``lax.axis_index(axis)``: branch ``d`` converts, dispatches and locally
  scatter-adds the partitions assigned to device ``d`` into full-size
  per-task buffers (zeros for tasks the device doesn't touch). Within a
  cluster, partitions round-robin across the cluster's device span in
  dispatch order.
* A single ``psum`` over the axis merges everything: M/N-split partials
  land in disjoint tiles, K-split partials (including the ``optimized``
  policy's cross-cluster straggler splits) accumulate — the same
  scatter-add tile merge as the sequential executor, now crossing
  sub-mesh boundaries through the reduction. In ``measure=True`` mode the
  program instead emits per-device partials plus a per-device completion
  token (no collective, so each span's token is ready the moment that
  span's compute finishes); the merge runs as a follow-up reduction and
  the retire step fences token shards at span granularity to produce
  wall-clock :class:`SpanTiming` entries.

**Pipelined batch execution** (:func:`execute_job_batches_sharded`):
admitted batches become a stream of programs with at most
``pipeline_depth`` in flight. Dispatch is pure host work (numpy packing,
host capacities, program-cache lookup) plus asynchronous ``device_put``
and an asynchronous compiled call, so batch N+1's transfers, tracing and
compilation overlap batch N's device compute; payload buffers are donated
to the runtime (``donate_argnums``) so steady-state memory is bounded by
the pipeline depth. ``pipeline_depth=1`` retires each batch before
dispatching the next — today's serialized behavior, bit-compatible.

Compiled programs are cached on the mesh *fingerprint* (device ids, axis
names, mesh shape) plus the static batch structure — never on the ``Mesh``
object — so equal-but-distinct meshes (e.g. one rebuilt per ``serve()``
call) share compiles (:func:`program_cache_info` exposes hit/miss
counters; regression-tested in ``tests/test_scheduler.py``).

Single-device equivalence: ``mesh=None`` anywhere in the executor API is
the sequential path, untouched; a sharded run is numerically equal to it
(same kernels, same capacities; summation order across sub-meshes may
differ, so equality is allclose at dtype precision — pinned by
``tests/test_sharded_exec.py`` under ``XLA_FLAGS=
--xla_force_host_platform_device_count=8``, the same forced-host-device
trick ``tests/test_sharded.py`` uses).
"""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs as _obs
from repro.core import costmodel as cm
from repro.core.hetero_matmul import (
    _compressed_operands,
    _dispatch_partition,
    _prep_operands,
    cluster_submeshes,
    prepare_partitions,
)
from repro.core.scheduler import KernelSchedule
from repro.formats.ell import bucket_capacity
from repro.launch.mesh import axis_sizes
from repro.obs import trace as _trace_mod

import contextlib


@contextlib.contextmanager
def _quiet_donation():
    # Payloads are donated so the runtime can recycle them between
    # pipelined batches; XLA warns when a donated buffer finds no
    # aliasable output (payload and output shapes rarely match) —
    # expected here, not a bug.
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def _axis_size(mesh, axis: str) -> int:
    sizes = axis_sizes(mesh)
    if axis not in sizes:
        raise ValueError(
            f"mesh has no {axis!r} axis (axes: {mesh.axis_names}); the "
            "sharded executor slices clusters along one named mesh axis")
    return sizes[axis]


def device_for_partition(spans, counters, cluster: int) -> int:
    """§6 device-span assignment rule: partition ``i`` (in dispatch order)
    of cluster ``c`` runs on device ``lo_c + (i mod (hi_c - lo_c))`` — the
    cluster's queue round-robins across its own contiguous span.
    ``counters`` is the mutable per-cluster dispatch counter."""
    _, lo, hi = spans[cluster]
    d = lo + counters.get(cluster, 0) % (hi - lo)
    counters[cluster] = counters.get(cluster, 0) + 1
    return d


# ------------------------------------------------------------ program cache
def _mesh_fingerprint(mesh) -> Tuple:
    """Value identity of a mesh: device ids + axis names + shape. Two
    equal-but-distinct ``Mesh`` objects (e.g. rebuilt per ``serve()``
    call) share this fingerprint — and therefore compiled programs."""
    return (tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names), tuple(mesh.devices.shape))


_PROGRAM_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_PROGRAM_CACHE_MAX = 128
_cache_hits = 0
_cache_misses = 0

# Registry twins of the module counters (obs.METRICS.snapshot() carries
# them without importing this module's globals); the in-flight gauge is
# sampled by the pipelined driver below.
_MET_CACHE_HITS = _obs.METRICS.counter("executor.program_cache.hits")
_MET_CACHE_MISSES = _obs.METRICS.counter("executor.program_cache.misses")
_MET_INFLIGHT = _obs.METRICS.gauge("executor.pipeline.in_flight")


def program_cache_info() -> Dict[str, int]:
    """Hit/miss/size counters of the compiled-program cache (keyed on the
    mesh fingerprint + static batch structure, never the Mesh object)."""
    return {"hits": _cache_hits, "misses": _cache_misses,
            "size": len(_PROGRAM_CACHE)}


def program_cache_clear() -> None:
    _PROGRAM_CACHE.clear()


def program_cache_reset() -> None:
    """Zero the hit/miss counters (and their registry twins) *and* drop
    the cached programs — tests and benchmarks call this so cache stats
    can't leak across measurements (the counters previously had no reset
    and accumulated for the life of the process)."""
    global _cache_hits, _cache_misses
    _cache_hits = 0
    _cache_misses = 0
    _MET_CACHE_HITS.reset()
    _MET_CACHE_MISSES.reset()
    _PROGRAM_CACHE.clear()


_obs.METRICS.register_callback("executor.program_cache", program_cache_info)


def _cached_program(key, build):
    global _cache_hits, _cache_misses
    fn = _PROGRAM_CACHE.get(key)
    if fn is not None:
        _cache_hits += 1
        _MET_CACHE_HITS.inc()
        _PROGRAM_CACHE.move_to_end(key)
        return fn
    _cache_misses += 1
    _MET_CACHE_MISSES.inc()
    fn = build()
    _PROGRAM_CACHE[key] = fn
    if len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
        _PROGRAM_CACHE.popitem(last=False)
    return fn


# -------------------------------------------------- host-side operand prep
def _host_capacities(parts, a_np: np.ndarray, b_np: np.ndarray):
    """Numpy twin of :func:`repro.core.hetero_matmul.prepare_partitions`
    for one job: slice operands and derive bucketed static capacities from
    TRUE fiber occupancy without touching the device stream (the pipelined
    dispatch path must not sync behind in-flight batches). Enforces the
    same strict cap >= measured-need contract, bit-identically — counts
    run on the exact slice values the device pass would see."""
    rows = []
    for p in parts:
        r = p.region
        sa = a_np[r.m0:r.m1, r.k0:r.k1]
        sb = b_np[r.k0:r.k1, r.n0:r.n1]
        caps = []
        for operand, ax in _compressed_operands(p.cls, p.mirror):
            x = sa if operand == "a" else sb
            work = x if ax == 0 else x.T
            need = int((work != 0).sum(axis=-1).max()) if work.size else 0
            need = max(need, 1)
            cap = bucket_capacity(need, max_cap=x.shape[1 - ax])
            if cap < need:
                raise ValueError(
                    f"partition {p.cls.value} (region {p.region}): "
                    f"bucketed capacity {cap} below measured fiber "
                    f"occupancy {need} — would silently drop nonzeros")
            caps.append(cap)
        rows.append((p, sa, sb, tuple(caps)))
    return rows


def _bucket_len(n: int) -> int:
    """Next power of two (min 8) — keeps payload widths stable across
    batches whose structures repeat approximately."""
    return max(8, 1 << max(int(n) - 1, 0).bit_length())


def _pack_jobs(jobs, config: cm.AcceleratorConfig, mesh, axis: str):
    """Host-side packing pass: assign partitions to devices (§6
    round-robin) and lay every partition's operand slices into per-device
    flat payload buffers, one buffer per operand dtype.

    Returns ``(meta, payloads, payload_struct, out_shapes, spans)`` where
    ``meta[d]`` is the hashable static assignment of device ``d`` —
    ``(job_idx, partition, caps, a_payload_idx, a_offset, b_payload_idx,
    b_offset)`` — and ``payloads`` are numpy ``(n_dev, L)`` arrays ready
    for a sharded ``device_put``. Slice shapes are static in the
    partition's region, so ``meta`` fully keys the compiled program.
    """
    n_dev = _axis_size(mesh, axis)
    spans = tuple(cluster_submeshes(n_dev, config))
    known = {ci for ci, _, _ in spans}

    a_ops = [np.asarray(a) for a, _, _ in jobs]
    b_ops = [np.asarray(b) for _, b, _ in jobs]
    out_shapes = tuple(
        ((a.shape[0], b.shape[1]), jnp.promote_types(a.dtype, b.dtype))
        for a, b in zip(a_ops, b_ops))

    per_device: List[List[Tuple]] = [[] for _ in range(n_dev)]
    counters: dict = {}
    for job_idx, (a_np, b_np, (_, _, parts)) in enumerate(
            zip(a_ops, b_ops, jobs)):
        for p, sa, sb, caps in _host_capacities(parts, a_np, b_np):
            if p.cluster not in known:
                raise ValueError(
                    f"partition on cluster {p.cluster} but config "
                    f"{config.name!r} has {len(config.clusters)} clusters")
            d = device_for_partition(spans, counters, p.cluster)
            per_device[d].append((job_idx, p, caps, sa, sb))

    dtypes = sorted(
        {x.dtype for entries in per_device for (_, _, _, sa, sb) in entries
         for x in (sa, sb)},
        key=str)
    payload_idx = {dt: i for i, dt in enumerate(dtypes)}

    meta: List[Tuple] = []
    slices: List[List[Tuple[int, int, np.ndarray]]] = [
        [] for _ in range(n_dev)]           # (payload_idx, offset, slice)
    widths = [0] * len(dtypes)
    for d, entries in enumerate(per_device):
        cursors = [0] * len(dtypes)
        assigned = []
        for job_idx, p, caps, sa, sb in entries:
            refs = []
            for x in (sa, sb):
                i = payload_idx[x.dtype]
                off = cursors[i]
                cursors[i] += x.size
                refs.append((i, off))
                slices[d].append((i, off, x))
            assigned.append((job_idx, p, caps,
                             refs[0][0], refs[0][1], refs[1][0], refs[1][1]))
        meta.append(tuple(assigned))
        widths = [max(w, c) for w, c in zip(widths, cursors)]

    payload_struct = tuple(
        (str(dt), _bucket_len(w)) for dt, w in zip(dtypes, widths))
    payloads = [np.zeros((n_dev, L), dtype=dt)
                for dt, (_, L) in zip(dtypes, payload_struct)]
    for d in range(n_dev):
        for i, off, x in slices[d]:
            payloads[i][d, off:off + x.size] = x.ravel()
    return tuple(meta), payloads, payload_struct, out_shapes, spans


# ------------------------------------------------------------ SPMD builders
def _build_program(mesh, axis, per_device, out_shapes, operand_struct,
                   interpret, block):
    """jit(shard_map(...)) for one *replicated-operand* batch structure
    (the legacy ``shard_operands=False`` program). Cached on the mesh
    fingerprint + full static key — never the Mesh object, so rebuilt
    meshes over the same devices hit the same compile."""
    key = ("replicated", _mesh_fingerprint(mesh), axis, per_device,
           out_shapes, operand_struct, interpret, block)

    def build():
        def make_branch(assigned):
            def branch(a_list, b_list):
                outs = [jnp.zeros(shape, dtype)
                        for shape, dtype in out_shapes]
                for job_idx, p, caps in assigned:
                    r = p.region
                    sa = a_list[job_idx][r.m0:r.m1, r.k0:r.k1]
                    sb = b_list[job_idx][r.k0:r.k1, r.n0:r.n1]
                    pa, pb = _prep_operands(p.cls, sa, sb, p.mirror, caps)
                    partial = _dispatch_partition(p.cls, pa, pb, p.mirror,
                                                  interpret, block)
                    dtype = out_shapes[job_idx][1]
                    outs[job_idx] = outs[job_idx].at[
                        r.m0:r.m1, r.n0:r.n1].add(partial.astype(dtype))
                return tuple(outs)
            return branch

        branches = [make_branch(assigned) for assigned in per_device]

        def spmd(a_list, b_list):
            d = jax.lax.axis_index(axis)
            partials = jax.lax.switch(d, branches, a_list, b_list)
            # Cross-submesh merge: disjoint tiles union, K-partials add.
            return tuple(jax.lax.psum(x, axis) for x in partials)

        n_jobs = len(out_shapes)
        in_spec = ([P()] * n_jobs, [P()] * n_jobs)
        out_spec = tuple(P() for _ in range(n_jobs))
        return jax.jit(jax.shard_map(spmd, mesh=mesh, in_specs=in_spec,
                                     out_specs=out_spec, check_vma=False))

    return _cached_program(key, build)


def _build_packed_program(mesh, axis, meta, out_shapes, payload_struct,
                          interpret, block, measure):
    """jit(shard_map(...)) for one *operand-sharded* batch structure:
    payloads enter sharded along ``axis`` (one flat row per device), each
    branch reshapes its own statically-offset slices back out, and either
    a closing ``psum`` merges partials (``measure=False``) or per-device
    partials + a completion token come back sharded (``measure=True``) so
    the caller can fence spans individually and merge afterwards. Payload
    arguments are donated — they are dead after the call."""
    key = ("packed", _mesh_fingerprint(mesh), axis, meta, out_shapes,
           payload_struct, interpret, block, measure)

    def build():
        def make_branch(assigned):
            def branch(rows):
                outs = [jnp.zeros(shape, dtype)
                        for shape, dtype in out_shapes]
                for job_idx, p, caps, ia, off_a, ib, off_b in assigned:
                    r = p.region
                    am, ak = r.m1 - r.m0, r.k1 - r.k0
                    bn = r.n1 - r.n0
                    sa = rows[ia][off_a:off_a + am * ak].reshape(am, ak)
                    sb = rows[ib][off_b:off_b + ak * bn].reshape(ak, bn)
                    pa, pb = _prep_operands(p.cls, sa, sb, p.mirror, caps)
                    partial = _dispatch_partition(p.cls, pa, pb, p.mirror,
                                                  interpret, block)
                    dtype = out_shapes[job_idx][1]
                    outs[job_idx] = outs[job_idx].at[
                        r.m0:r.m1, r.n0:r.n1].add(partial.astype(dtype))
                return tuple(outs)
            return branch

        branches = [make_branch(assigned) for assigned in meta]

        def spmd(*payloads):
            rows = tuple(pl[0] for pl in payloads)
            d = jax.lax.axis_index(axis)
            partials = jax.lax.switch(d, branches, rows)
            if measure:
                # No collective: device d's outputs are ready the moment
                # its branch finishes, so token shard d fences exactly the
                # span compute (the merge happens outside this program).
                token = jnp.zeros((1,), jnp.float32)
                for x in partials:
                    token = token + jnp.sum(
                        jnp.abs(x.astype(jnp.float32)))[None]
                return tuple(x[None] for x in partials), token
            return tuple(jax.lax.psum(x, axis) for x in partials)

        n_payloads = len(payload_struct)
        in_specs = tuple(P(axis) for _ in range(n_payloads))
        if measure:
            out_specs = (tuple(P(axis) for _ in out_shapes), P(axis))
        else:
            out_specs = tuple(P() for _ in out_shapes)
        return jax.jit(
            jax.shard_map(spmd, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False),
            donate_argnums=tuple(range(n_payloads)))

    return _cached_program(key, build)


# --------------------------------------------------- measured timelines
@dataclasses.dataclass(frozen=True)
class SpanTiming:
    """Measured wall-clock window of one cluster's sub-mesh span for one
    batch program: ``start_s`` is the batch's dispatch timestamp,
    ``end_s`` the instant the span's per-device completion tokens were
    observed ready (block-until-ready fence at span granularity).
    Seconds, relative to the driver's origin."""

    cluster: int
    lo_device: int
    hi_device: int
    start_s: float
    end_s: float

    @property
    def busy_s(self) -> float:
        return max(self.end_s - self.start_s, 0.0)

    def to_json(self) -> Dict:
        d = dataclasses.asdict(self)
        d["busy_s"] = self.busy_s
        return d


@dataclasses.dataclass(frozen=True)
class BatchTimeline:
    """Per-batch measured timeline: dispatch/done wall timestamps plus one
    :class:`SpanTiming` per cluster span (``measure=True`` runs only —
    unmeasured runs still record dispatch/done)."""

    batch_id: int
    n_jobs: int
    dispatch_s: float
    done_s: float
    spans: Tuple[SpanTiming, ...] = ()

    @property
    def elapsed_s(self) -> float:
        return max(self.done_s - self.dispatch_s, 0.0)

    def to_json(self) -> Dict:
        return {
            "batch_id": self.batch_id,
            "n_jobs": self.n_jobs,
            "dispatch_s": self.dispatch_s,
            "done_s": self.done_s,
            "elapsed_s": self.elapsed_s,
            "spans": [s.to_json() for s in self.spans],
        }


def trace_batch_timeline(tl: BatchTimeline, origin: float) -> None:
    """Re-emit one retired batch's measured timeline onto the process
    tracer's MEASURED rows (DESIGN.md §8): the batch's dispatch→done
    window on a per-pipeline row and each :class:`SpanTiming` as a span on
    its cluster's sub-mesh row. ``origin`` is the driver's absolute
    ``perf_counter`` epoch (timeline stamps are relative to it); the
    tracer maps both onto its own epoch so measured rows line up with the
    host-driver spans. No-op while tracing is disabled."""
    if not _trace_mod.ENABLED:
        return
    tr = _trace_mod.TRACE
    ts0 = tr.ts_from_perf(origin + tl.dispatch_s)
    tr.complete(f"batch{tl.batch_id}", ts0,
                max(tl.done_s - tl.dispatch_s, 0.0) * 1e6,
                pid=_trace_mod.PID_MEASURED, tid="batches", cat="batch",
                batch=tl.batch_id, n_jobs=tl.n_jobs)
    for sp in tl.spans:
        tr.complete(
            f"batch{tl.batch_id}", tr.ts_from_perf(origin + sp.start_s),
            sp.busy_s * 1e6, pid=_trace_mod.PID_MEASURED,
            tid=f"cluster{sp.cluster}[dev{sp.lo_device}:{sp.hi_device}]",
            cat="submesh", batch=tl.batch_id, cluster=sp.cluster)


def aggregate_timelines(timelines: Sequence[BatchTimeline],
                        n_clusters: int
                        ) -> Tuple[Tuple[float, ...], float, float]:
    """Fold measured batch timelines into the ``QueueStats.measured_*``
    triple: per-cluster busy seconds (Σ span windows), wall makespan
    (first dispatch → last done) and sequential seconds (Σ busy) — the
    observed twin of the modelled concurrent/sequential makespan pair."""
    busy = [0.0] * n_clusters
    for tl in timelines:
        for sp in tl.spans:
            if 0 <= sp.cluster < n_clusters:
                busy[sp.cluster] += sp.busy_s
    if timelines:
        makespan = (max(tl.done_s for tl in timelines)
                    - min(tl.dispatch_s for tl in timelines))
    else:
        makespan = 0.0
    return tuple(busy), max(makespan, 0.0), sum(busy)


# ----------------------------------------------------- dispatch and retire
class _InFlight:
    """One dispatched batch program awaiting retirement."""

    __slots__ = ("batch_id", "n_jobs", "outs", "partials", "token",
                 "spans", "dispatch_s")

    def __init__(self, batch_id, n_jobs, outs, partials, token, spans,
                 dispatch_s):
        self.batch_id = batch_id
        self.n_jobs = n_jobs
        self.outs = outs
        self.partials = partials
        self.token = token
        self.spans = spans
        self.dispatch_s = dispatch_s


def _dispatch_batch(batch_id, jobs, config, mesh, axis, interpret, block,
                    shard_operands, measure, origin):
    """Enqueue one batch as a single SPMD program; returns immediately
    (JAX async dispatch) with an :class:`_InFlight` handle."""
    if not jobs:
        now = time.perf_counter() - origin
        return _InFlight(batch_id, 0, [], None, None, (), now)

    if shard_operands:
        meta, payloads, payload_struct, out_shapes, spans = _pack_jobs(
            jobs, config, mesh, axis)
        fn = _build_packed_program(mesh, axis, meta, out_shapes,
                                   payload_struct, interpret, block,
                                   measure)
        sharding = NamedSharding(mesh, P(axis))
        dev_payloads = tuple(jax.device_put(buf, sharding)
                             for buf in payloads)
        dispatch_s = time.perf_counter() - origin
        with jax.set_mesh(mesh), _quiet_donation():
            if measure:
                partials, token = fn(*dev_payloads)
                return _InFlight(batch_id, len(jobs), None, partials,
                                 token, spans, dispatch_s)
            outs = fn(*dev_payloads)
        return _InFlight(batch_id, len(jobs), list(outs), None, None,
                         spans, dispatch_s)

    # Legacy replicated-operand program (PR-5 behavior): full operands on
    # every device, capacities via the shared device pass (one host sync).
    n_dev = _axis_size(mesh, axis)
    spans = tuple(cluster_submeshes(n_dev, config))
    span_of = {ci: (lo, hi) for ci, lo, hi in spans}
    a_ops = [jnp.asarray(a) for a, _, _ in jobs]
    b_ops = [jnp.asarray(b) for _, b, _ in jobs]
    out_shapes = [
        ((a.shape[0], b.shape[1]), jnp.promote_types(a.dtype, b.dtype))
        for a, b in zip(a_ops, b_ops)
    ]
    prepared = prepare_partitions(
        [(a, b, list(parts)) for a, b, (_, _, parts) in
         zip(a_ops, b_ops, jobs)])
    per_device: List[List[Tuple[int, object, Tuple[int, ...]]]] = [
        [] for _ in range(n_dev)]
    counters: dict = {}
    for job_idx, rows in enumerate(prepared):
        for p, _, _, caps in rows:
            if p.cluster not in span_of:
                raise ValueError(
                    f"partition on cluster {p.cluster} but config "
                    f"{config.name!r} has {len(config.clusters)} clusters")
            d = device_for_partition(spans, counters, p.cluster)
            per_device[d].append((job_idx, p, caps))
    fn = _build_program(
        mesh, axis,
        tuple(tuple(assigned) for assigned in per_device),
        tuple(out_shapes),
        tuple((a.shape, a.dtype, b.shape, b.dtype)
              for a, b in zip(a_ops, b_ops)),
        interpret, block)
    dispatch_s = time.perf_counter() - origin
    with jax.set_mesh(mesh):
        outs = fn(a_ops, b_ops)
    return _InFlight(batch_id, len(jobs), list(outs), None, None, spans,
                     dispatch_s)


def _retire_batch(handle: _InFlight, measure: bool, origin: float
                  ) -> Tuple[List, BatchTimeline]:
    """Block until a dispatched batch completes; in measured mode fence
    each cluster span's completion tokens first (recording per-span end
    timestamps), then merge the per-device partials."""
    if handle.n_jobs == 0:
        now = time.perf_counter() - origin
        return [], BatchTimeline(handle.batch_id, 0, handle.dispatch_s, now)

    span_timings: Tuple[SpanTiming, ...] = ()
    if measure and handle.token is not None:
        by_pos: Dict[int, List] = {}
        for shard in handle.token.addressable_shards:
            pos = shard.index[0].start or 0
            by_pos.setdefault(pos, []).append(shard.data)
        stamps = []
        for ci, lo, hi in handle.spans:
            for d in range(lo, hi):
                for data in by_pos.get(d, ()):
                    jax.block_until_ready(data)
            stamps.append(SpanTiming(ci, lo, hi, handle.dispatch_s,
                                     time.perf_counter() - origin))
        span_timings = tuple(stamps)
        # Cross-submesh merge, deferred out of the measured program:
        # sum over the device axis == the psum the fused program runs.
        outs = [jnp.sum(x, axis=0, dtype=x.dtype) for x in handle.partials]
    else:
        outs = handle.outs
    jax.block_until_ready(outs)
    done_s = time.perf_counter() - origin
    return outs, BatchTimeline(handle.batch_id, handle.n_jobs,
                               handle.dispatch_s, done_s, span_timings)


# ------------------------------------------------------------- public API
def execute_job_batches_sharded(
    batches: Sequence[Sequence[Tuple]],
    config: cm.AcceleratorConfig,
    mesh,
    axis: str = "model",
    interpret: Optional[bool] = None,
    block: int = 128,
    pipeline_depth: int = 1,
    shard_operands: bool = True,
    measure: bool = False,
    timeline_sink: Optional[list] = None,
) -> List[List[jnp.ndarray]]:
    """Run a stream of job batches — each a sequence of ``(a, b,
    partitions)`` triples — as pipelined ``shard_map`` programs over
    ``mesh``, one program per batch, at most ``pipeline_depth`` in flight.

    ``pipeline_depth=1`` retires every batch before dispatching the next
    (today's serialized behavior, bit-compatible); deeper pipelines
    overlap batch N+1's host-side packing, tracing/compilation and
    host→device transfers with batch N's device compute.
    ``shard_operands`` selects packed per-span operand placement (default)
    vs the legacy fully-replicated program. ``measure=True`` (packed mode
    only) fences each cluster span per batch and appends one
    :class:`BatchTimeline` per batch to ``timeline_sink``; unmeasured runs
    append dispatch/done-only timelines when a sink is given.

    Returns per-batch output lists (job order within each batch).
    """
    if pipeline_depth < 1:
        raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
    if measure and not shard_operands:
        raise ValueError("measure=True requires shard_operands=True (the "
                         "replicated program has no span-granular fences)")
    batches = list(batches)
    results: List[Optional[List]] = [None] * len(batches)
    origin = time.perf_counter()
    inflight: "collections.deque" = collections.deque()
    tr = _trace_mod.TRACE

    def sample_inflight():
        _MET_INFLIGHT.set(len(inflight))
        if _trace_mod.ENABLED:
            tr.counter("in_flight", float(len(inflight)),
                       pid=_trace_mod.PID_HOST, tid="pipeline")

    def retire_one():
        bi, handle = inflight.popleft()
        with tr.span("retire", pid=_trace_mod.PID_HOST, tid="pipeline",
                     cat="executor", batch=bi, n_jobs=handle.n_jobs):
            outs, tl = _retire_batch(handle, measure, origin)
        results[bi] = outs
        trace_batch_timeline(tl, origin)
        sample_inflight()
        if timeline_sink is not None:
            timeline_sink.append(tl)

    for bi, jobs in enumerate(batches):
        while len(inflight) >= pipeline_depth:
            retire_one()
        jobs = list(jobs)
        with tr.span("dispatch", pid=_trace_mod.PID_HOST, tid="pipeline",
                     cat="executor", batch=bi, n_jobs=len(jobs)):
            handle = _dispatch_batch(
                bi, jobs, config, mesh, axis, interpret, block,
                shard_operands, measure, origin)
        inflight.append((bi, handle))
        sample_inflight()
    while inflight:
        retire_one()
    return results  # type: ignore[return-value]


def execute_jobs_sharded(
    jobs: Sequence[Tuple[jnp.ndarray, jnp.ndarray, Sequence]],
    config: cm.AcceleratorConfig,
    mesh,
    axis: str = "model",
    interpret: Optional[bool] = None,
    block: int = 128,
    shard_operands: bool = True,
) -> List[jnp.ndarray]:
    """Run a batch of jobs — ``(a, b, partitions)`` triples — as ONE
    ``shard_map`` program over ``mesh``, each cluster's partition queue on
    its own sub-mesh span, concurrently.

    Returns per-job outputs (replicated across the mesh), in job order.
    This is the batch entry the executor API routes ``mesh=`` calls to:
    ``execute_assignments(..., mesh=)`` hands it every assignment of an
    admitted batch so tasks placed on different clusters overlap.
    """
    if not jobs:
        return []
    return execute_job_batches_sharded(
        [jobs], config, mesh, axis=axis, interpret=interpret, block=block,
        pipeline_depth=1, shard_operands=shard_operands)[0]


def execute_schedule_sharded(a, b, schedule: KernelSchedule, mesh,
                             axis: str = "model",
                             interpret: Optional[bool] = None,
                             block: int = 128) -> jnp.ndarray:
    """Sharded single-kernel entry: run one :class:`KernelSchedule`'s
    partitions across the cluster sub-meshes of ``mesh`` and merge.
    Numerically equal to ``execute_schedule(a, b, schedule)`` (allclose at
    dtype precision)."""
    parts = [p for p in schedule.partitions if not p.region.empty]
    job = (jnp.asarray(a), jnp.asarray(b), parts)
    return execute_jobs_sharded([job], schedule.config, mesh, axis=axis,
                                interpret=interpret, block=block)[0]
