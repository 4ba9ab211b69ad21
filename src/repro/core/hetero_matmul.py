"""Heterogeneous matmul executor — runs a :class:`KernelSchedule`
numerically by dispatching each partition to its dataflow-class kernel and
merging the partial outputs (paper §V-A: K-split partials are reduced at
the end).

This is the numerical twin of the analytical cost model: the schedule says
*where* each region runs and in *which* formats; this module proves the
composition computes exactly ``A @ B``.

Host-side API: operands arrive dense (the host knows true densities and
prepares formats — the paper's §VI assumption). The execution itself stays
device-resident: slicing, format conversion, kernel dispatch and partial
merging are all jnp ops on device arrays — the only host synchronisation is
one batched fetch of per-partition capacity scalars (kernel shapes must be
static), and those capacities are power-of-two bucketed
(:func:`repro.formats.ell.bucket_capacity`) so jit caches hit across
partitions and repeated calls (DESIGN.md §2).
"""
from __future__ import annotations

import contextlib
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.core import costmodel as cm
from repro.core.scheduler import (
    KernelSchedule,
    ManyKernelSchedule,
    schedule_many_kernels,
    schedule_single_kernel,
)
from repro.core.workloads import Workload
from repro.formats.ell import bucket_capacity, dense_to_ell
from repro.formats.taxonomy import DataflowClass
from repro.kernels import ops

# Dense operands handed to the ELL conversion, counted on the host once per
# conversion dispatched (both executors, :func:`count_conversions`).
_MET_ELL_CONVERSIONS = _obs.METRICS.counter("executor.ell.conversions")
_MET_ELL_BYTES = _obs.METRICS.counter("executor.ell.dense_bytes")


def _compressed_operands(cls: DataflowClass, mirror: bool):
    """Which operands a class compresses, as ``(operand, major_axis)``
    pairs in REQUIRED_FORMATS order (operand is "a" or "b")."""
    if cls == DataflowClass.GEMM:
        return ()
    if cls == DataflowClass.SPMM:
        return (("a", 0),) if mirror else (("b", 1),)
    if cls == DataflowClass.SPGEMM_INNER:
        return (("a", 0), ("b", 1))
    if cls == DataflowClass.SPGEMM_OUTER:
        return (("a", 1), ("b", 0))
    if cls == DataflowClass.SPGEMM_GUSTAVSON:
        return (("a", 1), ("b", 1))
    raise ValueError(cls)


def _fiber_nnz_max(x: jnp.ndarray, major_axis: int) -> jnp.ndarray:
    """Device-side scalar: max nonzeros in any fiber along ``major_axis``."""
    work = x if major_axis == 0 else x.T
    return jnp.max(jnp.sum(work != 0, axis=-1))


def _prep_operands(cls: DataflowClass, a, b, mirror: bool, caps,
                   scope=None):
    """Device slices -> REQUIRED_FORMATS[cls] operands.

    ``caps`` are the bucketed static capacities for each compressed operand,
    in :func:`_compressed_operands` order. ``scope(operand)``, when given,
    is a context entered around the conversion of that operand ("a"/"b").
    """
    x = {"a": a, "b": b}
    for (operand, ax), cap in zip(_compressed_operands(cls, mirror), caps):
        with scope(operand) if scope else contextlib.nullcontext():
            x[operand] = dense_to_ell(x[operand], ax, cap)
    return x["a"], x["b"]


def count_conversions(p, a_dtype, b_dtype) -> None:
    """Count the ELL conversions partition ``p`` makes, and the dense bytes
    handed to them (its region's slice of each compressed operand), into
    ``executor.ell.conversions`` / ``executor.ell.dense_bytes``."""
    r = p.region
    size = {"a": (r.m1 - r.m0) * (r.k1 - r.k0) * np.dtype(a_dtype).itemsize,
            "b": (r.k1 - r.k0) * (r.n1 - r.n0) * np.dtype(b_dtype).itemsize}
    for operand, _ in _compressed_operands(p.cls, p.mirror):
        _MET_ELL_CONVERSIONS.inc()
        _MET_ELL_BYTES.inc(size[operand])


def _dispatch_partition(cls: DataflowClass, a, b, mirror: bool,
                        interpret: Optional[bool], block: int):
    kw = dict(interpret=interpret)
    sized = dict(bm=block, bn=block, bk=block)
    if cls == DataflowClass.GEMM:
        return ops.gemm(a, b, **sized, **kw)
    if cls == DataflowClass.SPMM:
        if mirror:
            return ops.spmm_mirror(a, b, bm=block, bn=block, **kw)
        return ops.spmm(a, b, bm=block, bn=block, **kw)
    if cls == DataflowClass.SPGEMM_INNER:
        return ops.spgemm_inner(a, b, **sized, **kw)
    if cls == DataflowClass.SPGEMM_OUTER:
        return ops.spgemm_outer(a, b, **sized, **kw)
    if cls == DataflowClass.SPGEMM_GUSTAVSON:
        return ops.spgemm_gustavson(a, b, **sized, **kw)
    raise ValueError(cls)


def prepare_partitions(jobs):
    """Slice operands and derive bucketed static capacities for a batch of
    jobs, with ONE host sync for every capacity in the batch.

    ``jobs`` is ``[(a_d, b_d, parts), ...]`` (device operands + non-empty
    partitions); returns, per job, ``[(partition, sa, sb, caps), ...]``
    ready for :func:`_prep_operands`/:func:`_dispatch_partition`. Shared by
    the sequential executor below and the sharded sub-mesh executor
    (``core/sharded_exec.py``), so both enforce the same strict-capacity
    contract: every capacity is derived from TRUE fiber occupancy, and a
    cap below the measured need would silently drop nonzeros — a
    correctness bug, never a policy (formats/ell.py:dense_to_ell strict
    contract). The batched fetch here is the executor's one-sync
    realisation of strict mode: enforce cap >= need host-side instead of
    paying a per-conversion device sync inside dense_to_ell.
    """
    # Pass 1 (device): slice operands, queue capacity-need scalars.
    sliced, needs = [], []
    with _obs.TRACE.span("exec.slice", tid="executor", cat="executor"):
        for a_d, b_d, parts in jobs:
            rows = []
            for p in parts:
                r = p.region
                sa = a_d[r.m0:r.m1, r.k0:r.k1]
                sb = b_d[r.k0:r.k1, r.n0:r.n1]
                refs = []
                for operand, ax in _compressed_operands(p.cls, p.mirror):
                    x = sa if operand == "a" else sb
                    refs.append((x, ax, len(needs)))
                    needs.append(_fiber_nnz_max(x, ax))
                rows.append((p, sa, sb, refs))
            sliced.append(rows)
    # One host sync for every static capacity in the batch.
    with _obs.TRACE.span("exec.capacity_sync", tid="executor",
                         cat="executor"):
        need_vals = jax.device_get(needs) if needs else []

    prepared = []
    for rows in sliced:
        out_rows = []
        for p, sa, sb, refs in rows:
            caps = []
            for x, ax, i in refs:
                need = max(int(need_vals[i]), 1)
                cap = bucket_capacity(need, max_cap=x.shape[1 - ax])
                if cap < need:
                    raise ValueError(
                        f"partition {p.cls.value} (region {p.region}): "
                        f"bucketed capacity {cap} below measured fiber "
                        f"occupancy {need} — would silently drop nonzeros")
                caps.append(cap)
            out_rows.append((p, sa, sb, tuple(caps)))
        prepared.append(out_rows)
    return prepared


def execute_schedule(a, b, schedule: KernelSchedule,
                     interpret: Optional[bool] = None,
                     block: int = 128,
                     mesh=None, mesh_axis: str = "model",
                     cost_sink: Optional[list] = None) -> jnp.ndarray:
    """Run every partition on its assigned sub-accelerator kernel and merge.

    M/N-split partials tile the output; K-split partials accumulate
    (the paper's "partial output matrices are merged at the end").
    Everything stays on device: partition slices are jnp views of the
    device operands, and partials sharing an output tile are summed before
    a single scatter-add per tile.

    ``mesh`` (optional) switches to the sharded cluster-submesh executor
    (DESIGN.md §6): each cluster's partitions run on its own contiguous
    slice of the mesh ``mesh_axis`` axis, concurrently, and partials merge
    across sub-meshes. ``mesh=None`` (default) is the single-device path,
    bit-identical to previous releases.

    ``cost_sink`` (optional list) is the achieved-intensity hook
    (DESIGN.md §7): one :class:`repro.core.costmodel.SwKernelCost` is
    appended per dispatched partition, carrying the modelled FLOPs/bytes/
    time-proxy of exactly the kernel invocation made. Opt-in because each
    entry forces a host sync for the partition's true nonzero count;
    sequential path only (``mesh=None``).
    """
    if cost_sink is not None and mesh is not None:
        raise ValueError("cost_sink requires the sequential executor "
                         "(mesh=None)")
    if mesh is not None:
        from repro.core.sharded_exec import execute_schedule_sharded

        return execute_schedule_sharded(a, b, schedule, mesh,
                                        axis=mesh_axis, interpret=interpret,
                                        block=block)
    a_d = jnp.asarray(a)
    b_d = jnp.asarray(b)
    m, n = a_d.shape[0], b_d.shape[1]
    out_dtype = jnp.promote_types(a_d.dtype, b_d.dtype)
    parts = [p for p in schedule.partitions if not p.region.empty]

    # Pass 2 (device): convert at bucketed caps, dispatch, group by tile.
    tiles: dict = {}
    for p, sa, sb, caps in prepare_partitions([(a_d, b_d, parts)])[0]:
        cls = p.cls.value
        count_conversions(p, sa.dtype, sb.dtype)
        pa, pb = _prep_operands(
            p.cls, sa, sb, p.mirror, caps,
            scope=lambda operand: _obs.TRACE.span(
                "exec.ell", tid="executor", cat="executor", cls=cls,
                operand=operand))
        if cost_sink is not None:
            cost_sink.append(ops.op_cost(p.cls, pa, pb, bm=block, bn=block,
                                         mirror=p.mirror))
        with _obs.TRACE.span("exec.kernel", tid="executor", cat="executor",
                             cls=cls):
            partial = _dispatch_partition(p.cls, pa, pb, p.mirror,
                                          interpret, block)
        r = p.region
        tiles.setdefault((r.m0, r.m1, r.n0, r.n1), []).append(partial)

    # Merge: K-split partials for the same output tile sum first, then each
    # tile lands with one scatter-add.
    with _obs.TRACE.span("exec.merge", tid="executor", cat="executor"):
        out = jnp.zeros((m, n), out_dtype)
        for (m0, m1, n0, n1), partials in tiles.items():
            acc = partials[0].astype(out_dtype)
            for q in partials[1:]:
                acc = acc + q.astype(out_dtype)
            out = out.at[m0:m1, n0:n1].add(acc)
    return out


def hetero_matmul(a, b, config: cm.AcceleratorConfig,
                  interpret: Optional[bool] = None,
                  block: int = 128):
    """Schedule + execute ``a @ b`` on a heterogeneous accelerator config.

    Returns ``(result, schedule)`` — the schedule carries the analytical
    report (runtime/energy/utilization estimates).
    """
    a_d = jnp.asarray(a)
    b_d = jnp.asarray(b)
    m, k = a_d.shape
    k2, n = b_d.shape
    assert k == k2
    if a_d.size and b_d.size:
        d_mk, d_kn = (float(x) for x in jax.device_get(
            [jnp.mean(a_d != 0), jnp.mean(b_d != 0)]))
    else:
        d_mk = d_kn = 0.0
    w = Workload("adhoc", "api", m, k, n, d_mk, d_kn)
    schedule = schedule_single_kernel(config, w)
    return execute_schedule(a_d, b_d, schedule, interpret=interpret,
                            block=block), schedule


def _validated_jobs(assignments, operands_by_index):
    """Pair each assignment with its operands, checking shapes against the
    scheduled dims WITHOUT forcing a device copy (``np.shape`` works on
    numpy and jax arrays alike — the sharded packed path wants to keep
    operands host-side until they are placed on their span)."""
    jobs = []
    for asg in assignments:
        idx = asg.task_index
        w = asg.workload
        if idx not in operands_by_index:
            raise ValueError(f"task {idx} ({w.name}): no operands supplied")
        a_d, b_d = operands_by_index[idx]
        if (tuple(np.shape(a_d)) != (w.m, w.k)
                or tuple(np.shape(b_d)) != (w.k, w.n)):
            raise ValueError(
                f"task {idx} ({w.name}): operands "
                f"{np.shape(a_d)}x{np.shape(b_d)} "
                f"don't match scheduled dims {(w.m, w.k)}x{(w.k, w.n)}")
        if not asg.placed:
            raise ValueError(
                f"task {idx} ({w.name}) has no placement timeline; "
                "build schedules via schedule_many_kernels")
        jobs.append((asg, a_d, b_d))
    return jobs


def execute_assignment_batches(
    batches,
    operands_by_index,
    config: cm.AcceleratorConfig,
    *,
    interpret: Optional[bool] = None,
    block: int = 128,
    mesh=None,
    mesh_axis: str = "model",
    pipeline_depth: int = 1,
    shard_operands: bool = True,
    measure: bool = False,
    timeline_sink: Optional[list] = None,
):
    """Run a STREAM of assignment batches through the sharded executor's
    pipelined path (DESIGN.md §6): each batch becomes one ``shard_map``
    program, at most ``pipeline_depth`` in flight, so batch N+1's operand
    placement and tracing overlap batch N's compute. ``measure=True``
    fences each cluster span per batch and appends per-batch
    :class:`repro.core.sharded_exec.BatchTimeline` records to
    ``timeline_sink``. Requires ``mesh``; returns ``{task_index: output}``
    across all batches (task indices must be unique across the stream).
    """
    if mesh is None:
        raise ValueError(
            "execute_assignment_batches requires mesh= (the pipelined "
            "batch stream is a sharded-executor feature; use "
            "execute_assignments for the sequential path)")
    from repro.core.sharded_exec import execute_job_batches_sharded

    job_batches, order = [], []
    for batch in batches:
        jobs = _validated_jobs(batch, operands_by_index)
        job_batches.append([
            (np.asarray(a_d), np.asarray(b_d),
             [pp.partition for pp in asg.placed
              if not pp.partition.region.empty])
            for asg, a_d, b_d in jobs
        ])
        order.append([asg.task_index for asg, _, _ in jobs])
    outs_batches = execute_job_batches_sharded(
        job_batches, config, mesh, axis=mesh_axis, interpret=interpret,
        block=block, pipeline_depth=pipeline_depth,
        shard_operands=shard_operands, measure=measure,
        timeline_sink=timeline_sink)
    result = {}
    for idxs, outs in zip(order, outs_batches):
        for i, out in zip(idxs, outs):
            if i in result:
                raise ValueError(
                    f"task index {i} appears in more than one batch")
            result[i] = out
    return result


def execute_assignments(
    assignments,
    operands_by_index,
    config: cm.AcceleratorConfig,
    interpret: Optional[bool] = None,
    block: int = 128,
    mesh=None,
    mesh_axis: str = "model",
    pipeline_depth: int = 1,
    shard_operands: bool = True,
):
    """Numerically run a batch of :class:`TaskAssignment` placements.

    ``operands_by_index`` maps ``task_index`` -> dense ``(a, b)``; every
    assignment is dispatched through :func:`execute_schedule` on its
    placed partitions (including multi-cluster splits with K-partial
    merging). Returns ``{task_index: output}``. This is the shared batch
    executor: :func:`execute_many_kernel_schedule` feeds it a whole
    schedule, the serving runtime (``repro.serve.cluster``) feeds it each
    admitted batch as it retires.

    ``mesh`` (optional) switches the whole batch to the sharded
    cluster-submesh executor (DESIGN.md §6): ``shard_map`` programs in
    which each cluster's partition queue — across every assignment in the
    batch — runs on its own contiguous slice of the mesh ``mesh_axis``
    axis, so assignments on different clusters execute concurrently.
    ``shard_operands`` (sharded path only) selects packed per-span operand
    placement — each partition's slices resident only on the executing
    device, O(batch/devices) working set — vs the legacy fully-replicated
    program. ``pipeline_depth > 1`` (sharded path only) splits the batch
    into ``min(pipeline_depth, len(assignments))`` contiguous chunks and
    pipelines them as overlapping programs; depth 1 is one program per
    batch, bit-compatible with previous releases. ``mesh=None`` (default)
    keeps the sequential single-device path, bit-identical to previous
    releases, and rejects ``pipeline_depth != 1``.
    """
    if pipeline_depth < 1:
        raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
    if mesh is None and pipeline_depth != 1:
        raise ValueError(
            "pipeline_depth > 1 requires mesh= (pipelining overlaps "
            "shard_map programs; the sequential path has none)")
    jobs = _validated_jobs(assignments, operands_by_index)

    if mesh is not None:
        if pipeline_depth > 1 and len(jobs) > 1:
            n_chunks = min(pipeline_depth, len(jobs))
            size, rem = divmod(len(jobs), n_chunks)
            batches, lo = [], 0
            for c in range(n_chunks):
                hi = lo + size + (1 if c < rem else 0)
                batches.append([asg for asg, _, _ in jobs[lo:hi]])
                lo = hi
        else:
            batches = [[asg for asg, _, _ in jobs]]
        return execute_assignment_batches(
            batches, operands_by_index, config, interpret=interpret,
            block=block, mesh=mesh, mesh_axis=mesh_axis,
            pipeline_depth=pipeline_depth, shard_operands=shard_operands)

    outs = {}
    for asg, a_d, b_d in jobs:
        parts = tuple(pp.partition for pp in asg.placed)
        ks = KernelSchedule(asg.workload, config, parts, asg.report)
        with _obs.TRACE.span("exec.request", tid="executor", cat="executor",
                        task=asg.task_index):
            outs[asg.task_index] = execute_schedule(
                jnp.asarray(a_d), jnp.asarray(b_d), ks,
                interpret=interpret, block=block)
    return outs


def execute_many_kernel_schedule(
    operands: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
    schedule: ManyKernelSchedule,
    interpret: Optional[bool] = None,
    block: int = 128,
    mesh=None,
    mesh_axis: str = "model",
    pipeline_depth: int = 1,
    shard_operands: bool = True,
) -> List[jnp.ndarray]:
    """Numerically run a many-kernel (multi-tenant) schedule.

    ``operands[i]`` is the dense ``(a, b)`` pair of the i-th task in the
    queue originally passed to :func:`repro.core.scheduler.
    schedule_many_kernels`; shapes must match that task's workload dims
    (the schedule is analytic on exactly those shapes). Every assignment is
    dispatched on its cluster's chosen (class, orientation) format pair via
    :func:`execute_schedule` — including per-partition dispatch + K-split
    merging for tasks the ``optimized`` policy split across clusters — so
    multi-tenant placements are checkable against the dense reference
    (``kernels/ref.py``), not just the cost model.

    ``mesh`` (optional) routes the whole batch through the sharded
    cluster-submesh executor (DESIGN.md §6): each cluster's task queue
    runs concurrently on its own slice of the mesh ``mesh_axis`` axis.
    Outputs are numerically equal to the ``mesh=None`` sequential path
    (allclose; parity pinned in ``tests/test_sharded_exec.py``).

    Returns per-task outputs in queue order.
    """
    operands = list(operands)
    if len(operands) != len(schedule.assignments):
        raise ValueError(
            f"{len(operands)} operand pairs for "
            f"{len(schedule.assignments)} scheduled tasks")
    # Assignments are in priority order, not queue order: the task_index
    # mapping must be a full permutation or operands would silently pair
    # with the wrong (same-shaped) tasks.
    indices = sorted(a.task_index for a in schedule.assignments)
    if indices != list(range(len(operands))):
        raise ValueError(
            "schedule assignments lack a complete task_index permutation "
            f"(got {indices}); build schedules via schedule_many_kernels")
    outs = execute_assignments(
        schedule.assignments, dict(enumerate(operands)), schedule.config,
        interpret=interpret, block=block, mesh=mesh, mesh_axis=mesh_axis,
        pipeline_depth=pipeline_depth, shard_operands=shard_operands)
    return [outs[i] for i in range(len(operands))]


def hetero_many_matmul(
    pairs: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
    config: cm.AcceleratorConfig,
    policy: str = "lpt",
    arrivals: Optional[Sequence[float]] = None,
    interpret: Optional[bool] = None,
    block: int = 128,
    mesh=None,
    mesh_axis: str = "model",
):
    """Schedule + execute a queue of matmuls on a heterogeneous accelerator.

    Builds one :class:`Workload` per ``(a, b)`` pair (true shapes and
    measured densities), list-schedules the queue under ``policy``, and
    runs every assignment numerically. Returns ``(outputs, schedule)``.
    """
    dense_pairs = [(jnp.asarray(a), jnp.asarray(b)) for a, b in pairs]
    dens = jax.device_get([jnp.mean(x != 0) for ab in dense_pairs
                           for x in ab]) if dense_pairs else []
    tasks = []
    for i, (a, b) in enumerate(dense_pairs):
        m, k = a.shape
        k2, n = b.shape
        assert k == k2, (a.shape, b.shape)
        tasks.append(Workload(f"task{i}", "api", m, k, n,
                              float(dens[2 * i]), float(dens[2 * i + 1])))
    ms = schedule_many_kernels(config, tasks, policy=policy,
                               arrivals=arrivals)
    outs = execute_many_kernel_schedule(dense_pairs, ms,
                                        interpret=interpret, block=block,
                                        mesh=mesh, mesh_axis=mesh_axis)
    return outs, ms


def cluster_submeshes(n_model_devices: int, config: cm.AcceleratorConfig):
    """Map clusters onto contiguous slices of the mesh 'model' axis,
    proportional to PE share (DESIGN.md §2 'clusters = sub-meshes', §6
    device-span assignment rule).

    Returns ``[(cluster_index, lo_device, hi_device), ...]`` covering
    ``range(n_model_devices)`` with every cluster owning at least one
    device — a proportional split is repaired so tiny-PE clusters never
    round to an empty span (an empty span would silently drop that
    cluster's partitions from a sharded run). When the axis has fewer
    devices than the config has clusters no such repair exists, and the
    mapping raises ``ValueError`` instead of emitting empty spans.
    """
    n_clusters = len(config.clusters)
    if n_model_devices < n_clusters:
        raise ValueError(
            f"cannot map {n_clusters} clusters onto {n_model_devices} "
            f"device(s): every cluster needs >= 1 device on the mesh "
            "'model' axis (shrink the config or grow the mesh)")
    total = sum(c.pes for c in config.clusters)
    spans = []
    lo = 0
    for i, c in enumerate(config.clusters):
        hi = lo + int(round(n_model_devices * c.pes / total))
        if i == n_clusters - 1:
            hi = n_model_devices
        # Repair the proportional split: at least one device per cluster,
        # while leaving room for every cluster still to come.
        hi = max(hi, lo + 1)
        hi = min(hi, n_model_devices - (n_clusters - 1 - i))
        spans.append((i, lo, hi))
        lo = hi
    return spans
