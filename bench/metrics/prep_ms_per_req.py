"""Executor layer (``core/hetero_matmul.py``, ``core/sharded_exec.py``):
device ms per completed request spent in XLA operations that are not
Mosaic kernels (slicing, padding, the ELL conversion's sort, the merge),
summed over the chips used."""


def read(run):
    if run.trace is None or not run.trace.has_device_ops or not run.completed:
        return None
    return 1e3 * run.trace.op_seconds("xla") / len(run.completed)
