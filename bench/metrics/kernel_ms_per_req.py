"""Kernel layer (``kernels/expand.py``, ``kernels/gemm.py``): device ms
per completed request spent in Mosaic kernels, summed over the chips
used. Kernels are told apart from other operations by their kind in the
trace (a Mosaic custom call), not by their names."""


def read(run):
    if run.trace is None or not run.trace.has_device_ops or not run.completed:
        return None
    return 1e3 * run.trace.op_seconds("kernel") / len(run.completed)
