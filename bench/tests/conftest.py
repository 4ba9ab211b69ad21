"""Shared helpers for the benchmark's own tests (``pytest bench/tests``).

They run on the CPU at tiny sizes: the Pallas kernels run in interpret
mode there. Nothing here describes or touches a TPU."""
import dataclasses
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

#: Kernels of the Table-I shapes' kinds at a size the interpreter serves
#: in well under a second: a sparse-by-dense, a dense and a
#: sparse-by-sparse product.
TINY_KERNELS = [
    {"name": "sparse_dense", "application": "test", "m": 160, "k": 136,
     "n": 72, "d_mk": 0.02, "d_kn": 1.0},
    {"name": "dense", "application": "test", "m": 64, "k": 48, "n": 40,
     "d_mk": 1.0, "d_kn": 1.0},
    {"name": "sparse_sparse", "application": "test", "m": 96, "k": 120,
     "n": 88, "d_mk": 0.05, "d_kn": 0.1},
]

CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(name: str, **traffic):
    """Cell ``name`` of ``BENCHMARK.json`` with its kernels replaced by
    :data:`TINY_KERNELS` and its traffic's parameters overridden."""
    import harness

    cell = harness.resolve(name)
    config = dict(cell.config, kernels=TINY_KERNELS)
    if cell.traffic["kind"] == "paced":
        # Tiny requests are served in milliseconds: pace them so that a
        # one-second window holds a few whole rounds.
        traffic = dict({"rate_rps": 12.0}, **traffic)
    return dataclasses.replace(cell, config=config,
                               traffic=dict(cell.traffic, **traffic))


def run_tiny(cell, seed=7, seconds=1.0, trace=False):
    import time

    import jax

    import harness

    return harness.run(cell, seed, seconds, trace,
                       process_start=time.perf_counter(),
                       devices=jax.devices()[:cell.chips], peaks=CPU_PEAKS)


@pytest.fixture
def tiny():
    return tiny_cell
