"""Run one cell of the on-chip serving benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``. The run builds
the cell's accelerator and operands from its files and the seed, warms up
every batch shape the window serves, measures for ``--seconds``, checks a
seeded sample of the window's outputs against the plain reference, and
prints one JSON object as its last line of standard output: the cell's
end-to-end metrics (``--trace 0``) or its per-layer metrics from a
profiler trace of the window (``--trace 1``).

It needs a TPU: without one, or with fewer chips than the cell asks for,
it exits non-zero and prints no result. JAX's compilation cache is kept at
``JAX_COMPILATION_CACHE_DIR`` when that is set, else at ``.jax_cache`` in
the checkout.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(BENCH), str(REPO / "src")]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    import harness

    bench_file = REPO / "BENCHMARK.json"
    if not bench_file.is_file():
        print(f"run.py: no {bench_file.name} in {REPO}", file=sys.stderr)
        return 2
    cell = harness.resolve(args.workload, harness.load_benchmark(bench_file))

    import jax

    from repro.common.compile_cache import use_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"run.py: cell {cell.name} needs {cell.chips} chips; JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 3
    use_compile_cache(REPO / ".jax_cache")
    # Every program, however quick to compile, goes to the cache, so that
    # a run after the first compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         process_start=PROCESS_START,
                         devices=devices[:cell.chips],
                         peaks=harness.work.load_peaks(devices[0].device_kind))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
