"""Read the comparison's two ends for a cell: the program's gap on many
seeds (sound runs) and the control's gap on the same requests.

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 --seconds 10

Runs every seed in one process, so only the first compiles. For each
seed it prints one JSON line with the program's ``gap_eps`` and the
control's (the reference at ``Precision.HIGH``, three bfloat16 passes, in
the program's place), and at the end the largest program reading and the
smallest control reading. The benchmark's own runs never run the control.
Like ``run.py`` it needs a TPU.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    import harness
    import work
    from repro.common.compile_cache import use_compile_cache

    cell = harness.resolve(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("readings.py: needs a TPU with the cell's chips",
              file=sys.stderr)
        return 3
    use_compile_cache(harness.REPO / ".jax_cache")
    # Every program, however quick to compile, goes to the cache, so that
    # a run after the first compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = work.load_peaks(devices[0].device_kind)
    program, control = [], []
    start = PROCESS_START
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run(cell, seed, args.seconds, False,
                          process_start=start, devices=devices[:cell.chips],
                          peaks=peaks, control=True)
        start = time.perf_counter()
        c = res["checks"]
        program.append(float(c["gap_eps"]["value"]))
        control.append(float(c["control_gap_eps"]))
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], "metrics": res["metrics"],
                          "checks": c}), flush=True)
    print(json.dumps({"cell": cell.name, "program_max": max(program),
                      "control_min": min(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
