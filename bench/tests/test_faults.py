"""Whole runs at a tiny size on the CPU, with the timed path broken
underneath: ``correct`` must come out false for every fault the cell can
have, and true with none. The control (the reference one precision step
down in the program's place) must read above each configuration's limit.
The harness's look for a chip is skipped; everything else is the run."""
import pytest

from conftest import run_tiny, tiny_cell

ONE_CHIP = ["sci.paced", "dl.doubled"]
ONE_CHIP_FAULTS = ["answer_altered", "half_batch_unanswered",
                   "output_unchanged"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct_and_control_is_not(name):
    import harness
    import jax
    import time

    cell = tiny_cell(name)
    res = harness.run(cell, 11, 1.0, False, process_start=time.perf_counter(),
                      devices=jax.devices()[:1],
                      peaks={"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
                      control=True)
    checks = res["checks"]
    limit = checks["gap_eps"]["limit"]
    assert res["correct"] and res["failed"] == 0, checks
    assert checks["gap_eps"]["value"] < limit / 3
    assert checks["control_gap_eps"] > 3 * limit


@pytest.mark.parametrize("fault", ONE_CHIP_FAULTS)
@pytest.mark.parametrize("name", ONE_CHIP)
def test_fault_makes_the_run_incorrect(name, fault, monkeypatch):
    from faults import hetero_matmul

    for attr in ("_dispatch_partition", "execute_assignments",
                 "execute_schedule"):
        monkeypatch.setattr(hetero_matmul, attr, getattr(hetero_matmul, attr))
    import faults

    faults.FAULTS[fault]()
    res = run_tiny(tiny_cell(name), seed=12)
    assert res["correct"] is False, res["checks"]
