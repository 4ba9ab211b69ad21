"""Faults planted in the program under test, underneath the timed path.

Each ``plant_<name>()`` patches the program in this process; the test
process (or a child it starts) then drives a whole run and expects
``correct`` to come out false."""
import importlib

import jax.numpy as jnp

hetero_matmul = importlib.import_module("repro.core.hetero_matmul")


def plant_answer_altered():
    """Every partition's partial product is off by one part in 10^4
    where the kernel produces it."""
    orig = hetero_matmul._dispatch_partition

    def altered(*args, **kw):
        return orig(*args, **kw) * jnp.float32(1.0 + 1e-4)

    hetero_matmul._dispatch_partition = altered


def plant_half_batch_unanswered():
    """The executor returns outputs for the first half of each batch
    only."""
    orig = hetero_matmul.execute_assignments

    def half(assignments, operands_by_index, *args, **kw):
        outs = orig(assignments, operands_by_index, *args, **kw)
        keep = sorted(outs)[:max(1, len(outs) // 2)]
        return {i: outs[i] for i in keep} if len(outs) > 1 else {}

    hetero_matmul.execute_assignments = half


def plant_output_unchanged():
    """The executor returns its output buffer as it was allocated,
    without computing into it."""
    def zeros(a, b, schedule, *args, **kw):
        return jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)

    hetero_matmul.execute_schedule = zeros


FAULTS = {
    "answer_altered": plant_answer_altered,
    "half_batch_unanswered": plant_half_batch_unanswered,
    "output_unchanged": plant_output_unchanged,
}
