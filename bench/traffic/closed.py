"""A closed loop: one client keeps one round outstanding, and sends the
next as soon as the server answers it.

No parameters beyond those every kind shares (``bench/arrivals.py``).
Each round is one batch, due when it is sent. The window closes when the
last round sent within ``seconds`` is served, so it holds all the work and
all the time of what it served.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import arrivals


def validate(traffic: Dict) -> None:
    pass


def plan(kernels: Sequence[str], traffic: Dict, seed: int, seconds: float,
         cost: Dict[str, float]):
    return arrivals.rounds(kernels, traffic, seed, cost)


def warmup(kernels: Sequence[str], traffic: Dict, placement,
           cost: Dict[str, float]) -> List[List[arrivals.Arrival]]:
    """Successive rounds until the operand-set pattern repeats
    (``operand_sets / gcd(copies, operand_sets)`` rounds), so that every
    batch the window sends has been served with the same operand sets.
    With a shuffle the order differs from the window's; the placement
    does not depend on it (:func:`arrivals.covering_batches`)."""
    sets = int(traffic["operand_sets"])
    period = sets // int(np.gcd(int(traffic["copies"]), sets))
    gen = arrivals.rounds(kernels, traffic, 0, cost)
    return [next(gen) for _ in range(period)]


def drive(window, batches, seconds: float) -> float:
    while window.clock() < seconds:
        batch = next(batches)
        due = window.clock()
        window.serve(batch, [due] * len(batch))
    return window.clock()
