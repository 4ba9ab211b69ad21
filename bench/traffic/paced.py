"""Paced arrivals (an open loop): requests fall due on a fixed schedule,
whatever the server does, as independent tenants send them.

Parameters, besides those every kind shares (``bench/arrivals.py``):

* ``rate_rps``: requests due per second, evenly paced;
* ``bursts`` (optional): ``{"every_s", "length_s", "factor"}``, the rate
  multiplied by ``factor`` for the first ``length_s`` seconds of every
  ``every_s``;
* ``max_batch``: the largest batch whose placements set-up warms.

The window holds as many whole rounds as fall due in it. Each serve call
admits every request that is due and not yet served as one batch, so the
batch's composition follows the timing; set-up serves batches that place
every request the way a batch of up to ``max_batch`` would
(:func:`arrivals.covering_batches`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Sequence

import numpy as np

import arrivals


def validate(traffic: Dict) -> None:
    if float(traffic.get("rate_rps", 0)) <= 0:
        raise ValueError("paced traffic needs rate_rps > 0")
    if int(traffic.get("max_batch", 0)) < 1:
        raise ValueError("paced traffic needs max_batch >= 1")
    b = traffic.get("bursts")
    if b is not None and not (float(b["every_s"]) > float(b["length_s"])
                              > 0 and float(b["factor"]) > 0):
        raise ValueError("bursts need every_s > length_s > 0, factor > 0")


def due_times(n: int, traffic: Dict) -> np.ndarray:
    """When each of the first ``n`` requests falls due: request ``i`` at
    the moment ``i`` requests' worth of the rate has passed."""
    rate = float(traffic["rate_rps"])
    b = traffic.get("bursts")
    if b is None:
        return np.arange(n) / rate
    every, length = float(b["every_s"]), float(b["length_s"])
    hi = rate * float(b["factor"])
    per_period = hi * length + rate * (every - length)
    out = np.empty(n)
    for i in range(n):
        period, left = divmod(float(i), per_period)
        t = left / hi if left < hi * length else (
            length + (left - hi * length) / rate)
        out[i] = period * every + t
    return out


def _count_due(traffic: Dict, seconds: float) -> int:
    """How many requests fall due before ``seconds``."""
    n = int(float(traffic["rate_rps"]) * seconds)
    b = traffic.get("bursts")
    if b is not None:
        n = int(n * float(b["factor"])) + 1
    times = due_times(n + 1, traffic)
    return int(np.searchsorted(times, seconds, side="left"))


def plan(kernels: Sequence[str], traffic: Dict, seed: int, seconds: float,
         cost: Dict[str, float]) -> List[arrivals.Arrival]:
    """The requests due in a window of ``seconds``: whole rounds only, in
    the seed's order."""
    per_round = len(kernels) * int(traffic["copies"])
    n_rounds = _count_due(traffic, seconds) // per_round
    if n_rounds < 1:
        raise ValueError(f"{seconds} s of this traffic holds no whole "
                         f"round of {per_round} requests")
    gen = arrivals.rounds(kernels, traffic, seed, cost)
    reqs = [a for _ in range(n_rounds) for a in next(gen)]
    due = due_times(len(reqs), traffic)
    return [dataclasses.replace(a, due_s=float(t))
            for a, t in zip(reqs, due)]


def warmup(kernels: Sequence[str], traffic: Dict, placement,
           cost: Dict[str, float]) -> List[List[arrivals.Arrival]]:
    return arrivals.covering_batches(kernels, traffic, placement,
                                     int(traffic["max_batch"]), cost)


def drive(window, schedule: Sequence[arrivals.Arrival],
          seconds: float) -> float:
    """Serve every due request, all that are pending as one batch per
    call, until every request due in the window is served; returns the
    seconds that took."""
    due = [a.due_s for a in schedule]
    i = 0
    while i < len(schedule):
        now = window.clock()
        if due[i] > now:
            with window.annotate("bench.wait_arrival"):
                time.sleep(due[i] - now)
            continue
        j = int(np.searchsorted(due, now, side="right"))
        window.serve(schedule[i:j], [a.due_s for a in schedule[i:j]])
        i = j
    return window.clock()
