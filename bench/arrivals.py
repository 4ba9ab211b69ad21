"""Traffic: turns a traffic file's parameters, a configuration's kernel
list and a seed into the requests a run sends.

A traffic mix is a data file, ``bench/traffic/<name>.json``. Its ``kind``
names the arrival process that reads it, a module
``bench/traffic/<kind>.py`` found by that name. A new mix of a known kind
is a new data file; a new kind is a new module beside the others. Each
kind module defines:

* ``validate(traffic)``: raise ``ValueError`` on a bad parameter;
* ``plan(kernels, traffic, seed, seconds, cost)``: what the window will
  send (``cost``: each kernel's ``m*k*n``, its relative serve time);
* ``warmup(kernels, traffic, placement, cost)``: the batches that set-up
  serves so that the window compiles nothing (``placement`` is
  :meth:`harness.Server.placement`);
* ``drive(window, plan, seconds)``: the window's loop, serving batches
  through ``window.serve(batch, due)``; returns the window's length.

Parameters every kind shares:

* ``copies``: how many times each kernel of the configuration appears in
  one round. A round is the unit of composition: every seed sends the same
  rounds, only their order differs.
* ``shuffle``: each round's order. ``"none"``: the configuration's
  order. ``"all"``: drawn from the seed. ``"light"``: the heaviest kernel
  (most ``m*k*n``) leads every round, so heavy requests stay evenly
  spaced, and the others follow in an order drawn from the seed.
* ``operand_sets``: how many operand sets each kernel has; a kernel's
  ``j``-th request uses set ``j % operand_sets``.

Every function here is pure: the same arguments give the same requests.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import itertools
import json
import pathlib
from typing import Callable, Dict, Iterator, List, Sequence, Tuple

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
SHUFFLES = ("none", "all", "light")


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request as the client sends it."""

    index: int          # position in the stream
    kernel: str
    operand_set: int
    due_s: float        # paced kinds: seconds after the window opens


def kind_module(kind: str):
    """The arrival process ``bench/traffic/<kind>.py``."""
    path = TRAFFIC_DIR / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"no arrival process {path}")
    spec = importlib.util.spec_from_file_location(f"bench_traffic_{kind}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_traffic(name: str) -> Dict:
    """Read ``bench/traffic/<name>.json`` and validate it with its kind."""
    path = TRAFFIC_DIR / f"{name}.json"
    t = json.loads(path.read_text())
    for key in ("copies", "operand_sets"):
        if int(t.get(key, 0)) < 1:
            raise ValueError(f"{path}: {key} must be a whole number >= 1")
    if t.get("shuffle") not in SHUFFLES:
        raise ValueError(f"{path}: shuffle must be one of {SHUFFLES}")
    try:
        kind_module(str(t.get("kind"))).validate(t)
    except ValueError as e:
        raise ValueError(f"{path}: {e}") from None
    return t


def round_names(kernels: Sequence[str], traffic: Dict,
                rng: np.random.Generator,
                cost: Dict[str, float]) -> List[str]:
    """One round: every kernel ``copies`` times, in the order that
    ``shuffle`` asks for (module docstring)."""
    names = [k for k in kernels for _ in range(int(traffic["copies"]))]
    lead: List[str] = []
    if traffic["shuffle"] == "none":
        return names
    if traffic["shuffle"] == "light":
        heavy = max(kernels, key=cost.__getitem__)
        lead = [k for k in names if k == heavy]
        names = [k for k in names if k != heavy]
    return lead + [names[i] for i in rng.permutation(len(names))]


def with_sets(names: Sequence[str], traffic: Dict, seen: Dict[str, int],
              start: int) -> List[Arrival]:
    """Number ``names`` from ``start`` and give each its operand set."""
    out = []
    for i, k in enumerate(names):
        j = seen.get(k, 0)
        seen[k] = j + 1
        out.append(Arrival(start + i, k, j % int(traffic["operand_sets"]),
                           0.0))
    return out


def rounds(kernels: Sequence[str], traffic: Dict, seed: int,
           cost: Dict[str, float]) -> Iterator[List[Arrival]]:
    """Endless iterator over the stream's rounds, numbered in order."""
    rng = np.random.default_rng(seed)
    seen: Dict[str, int] = {}
    start = 0
    while True:
        batch = with_sets(round_names(kernels, traffic, rng, cost), traffic,
                          seen, start)
        start += len(batch)
        yield batch


def covering_batches(kernels: Sequence[str], traffic: Dict,
                     placement: Callable[[Sequence[str]], List[Tuple]],
                     max_batch: int, cost: Dict[str, float]
                     ) -> List[List[Arrival]]:
    """Batches that between them place every request the way any batch of
    up to ``max_batch`` requests of the stream would place it.

    The executor compiles per request, from the request's kernel, operand
    set and placement; the placement depends on which other kernels share
    its batch, not on their order. So every multiset of kernels that a run
    of consecutive requests can hold is placed (host-side, no device), and
    a greedy cover picks the cheapest multisets that reach every
    (kernel, placement) pair; each is then served once per operand set.
    The same batches for every seed."""
    per_round = int(traffic["copies"])
    universe: Dict[Tuple, None] = {}
    options = []
    for size in range(1, max_batch + 1):
        # A run of ``size`` consecutive requests touches at most
        # ceil((size - 1) / round) + 1 rounds.
        spans = -(-(size - 1) // (per_round * len(kernels))) + 1
        most = per_round * spans
        for combo in itertools.combinations_with_replacement(kernels, size):
            if max(combo.count(k) for k in set(combo)) > most:
                continue
            keys = frozenset(placement(list(combo)))
            universe.update(dict.fromkeys(keys))
            options.append((list(combo), keys,
                            sum(cost[k] for k in combo)))
    missing = set(universe)
    chosen = []
    while missing:
        names, keys, _ = max(
            options, key=lambda o: (len(o[1] & missing) / o[2], -len(o[0])))
        chosen.append(names)
        missing -= keys
    out: List[List[Arrival]] = []
    for s in range(int(traffic["operand_sets"])):
        for names in chosen:
            out.append([Arrival(len(out) * max_batch + i, k, s, 0.0)
                        for i, k in enumerate(names)])
    return out
