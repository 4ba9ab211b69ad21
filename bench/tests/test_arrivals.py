"""The traffic kinds: deterministic from the seed, with the stated rate
and composition, and warm-ups that cover what the window serves."""
import collections

import numpy as np
import pytest

import arrivals

KERNELS = ["a", "b", "c", "d", "e"]
PACED = {"kind": "paced", "rate_rps": 2.5, "copies": 1, "shuffle": "all",
         "operand_sets": 2, "max_batch": 4}
CLOSED = {"kind": "closed", "copies": 2, "shuffle": "none",
          "operand_sets": 2}
#: Relative serve time of each kernel: "c" is the heaviest.
COST = {"a": 1.0, "b": 2.0, "c": 5.0, "d": 3.0, "e": 4.0}
paced = arrivals.kind_module("paced")
closed = arrivals.kind_module("closed")


def test_paced_plan_is_deterministic_and_paced():
    s1 = paced.plan(KERNELS, PACED, seed=2**33 + 5, seconds=40, cost=COST)
    s2 = paced.plan(KERNELS, PACED, seed=2**33 + 5, seconds=40, cost=COST)
    assert s1 == s2
    assert len(s1) == 100                       # 20 whole rounds of 5
    assert [a.due_s for a in s1] == pytest.approx(
        [i / 2.5 for i in range(100)])


def test_paced_rounds_hold_every_kernel_once_in_seeded_order():
    s1 = paced.plan(KERNELS, PACED, seed=1, seconds=40, cost=COST)
    s2 = paced.plan(KERNELS, PACED, seed=2, seconds=40, cost=COST)
    for s in (s1, s2):
        for r in range(0, len(s), 5):
            assert sorted(a.kernel for a in s[r:r + 5]) == KERNELS
    assert [a.kernel for a in s1] != [a.kernel for a in s2]
    # Same work for every seed: the same requests, in another order.
    key = lambda s: collections.Counter(  # noqa: E731
        (a.kernel, a.operand_set) for a in s)
    assert key(s1) == key(s2)


def test_light_shuffle_keeps_the_heaviest_kernel_first():
    light = dict(PACED, shuffle="light")
    s1 = paced.plan(KERNELS, light, seed=1, seconds=40, cost=COST)
    s2 = paced.plan(KERNELS, light, seed=2, seconds=40, cost=COST)
    assert s1 == paced.plan(KERNELS, light, seed=1, seconds=40, cost=COST)
    for s in (s1, s2):
        for r in range(0, len(s), 5):
            assert s[r].kernel == "c"
            assert sorted(a.kernel for a in s[r:r + 5]) == KERNELS
    assert [a.kernel for a in s1] != [a.kernel for a in s2]
    # The heavy requests fall due at the same times for every seed.
    assert [a.due_s for a in s1 if a.kernel == "c"] == [
        a.due_s for a in s2 if a.kernel == "c"]


def test_operand_sets_cycle_per_kernel():
    s = paced.plan(KERNELS, PACED, seed=3, seconds=40, cost=COST)
    for k in KERNELS:
        assert [a.operand_set for a in s if a.kernel == k] == [0, 1] * 10


def test_bursts_raise_the_rate_inside_each_burst():
    t = dict(PACED, rate_rps=2.0,
             bursts={"every_s": 10, "length_s": 2, "factor": 4})
    due = paced.due_times(60, t)
    assert np.all(np.diff(due) > 0)
    # 16 due in each 2 s burst, 16 in the 8 s after it: 32 per 10 s.
    assert np.count_nonzero(due < 2) == 16
    assert np.count_nonzero(due < 10) == 32
    assert np.diff(due[:16]) == pytest.approx(np.full(15, 1 / 8))
    assert np.diff(due[16:32]) == pytest.approx(np.full(15, 1 / 2))
    assert due[32] == pytest.approx(10.0)
    s = paced.plan(KERNELS, t, seed=4, seconds=20, cost=COST)
    assert len(s) == 60                         # 64 due, 12 whole rounds


def test_paced_plan_refuses_a_window_without_a_round():
    with pytest.raises(ValueError):
        paced.plan(KERNELS, PACED, seed=0, seconds=1, cost=COST)


def test_closed_batches_repeat_one_composition():
    gen = closed.plan(KERNELS[:3], CLOSED, seed=9, seconds=10, cost=COST)
    batches = [next(gen) for _ in range(4)]
    for b in batches:
        assert [(a.kernel, a.operand_set) for a in b] == [
            ("a", 0), ("a", 1), ("b", 0), ("b", 1), ("c", 0), ("c", 1)]
    assert [a.index for b in batches for a in b] == list(range(24))


def test_closed_warmup_serves_each_operand_set_pattern():
    rounds = dict(CLOSED, copies=1)
    warm = closed.warmup(KERNELS, rounds, None, {})
    assert [[a.operand_set for a in b] for b in warm] == [[0] * 5, [1] * 5]
    assert len(closed.warmup(KERNELS, CLOSED, None, {})) == 1


def _toy_placement(names):
    """A stand-in for the program's placement: a kernel is split when it
    shares its batch with 'e', and placed whole otherwise."""
    split = "e" in names
    return [(k, "split" if split and k != "e" else "whole") for k in names]


def test_paced_warmup_covers_every_placement_of_a_window_batch():
    cost = {k: 1.0 for k in KERNELS}
    warm = paced.warmup(KERNELS, PACED, _toy_placement, cost)
    served = {(k, p, a.operand_set)
              for b in warm
              for (k, p), a in zip(_toy_placement([a.kernel for a in b]), b)}
    # Every run of up to max_batch consecutive requests of any seed's
    # window, each with its operand set.
    for seed in range(20):
        s = paced.plan(KERNELS, PACED, seed=seed, seconds=40, cost=COST)
        for size in range(1, PACED["max_batch"] + 1):
            for i in range(len(s) - size + 1):
                run = s[i:i + size]
                for (k, p), a in zip(
                        _toy_placement([a.kernel for a in run]), run):
                    assert (k, p, a.operand_set) in served
    # The same batches whatever the seed: a warm-up takes no seed.
    assert len({a.index for b in warm for a in b}) == sum(map(len, warm))


def test_every_traffic_file_loads():
    for path in sorted(arrivals.TRAFFIC_DIR.glob("*.json")):
        arrivals.load_traffic(path.stem)


def test_unknown_kind_is_refused(tmp_path, monkeypatch):
    (tmp_path / "x.json").write_text(
        '{"kind": "nosuch", "copies": 1, "operand_sets": 1, '
        '"shuffle": "none"}')
    monkeypatch.setattr(arrivals, "TRAFFIC_DIR", tmp_path)
    with pytest.raises(ValueError, match="no arrival process"):
        arrivals.load_traffic("x")


def test_unknown_shuffle_is_refused(tmp_path, monkeypatch):
    (tmp_path / "x.json").write_text(
        '{"kind": "closed", "copies": 1, "operand_sets": 1, '
        '"shuffle": true}')
    monkeypatch.setattr(arrivals, "TRAFFIC_DIR", tmp_path)
    with pytest.raises(ValueError, match="shuffle"):
        arrivals.load_traffic("x")
