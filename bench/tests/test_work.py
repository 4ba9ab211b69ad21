"""Request-defined work against a brute-force count."""
import numpy as np
import pytest

import work


@pytest.mark.parametrize("seed,dens", [(0, (0.1, 0.3)), (1, (1.0, 1.0)),
                                       (2, (0.02, 0.5)), (3, (0.0, 1.0))])
def test_work_matches_brute_force(seed, dens):
    rng = np.random.default_rng(seed)
    m, k, n = 7, 11, 5
    a = rng.standard_normal((m, k)) * (rng.random((m, k)) < dens[0])
    b = rng.standard_normal((k, n)) * (rng.random((k, n)) < dens[1])
    pairs = sum(1 for i in range(m) for kk in range(k) for j in range(n)
                if a[i, kk] != 0 and b[kk, j] != 0)
    flops, nbytes = work.request_work((a != 0).sum(axis=0),
                                      (b != 0).sum(axis=1), m, n)
    assert flops == 2 * pairs
    assert nbytes == 4 * (np.count_nonzero(a) + np.count_nonzero(b)) \
        + 4 * m * n


def test_least_seconds_takes_the_binding_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000, 10, peaks) == 10.0     # compute-bound
    assert work.least_seconds(10, 1000, peaks) == 100.0    # memory-bound


def test_unknown_device_is_an_error():
    assert work.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.load_peaks("cpu")
