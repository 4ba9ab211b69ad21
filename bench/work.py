"""The work a request defines, whatever body serves it.

For ``C = A @ B`` with ``A`` (M x K) and ``B`` (K x N):

* flops = 2 * sum_k nnz(A[:, k]) * nnz(B[k, :]): one multiply and one add
  for every pair of nonzeros that meet on a contraction index. No body can
  do less and still compute every product.
* bytes = 4 * (nnz(A) + nnz(B)) + 4 * M * N: read each nonzero once,
  write the float32 output once. Index metadata is left out, so the count
  is a floor for any format.

``least_seconds`` is the roofline time of that work on a device: the
larger of flops over peak FLOP/s and bytes over peak HBM bytes/s.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Tuple

import numpy as np

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def request_work(col_nnz_a: np.ndarray, row_nnz_b: np.ndarray,
                 m: int, n: int) -> Tuple[int, int]:
    """``(flops, bytes)`` from the nonzero count of each column of ``A``
    and each row of ``B`` (exact integers)."""
    ca = np.asarray(col_nnz_a, dtype=np.int64)
    rb = np.asarray(row_nnz_b, dtype=np.int64)
    if ca.shape != rb.shape:
        raise ValueError(f"contraction lengths differ: {ca.shape} vs "
                         f"{rb.shape}")
    flops = 2 * int(np.dot(ca, rb))
    nbytes = 4 * (int(ca.sum()) + int(rb.sum())) + 4 * int(m) * int(n)
    return flops, nbytes


def load_peaks(device_kind: str) -> Dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: int, nbytes: int, peaks: Dict) -> float:
    return max(flops / float(peaks["flops_per_s"]),
               nbytes / float(peaks["hbm_bytes_per_s"]))
