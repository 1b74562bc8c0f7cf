"""Lower bounds on the work of each stage, counted from the problem.

Every count here is what ANY implementation of the stage must do on the same
inputs, never what a particular kernel's scheme does, so that a later change
to how a stage is computed is read against the same yardstick.  A roofline
share is the least time this work needs on the chip (the larger of
operations over the peak and bytes over the HBM bandwidth, ``peaks.json``)
divided by the kernel's device time.

Matrix work is set against the bf16 peak: the chip publishes no float32
peak.  The vector unit publishes none either, so selection work (top-k
networks, merges) is counted in no roofline.
"""
from __future__ import annotations

import json
import os
from typing import NamedTuple

import numpy as np

F32 = 4
I32 = 4


class Work(NamedTuple):
    ops: float  # arithmetic operations (a multiply-add is 2)
    bytes: float  # HBM bytes read or written at least once

    def __add__(self, other):
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def scaled(self, c: float) -> "Work":
        return Work(self.ops * c, self.bytes * c)


ZERO = Work(0.0, 0.0)


def load_peaks(device_kind: str, path: str | None = None) -> dict:
    """The peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path}: "
                       f"known {sorted(table)}")
    return table[device_kind]


def least_time(w: Work, peaks: dict) -> float:
    """Seconds the chip needs at least for ``w``: the larger bound."""
    return max(w.ops / peaks["bf16_flops_per_s"],
               w.bytes / peaks["hbm_bytes_per_s"])


def allpairs(n: int, d: int, k: int) -> Work:
    """One all-pairs kNN solve: each unordered pair scored once, d
    multiply-adds a pair (n(n-1)/2 * d * 2 operations); the n x d float32
    rows read once and the n x k distances and int32 ids written once."""
    return Work(ops=float(n) * (n - 1) / 2 * d * 2,
                bytes=float(n) * d * F32 + float(n) * k * (F32 + I32))


def pq_scan(probes: np.ndarray, counts: np.ndarray, pq_m: int) -> Work:
    """One ADC scan of a flushed batch.

    ``probes`` [m, nprobe] are the cells each real query probed (padding rows
    left out), ``counts`` [ncells] the live rows of each cell.  Operations:
    one lookup-add per sub-quantizer for each (query, live row of a cell that
    query probed).  Bytes: the ``pq_m`` one-byte codes and the float32 norm
    (``hy``) of every live row in the union of the cells the batch probed,
    each read once.
    """
    probes = np.asarray(probes)
    counts = np.asarray(counts, np.float64)
    if probes.size == 0:
        return ZERO
    pairs = counts[probes].sum()
    union = np.unique(probes)
    return Work(ops=float(pairs) * pq_m,
                bytes=float(counts[union].sum()) * (pq_m + F32))


def rescore(m: int, kp: int, d: int) -> Work:
    """Exact rescore of ``kp`` float32 candidate rows for each of ``m``
    queries: the rows read once (kp * d * 4 bytes a query) and d
    multiply-adds a candidate."""
    return Work(ops=float(m) * kp * d * 2, bytes=float(m) * kp * d * F32)
