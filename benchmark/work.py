"""Useful work of one job, from its sizes alone (the yardstick: the count
reads the same work whatever implements it).  LAPACK's useful-flop
counts, and the task counts of the tiled DAGs."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def potrf_flops(n: int) -> float:
    """Useful flop of an n x n Cholesky factorization: n^3 / 3."""
    return n ** 3 / 3.0


def potrf_tasks(nt: int) -> int:
    """Tasks of the tiled lower Cholesky over an nt x nt tile grid:
    nt POTRF + nt(nt-1)/2 TRSM + nt(nt-1)/2 SYRK + nt(nt-1)(nt-2)/6 GEMM
    = nt(nt+1)(nt+2)/6."""
    return nt * (nt + 1) * (nt + 2) // 6


def gemm_flops(m: int, n: int, k: int) -> float:
    """Useful flop of C(m x n) += A(m x k) B(k x n): 2mnk."""
    return 2.0 * m * n * k


def gemm_tasks(mt: int, nt: int, kt: int) -> int:
    """One GEMM task per (C tile, k panel)."""
    return mt * nt * kt


def peak(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` (peaks.json).  A device the
    table lacks is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"benchmark/peaks.json has no entry for device_kind "
            f"{device_kind!r}: add it with the source of each figure")
    return table[device_kind]
