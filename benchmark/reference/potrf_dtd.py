"""Plain reference for the Cholesky inserted task by task (DPLASMA's
``testing_dpotrf_dtd``).  Imports nothing of the program.

The factor is held to what ``reference/potrf.py`` holds the PTG's to:
``plain_cholesky`` there IS the sequential execution of the insert
stream below, one task after the other in insert order, and
``blockrow_residual`` the comparison that decides ``correct``.  What is
the discovery front end's own is counted here: the tasks of the stream,
and the inserts that find the window full.
"""

from __future__ import annotations

from benchmark.reference.potrf import (blockrow_residual,  # noqa: F401
                                       plain_cholesky, store_as, store_fp8)


def insert_stream(nt: int):
    """The inserts of one factorization of an nt x nt tile grid, in
    order, as ``(class, written tile, read tiles)``; tiles are (row,
    column) of the lower triangle, ``("W", k)`` is panel k's inverse.
    Right-looking in k: POTRF, the column's TRSMs, then row by row the
    SYRK and the row's GEMMs."""
    for k in range(nt):
        if k == nt - 1:
            yield "POTRFL", (k, k), ()
            return
        yield "POTRF", (k, k), ()            # writes ("W", k) too
        for m in range(k + 1, nt):
            yield "TRSM", (m, k), (("W", k),)
        for m in range(k + 1, nt):
            yield "SYRK", (m, m), ((m, k),)
            for n in range(k + 1, m):
                yield "GEMM", (m, n), ((m, k), (n, k))


def stream_tasks(nt: int) -> int:
    """Inserts of one factorization: nt(nt+1)(nt+2)/6 (nt = 32: 5 984)."""
    return nt * (nt + 1) * (nt + 2) // 6


def window_waits(inserts: int, window: int, threshold: int,
                 completed=lambda i, inflight: 0) -> int:
    """How many of ``inserts`` inserts find the window full, under a
    given drain order.  The window's rule: an insert that finds
    ``window`` or more tasks in flight blocks until fewer than
    ``threshold`` are.  ``completed(i, inflight)`` says how many of the
    tasks in flight have completed when insert ``i`` (from 0) is
    attempted; the default, none, is the inserter that outruns every
    task, and gives the most waits a stream can meet."""
    inflight = waits = 0
    for i in range(inserts):
        inflight -= min(inflight, completed(i, inflight))
        if inflight >= window:
            waits += 1
            inflight = min(inflight, threshold - 1)
        inflight += 1
    return waits
