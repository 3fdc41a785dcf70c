"""Tiled lower Cholesky inserted task by task through the DTD front end
(``potrf_dtd_taskpool``: DPLASMA's ``testing_dpotrf_dtd``).

Everything but the front end is ``apps/potrf.py``'s: the operand born on
the device from the seed, the staging, the comparison and its limit, the
control.  The pool inserts its own stream once it is attached and
started, so the harness's ``add_taskpool`` + ``wait`` is the whole job.
"""

from __future__ import annotations

from benchmark.apps import potrf

control = potrf.control


class Job(potrf.Job):
    def pool(self):
        from parsec_tpu.apps.potrf import potrf_dtd_taskpool
        return potrf_dtd_taskpool(self.A, device="tpu")

    def check(self) -> dict:
        out = super().check()
        # what the discovery counted over the run's pools (PERF.md §3)
        stats = getattr(self.ctx, "dtd_stats", None)
        if stats is not None:
            out["notes"]["dtd"] = stats.as_dict()
        return out
