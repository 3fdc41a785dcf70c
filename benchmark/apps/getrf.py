"""Tiled LU without pivoting through ``getrf_nopiv_taskpool`` (DPLASMA
dgetrf_nopiv, the factorization phase of HPL-MxP).

The operand is general and born on the device from the seed: every
entry of every tile from the counter hash of ``benchmark/tiles.py``
(mean 0, variance 1; tile (i, j) and tile (j, i) drawn independently),
plus ``diag_over_sqrt_n`` x sqrt(n) on the diagonal.  The random part's
spectrum lies in a disc of radius about sqrt(n), so at 4 every leading
block's lies near Re >= 3 sqrt(n) and no pivoting is needed, while the
off-diagonal entries keep real weight against the diagonal: a wrong
tile shows in the residual (HPL-MxP's generator, strictly diagonally
dominant with a diagonal of order n, would hide it).  The factor ends
packed in A's tiles; the panels' f32 inverses (arena scratch, 3.0 GB a
job at the cell's size) die with the job: ``stage()`` discards the last
job's before it re-generates A.

The first warm job runs GETRF as a plain task, not held for its row
panel: it builds ``jit_parsec_GETRF``, the program through which the
device module sends a held head out alone when no row wave carries it,
and the row panel's plain waves, so that this fall-back compiles nothing
inside the window.  The configuration's ``warm_jobs`` counts it.
"""

from __future__ import annotations

from benchmark import tiles
from benchmark.reference import getrf as reference


class Job:
    def __init__(self, config: dict, traffic: dict, ctx, seed: int):
        from parsec_tpu.data.matrix import TwoDimBlockCyclic
        self.ctx, self.seed = ctx, seed
        n, mb = int(traffic["n"]), int(traffic["mb"])
        if n % mb:
            raise ValueError(f"getrf: mb={mb} does not divide n={n}")
        self.nt = n // mb
        self.diag = float(config["diag_over_sqrt_n"]) * n ** 0.5
        self.A = TwoDimBlockCyclic(
            mb=mb, nb=mb, lm=n, ln=n, name="A",
            dtype=tiles.storage_dtype(config["storage"]))
        self.flop = reference.flops(n)
        self.tasks = reference.tasks(self.nt)
        self.outputs = (self.A,)
        self.limits = config["limits"]
        self._plain_warm = True

    def setup(self) -> None:
        """A program without the taskpool ends the run here, before a
        tile is made."""
        from parsec_tpu.apps.getrf import getrf_nopiv_taskpool
        self._taskpool = getrf_nopiv_taskpool

    def stage(self) -> None:
        tiles.discard_scratch(self.ctx)          # the last job's inverses
        tiles.stage(self.A, self.ctx, self.seed, diag=self.diag)

    def pool(self):
        tp = self._taskpool(self.A, device="tpu")
        if self._plain_warm:
            self._plain_warm = False
            del tp.task_classes["GETRF"].properties["fuse_chain"]
        return tp

    def check(self) -> dict:
        """The factor the last job left, held to the operand the seed
        defines (reference/getrf.py)."""
        A = self.A

        def factor(i, j):
            return tiles.newest(A, i, j)

        def operand(i, j):
            dev = getattr(factor(i, j), "device", None)
            return tiles.make_tile(A, self.seed, i, j,
                                   self.diag if i == j else 0.0, dev)

        r = reference.factor_check(self.nt, A.mb, factor, operand, self.seed)
        # diag_resid is logged, not compared, as in the Cholesky cells
        return {"numbers": {"offdiag_resid": r.pop("offdiag_resid")},
                "notes": r}

    def drop(self) -> None:
        tiles.discard_tiles(self.A)
        tiles.discard_scratch(self.ctx)


def control(config: dict, traffic: dict, seed: int, store: str) -> dict:
    """The plain reference in the program's place, at the cell's own
    size, its tiles stored as ``store`` says ("fp8": the nearest
    precision below the configuration's; "config": the configuration's
    own), held to the same comparison."""
    import types
    n, mb = int(traffic["n"]), int(traffic["mb"])
    nt = n // mb
    diag = float(config["diag_over_sqrt_n"]) * n ** 0.5
    dtype = tiles.storage_dtype(config["storage"])
    A = types.SimpleNamespace(mb=mb, nb=mb, dtype=dtype, name="A")

    def operand(i, j):
        return tiles.make_tile(A, seed, i, j, diag if i == j else 0.0)

    keep = reference.store_fp8 if store == "fp8" else reference.store_as(dtype)
    F = reference.plain_lu(
        {(i, j): operand(i, j) for i in range(nt) for j in range(nt)},
        nt, keep)
    return reference.factor_check(nt, mb, lambda i, j: F[(i, j)], operand,
                                  seed)
