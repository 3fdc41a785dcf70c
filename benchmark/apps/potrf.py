"""Tiled lower Cholesky through ``potrf_taskpool`` (DPLASMA dpotrf_L).

The operand is symmetric positive definite and born on the device from
the seed: lower tiles with entries of mean 0 and variance 1, and
``diag_over_sqrt_n`` x sqrt(n) added on the diagonal (the symmetric part
has its spectrum within +-2 sqrt(n), so 4 gives a condition number near
3).  Off-diagonal tiles carry real weight against the diagonal, which is
what lets the residual see a wrong tile; chip_smoke's matrix (diagonal
n, entries in [0, 1)) hides one under the diagonal's own rounding.
"""

from __future__ import annotations

from benchmark import tiles, work
from benchmark.reference import potrf as reference


class Job:
    def __init__(self, config: dict, traffic: dict, ctx, seed: int):
        from parsec_tpu.data.matrix import TwoDimBlockCyclic
        self.ctx, self.seed = ctx, seed
        n, mb = int(traffic["n"]), int(traffic["mb"])
        if n % mb:
            raise ValueError(f"potrf: mb={mb} does not divide n={n}")
        self.nt = n // mb
        self.diag = float(config["diag_over_sqrt_n"]) * n ** 0.5
        self.A = TwoDimBlockCyclic(
            mb=mb, nb=mb, lm=n, ln=n, name="A",
            dtype=tiles.storage_dtype(config["storage"]))
        if config.get("distribute"):
            self.A.distribute_devices(ctx)
        self.flop = work.potrf_flops(n)
        self.tasks = work.potrf_tasks(self.nt)
        self.outputs = (self.A,)
        self.limits = config["limits"]

    def setup(self) -> None:
        pass                     # nothing outlives a job: A is overwritten

    def stage(self) -> None:
        tiles.discard_scratch(self.ctx)          # the last job's W inverses
        # dpotrf_L reads and writes the lower triangle only
        tiles.stage(self.A, self.ctx, self.seed, diag=self.diag,
                    keep=lambda m, k: m >= k, symmetric=True)

    def pool(self):
        from parsec_tpu.apps.potrf import potrf_taskpool
        return potrf_taskpool(self.A, device="tpu")

    def check(self) -> dict:
        """The factor the last job left, held to the operand the seed
        defines (reference/potrf.py)."""
        A = self.A

        def factor(i, k):
            return tiles.newest(A, i, k)

        def operand(i, j):
            dev = getattr(factor(i, j), "device", None)
            return tiles.make_tile(A, self.seed, i, j,
                                   self.diag if i == j else 0.0, dev,
                                   symmetric=(i == j))

        r = reference.blockrow_residual(self.nt, A.mb, factor, operand,
                                        self.seed)
        # diag_resid is logged, not compared: its control reads under
        # three times the program (PERF.md, PR 24), so no limit could hold
        return {"numbers": {"offdiag_resid": r.pop("offdiag_resid")},
                "notes": r}

    def drop(self) -> None:
        tiles.discard_tiles(self.A)
        tiles.discard_scratch(self.ctx)


def control(config: dict, traffic: dict, seed: int, store: str) -> dict:
    """The plain reference in the program's place, at the cell's own
    size, its tiles stored as ``store`` says ("fp8": the nearest
    precision below the configuration's; "config": the configuration's
    own), held to the same comparison.  Where the configuration spreads
    the matrix, the control's tiles lie on the chips as
    ``distribute_devices`` would lay them (linear tile index mod chips)."""
    import types

    import jax
    n, mb = int(traffic["n"]), int(traffic["mb"])
    nt = n // mb
    diag = float(config["diag_over_sqrt_n"]) * n ** 0.5
    dtype = tiles.storage_dtype(config["storage"])
    A = types.SimpleNamespace(mb=mb, nb=mb, dtype=dtype, name="A")

    devs = jax.devices() if config.get("distribute") else jax.devices()[:1]

    def operand(i, j):
        return tiles.make_tile(A, seed, i, j, diag if i == j else 0.0,
                               devs[(i * nt + j) % len(devs)],
                               symmetric=(i == j))

    keep = reference.store_fp8 if store == "fp8" else reference.store_as(dtype)
    L = reference.plain_cholesky(
        {(i, j): operand(i, j) for i in range(nt) for j in range(i + 1)},
        nt, keep)
    return reference.blockrow_residual(nt, mb, lambda i, k: L[(i, k)],
                                       operand, seed)
