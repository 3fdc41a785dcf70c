"""Tiled QR through ``qr_taskpool`` (DPLASMA dgeqrf, flat TS tree:
GEQRT / UNMQR / TSQRT / TSMQR with inner blocking ib).

The operand is a general matrix born on the device from the seed, as
``testing_dgeqrf`` makes its own (dplrnt): every entry from the counter
hash of ``benchmark/tiles.py``, mean 0 and variance 1, no structure and
nothing added on any diagonal.  R ends in the upper triangle of A, the
tiles below it zeroed; Q is applied and not stored, so the comparison
holds R to R^T R = A^T A (reference/geqrf.py).  The panels' Q factors
(``q1``, ``q2`` arena scratch, as much again as A) die with the job:
``stage()`` discards the last job's before it re-generates A.
"""

from __future__ import annotations

import math

from benchmark import tiles
from benchmark.reference import geqrf as reference


class Job:
    def __init__(self, config: dict, traffic: dict, ctx, seed: int):
        from parsec_tpu.data.matrix import TwoDimBlockCyclic
        self.ctx, self.seed = ctx, seed
        n, mb = int(traffic["n"]), int(traffic["mb"])
        self.ib = int(traffic["ib"])
        if n % mb:
            raise ValueError(f"geqrf: mb={mb} does not divide n={n}")
        self.nt = n // mb
        self.A = TwoDimBlockCyclic(
            mb=mb, nb=mb, lm=n, ln=n, name="A",
            dtype=tiles.storage_dtype(config["storage"]))
        self.flop = reference.flops(n)
        self.tasks = reference.tasks(self.nt)
        self.outputs = (self.A,)
        self.limits = config["limits"]

    def setup(self) -> None:
        """The inner blocking is the traffic mix's; and the job does not
        start on a program that cannot bound its chain programs (the
        parent of PR 31: every chain shape of every column would compile
        GEQRT or TSQRT once more, an hour of set-up at nt = 8)."""
        from parsec_tpu.apps.qr import effective_ib
        from parsec_tpu.utils.mca import params
        for dev in self.ctx.device_registry.accelerators:
            if "chain_programs" not in dev.stats.as_dict():
                raise RuntimeError(
                    "geqrf: this program does not count its chain "
                    "programs (DeviceStats.chain_programs): its default "
                    "path cannot run this cell in a run's time")
        params.set("qr_ib", self.ib)
        if effective_ib(self.A.mb) != self.ib:
            raise ValueError(f"geqrf: ib={self.ib} does not block "
                             f"mb={self.A.mb}")

    def stage(self) -> None:
        tiles.discard_scratch(self.ctx)          # the last job's Q panels
        tiles.stage(self.A, self.ctx, self.seed)

    def pool(self):
        from parsec_tpu.apps.qr import qr_taskpool
        return qr_taskpool(self.A, device="tpu")

    def check(self) -> dict:
        """What the last job left in A's tiles, held to the operand the
        seed defines (reference/geqrf.py)."""
        A = self.A

        def factor(i, j):
            return tiles.newest(A, i, j)

        def operand(i, j):
            dev = getattr(factor(i, j), "device", None)
            return tiles.make_tile(A, self.seed, i, j, 0.0, dev)

        r = reference.factor_check(self.nt, A.mb, factor, operand, self.seed)
        return {"numbers": {k: r.pop(k) for k in
                            ("factor_resid", "below_diag_max")},
                "notes": r}

    def drop(self) -> None:
        from parsec_tpu.utils.mca import params
        params.unset("qr_ib")
        tiles.discard_tiles(self.A)
        tiles.discard_scratch(self.ctx)


def control(config: dict, traffic: dict, seed: int, store: str) -> dict:
    """The plain reference in the program's place, at the cell's own
    size: Householder QR of the whole matrix in float32 at HIGHEST,
    rounded through a storage precision once a tile column (what the
    tiled algorithm stores), held to the same comparison.  ``store``
    "config": the configuration's own storage; "fp8": the nearest
    storage precision below it.  A configuration that carries
    ``"control_panel": "bfloat16"`` (``control.py --override``) has
    every number of the panel construction rounded to bfloat16's eight
    bits besides, by ``lax.reduce_precision``: the nearest precision
    below the HIGHEST the configuration states for the panel."""
    import types

    import jax
    import jax.numpy as jnp
    n, mb, ib = int(traffic["n"]), int(traffic["mb"]), int(traffic["ib"])
    nt = n // mb
    dtype = tiles.storage_dtype(config["storage"])
    A = types.SimpleNamespace(mb=mb, nb=mb, dtype=dtype, name="A")

    def operand(i, j):
        return tiles.make_tile(A, seed, i, j, 0.0)

    def bench_ref_place(M, t, r, c):
        return jax.lax.dynamic_update_slice(M, t.astype(jnp.float32), (r, c))

    place = jax.jit(bench_ref_place, donate_argnums=(0,))
    M = jnp.zeros((n, n), jnp.float32)
    for i in range(nt):
        for j in range(nt):
            M = place(M, operand(i, j), jnp.int32(i * mb), jnp.int32(j * mb))
    chunk = math.gcd(mb, 2048)
    R = reference.plain_qr(
        M, ib if chunk % ib == 0 else chunk, chunk,
        panel_round=reference.round_bf16
        if config.get("control_panel") == "bfloat16" else None,
        store=reference.store_fp8 if store == "fp8"
        else reference.store_as(dtype), store_every=mb)
    return reference.factor_check(
        nt, mb, lambda i, j: R[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb],
        operand, seed)
