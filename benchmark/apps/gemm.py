"""C += A B through ``gemm_taskpool`` (DPLASMA dgemm): A and B in the
configuration's storage dtype, C in float32, all born on the device from
the seed.  A and B are staged once; C is re-staged before every job so
that every job leaves C0 + A B.
"""

from __future__ import annotations

import numpy as np

from benchmark import tiles, work
from benchmark.reference import gemm as reference


class Job:
    def __init__(self, config: dict, traffic: dict, ctx, seed: int):
        from parsec_tpu.data.matrix import TwoDimBlockCyclic
        self.ctx, self.seed = ctx, seed
        m, n, k, mb = (int(traffic[x]) for x in ("m", "n", "k", "mb"))
        if m % mb or n % mb or k % mb:
            raise ValueError(f"gemm: mb={mb} does not divide {m}x{n}x{k}")
        ab = tiles.storage_dtype(config["storage"])
        self.A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=m, ln=k, name="A",
                                   dtype=ab)
        self.B = TwoDimBlockCyclic(mb=mb, nb=mb, lm=k, ln=n, name="B",
                                   dtype=ab)
        self.C = TwoDimBlockCyclic(mb=mb, nb=mb, lm=m, ln=n, name="C")
        self.kt = k // mb
        self.flop = work.gemm_flops(m, n, k)
        self.tasks = work.gemm_tasks(m // mb, n // mb, self.kt)
        self.outputs = (self.C,)
        self.limits = config["limits"]

    def setup(self) -> None:
        for M in (self.A, self.B):
            tiles.stage(M, self.ctx, self.seed)

    def stage(self) -> None:
        tiles.stage(self.C, self.ctx, self.seed)

    def pool(self):
        from parsec_tpu.apps.gemm import gemm_taskpool
        return gemm_taskpool(self.A, self.B, self.C)

    def check(self) -> dict:
        """Every C tile the last job left against C0 + A B
        (reference/gemm.py), on the device the tile lives on."""
        import jax
        worst, at = 0.0, None
        for (m, n) in self.C.local_tiles():
            got = tiles.newest(self.C, m, n)
            dev = getattr(got, "device", None)

            def here(t):
                return t if dev is None else jax.device_put(t, dev)
            # operands as the seed defines them, made as they are used
            ref = reference.reference_tile(
                tiles.make_tile(self.C, self.seed, m, n, device=dev),
                (tiles.make_tile(self.A, self.seed, m, k, device=dev)
                 for k in range(self.kt)),
                (tiles.make_tile(self.B, self.seed, k, n, device=dev)
                 for k in range(self.kt)))
            num, den = reference.gap(here(got), ref)
            rel = num / max(den, 1e-30)
            if not np.isfinite(rel):
                rel = float("inf")
            if at is None or rel > worst:
                worst, at = rel, (m, n)
        return {"numbers": {"c_rel_err": worst},
                "notes": {"worst_tile": list(at)}}

    def drop(self) -> None:
        tiles.discard_tiles(self.A, self.B, self.C)


def control(config: dict, traffic: dict, seed: int, store: str) -> dict:
    """The plain reference in the program's place at the cell's own
    size, A's and B's tiles rounded as ``store`` says ("fp8": the nearest
    precision below the configuration's; "config": left as they are),
    held to the same comparison, tile by tile.  One device."""
    import types
    m, n, k, mb = (int(traffic[x]) for x in ("m", "n", "k", "mb"))
    ab = tiles.storage_dtype(config["storage"])
    mats = {name: types.SimpleNamespace(mb=mb, nb=mb, dtype=dt, name=name)
            for name, dt in (("A", ab), ("B", ab), ("C", np.float32))}
    keep = reference.store_fp8 if store == "fp8" else None

    def product(i, j, rounded):
        return reference.reference_tile(
            tiles.make_tile(mats["C"], seed, i, j),
            (tiles.make_tile(mats["A"], seed, i, kk) for kk in range(k // mb)),
            (tiles.make_tile(mats["B"], seed, kk, j) for kk in range(k // mb)),
            store=rounded)

    worst = 0.0
    for i in range(m // mb):
        for j in range(n // mb):
            num, den = reference.gap(product(i, j, keep), product(i, j, None))
            worst = max(worst, num / max(den, 1e-30))
    return {"c_rel_err": worst}
