"""The 1-D stencil through ``stencil_taskpool`` (PaRSEC's
``tests/apps/stencil``: ``testing_stencil_1D`` over ``stencil_1D.jdf``).

The grid — ``n`` rows along which the stencil runs x ``nb`` lanes, each
lane an independent 1-D periodic problem — is a matrix one tile wide,
tiles of ``mb`` rows x ``nb`` lanes born on the device from the seed
(the counter hash of ``benchmark/tiles.py``, mean 0 and variance 1).  A
job is ``steps`` sweeps at one task a tile a sweep (``fuse`` = 1): every
sweep's grid is materialised in HBM, and what crosses a tile boundary is
the R = 1 boundary rows.  The result stays in the tiles; the comparison
holds it to the lanes of a seeded probe swept in float64 and to every
lane's conserved sum (reference/stencil.py).  The halo buffers (arena
scratch, 2 x nb floats a task) die with the job: ``stage()`` discards
the last job's before it re-generates the grid.
"""

from __future__ import annotations

from benchmark import tiles
from benchmark.reference import stencil as reference

NUMBERS = ("probe_max_err", "lane_sum_drift")
#: bytes of the block of whole lanes the control sweeps at a time: the
#: block and the sweep's temporaries (a few times as much) fit beside
#: the result at any n
CONTROL_BLOCK_BYTES = 384 * 2 ** 20


def _sizes(traffic: dict) -> tuple:
    n, nb, mb = int(traffic["n"]), int(traffic["nb"]), int(traffic["mb"])
    if n % mb:
        raise ValueError(f"stencil: mb={mb} does not divide n={n}")
    return n, nb, mb, int(traffic["steps"])


class Job:
    def __init__(self, config: dict, traffic: dict, ctx, seed: int):
        from parsec_tpu.data.matrix import TwoDimBlockCyclic
        self.ctx, self.seed = ctx, seed
        n, nb, mb, self.steps = _sizes(traffic)
        self.nt = n // mb
        self.V = TwoDimBlockCyclic(
            mb=mb, nb=nb, lm=n, ln=nb, name="V",
            dtype=tiles.storage_dtype(config["storage"]))
        self.flop = reference.flops(n, nb, self.steps)
        self.tasks = reference.tasks(self.nt, self.steps)
        self.outputs = (self.V,)
        self.limits = config["limits"]

    def setup(self) -> None:
        pass                     # nothing outlives a job: V is overwritten

    def stage(self) -> None:
        tiles.discard_scratch(self.ctx)          # the last job's halos
        tiles.stage(self.V, self.ctx, self.seed)

    def pool(self):
        from parsec_tpu.apps.stencil import stencil_taskpool
        return stencil_taskpool(self.V, self.steps, device="tpu", fuse=1)

    def check(self) -> dict:
        """What the last job left in the tiles, held to the grid the
        seed defines (reference/stencil.py)."""
        V = self.V

        def final(i):
            return tiles.newest(V, i, 0)

        def operand(i):
            dev = getattr(final(i), "device", None)
            return tiles.make_tile(V, self.seed, i, 0, 0.0, dev)

        r = reference.check(self.nt, V.nb, self.steps, final, operand,
                            self.seed)
        return {"numbers": {k: r.pop(k) for k in NUMBERS}, "notes": r}

    def drop(self) -> None:
        tiles.discard_tiles(self.V)
        tiles.discard_scratch(self.ctx)


def control(config: dict, traffic: dict, seed: int, store: str) -> dict:
    """The plain reference in the program's place, at the cell's own
    size: whole lanes (no tiles, no halos), ``CONTROL_BLOCK_BYTES`` of
    them at a time (512 lanes at n = 196 608), all ``steps`` sweeps in
    float32 on the chip, held to the same comparison.  ``store`` "config": the configuration's own float32;
    "fp8" (control.py's name for one precision lower): every sweep's
    values rounded to bfloat16's eight bits, the nearest precision
    below float32."""
    import types

    import jax.numpy as jnp
    n, nb, mb, steps = _sizes(traffic)
    nt = n // mb
    V = types.SimpleNamespace(mb=mb, nb=nb, name="V",
                              dtype=tiles.storage_dtype(config["storage"]))
    w = max(1, min(nb, CONTROL_BLOCK_BYTES // (n * 4)))   # float32 columns

    def operand(i):
        return tiles.make_tile(V, seed, i, 0)

    keep = reference.round_bf16 if store == "fp8" else None
    blocks = []
    for lo in range(0, nb, w):
        cols = jnp.concatenate([operand(i)[:, lo:lo + w].astype(jnp.float32)
                                for i in range(nt)])
        blocks.append(reference.plain_sweeps(cols, steps, keep))

    def final(i):
        return jnp.concatenate([b[i * mb:(i + 1) * mb] for b in blocks],
                               axis=1)

    return reference.check(nt, nb, steps, final, operand, seed)
