"""1D periodic stencil: iterative halo-exchange pipeline.

Rebuild of the reference's stencil mini-app (reference:
tests/apps/stencil/testing_stencil_1D.c + stencil_1D.jdf — a radius-R 1D
stencil iterated T times, each tile exchanging ghost regions with its
neighbors every step; the wavefront pipeline is the canonical PTG
pattern).  The stencil runs along the rows of the grid: a tile is
``mb`` rows of a 1-D vector, or ``mb`` rows x ``nb`` lanes of a matrix
one tile wide, every lane an independent 1-D problem (upstream's rows of
the matrix; on a TPU the lanes are what fills a vector register).

What crosses a tile boundary is the halo alone, as upstream's ``displ``
partial tiles: every ``S(t, i)`` writes, beside its tile ``C``, the top
and the bottom ``H`` rows of the new tile into two arena buffers
(``TOP``, ``BOT``), and reads its neighbours' as ``HL`` / ``HR``.  So a
tile has ONE consumer — the next sweep's writer of the same tile — and
is updated in place (the device module donates it); nothing is snapshot,
nothing copied but 2 x H rows a task.  ``H`` is the number of sweeps a
task runs (``fuse``): with ``fuse`` > 1 a task runs ``fuse`` sweeps in
one kernel over its tile widened by ``fuse`` rows either side, the
S-deep-halo trade for an overhead-bound fine-grained pipeline.

The same computation lowers to one shard_map program on a mesh
(parallel/spmd.halo_stencil_fn) — the task graph is the irregular/
multi-pool form, the SPMD schedule the regular one.
"""

from __future__ import annotations

import numpy as np

from parsec_tpu.core.taskpool import ParameterizedTaskpool
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.dsl.ptg.api import DATA, IN, NEW, OUT, PTG, Range, TASK

_kernels = {}


def _sweeps(xp, HL, C, HR, ns: int):
    """``ns`` sweeps of the 3-point mean over ``C`` between its halos, in
    ``xp`` (numpy or jax.numpy): the widened tile loses a row either
    side a sweep, so after ``ns`` <= H sweeps its middle ``mb`` rows are
    the new tile.  Returns the written flows in their declaration order:
    the new tile, its top H rows, its bottom H rows."""
    H, mb = HL.shape[0], C.shape[0]
    u = xp.concatenate([HL, C, HR])
    for _ in range(ns):
        u = (u[:-2] + u[2:] + u[1:-1]) / 3.0
    new = u[H - ns:H - ns + mb]
    return new, new[:H], new[mb - H:]


def _k_sweep():
    fn = _kernels.get("sweep")
    if fn is None:
        import jax
        import jax.numpy as jnp
        from parsec_tpu.apps.pallas_kernels import pallas_sweep_tile
        in_place = pallas_sweep_tile(
            lambda HL, C, HR: _sweeps(jnp, HL, C, HR, 1))

        def fn(HL, C, HR, ns):
            # ns is a task local -> static argnum: at most two distinct
            # programs a halo depth (full blocks + the remainder)
            if ns == HL.shape[0] == 1 and C.ndim == 2 \
                    and jax.default_backend() == "tpu":
                # one sweep of a (rows x lanes) tile on the chip: in
                # place, where XLA's own form costs a copy of the tile
                return in_place(HL, C, HR)
            return _sweeps(jnp, HL, C, HR, ns)
        _kernels["sweep"] = fn
    return fn


def _k_halos(H: int):
    fn = _kernels.get(("halos", H))
    if fn is None:
        def fn(X):
            return X[:H], X[X.shape[0] - H:]
        _kernels[("halos", H)] = fn
    return fn


def stencil_taskpool(V: TiledMatrix, steps: int,
                     device: str = "tpu",
                     fuse: int = 1) -> ParameterizedTaskpool:
    """Iterate the 3-point periodic mean stencil ``steps`` times along
    the rows of V, a vector of tiles or a matrix one tile wide (in
    place).

    ``fuse``: sweeps per task = rows of halo a task trades with each
    neighbour (requires ``fuse <= V.mb``).  ``-(-steps // fuse)`` blocks
    of one ``S`` task a tile; the last block carries the remainder as
    its ``ns`` local (reference harness:
    tests/apps/stencil/testing_stencil_1D.c)."""
    NT, mb, H = V.mt, V.mb, int(fuse)
    if H < 1 or H > mb:
        raise ValueError(f"fuse depth {fuse} must lie in 1..{mb}, the tile")
    if NT < 2:
        raise ValueError("stencil needs at least 2 tiles")
    if V.nt != 1 or V.lm % mb:
        raise ValueError("stencil needs one column of whole tiles")
    NB = -(-steps // H)          # blocks of H sweeps, the last ragged
    halos = _k_halos(H)

    def ns_of(globals_, locals_):
        return [min(H, steps - locals_["t"] * H)]

    def cpu_sweep(HL, C, HR, ns):
        return _sweeps(np, np.asarray(HL), np.asarray(C), np.asarray(HR),
                       int(ns))

    p = PTG("stencil", NT=NT, T=steps)
    p.arena("halo", (H,) + tuple(V.tile_shape(0, 0)[1:]), dtype=V.dtype)
    # INIT(i) reads each tile once, cuts its two halos for the t=0
    # neighbours and hands the tile on to its own S(0, i) — reading AND
    # writing a collection tile at the same wavefront without a dep edge
    # would be a DAG race (and remote reads are not allowed anyway).
    tb = p.task("INIT", i=Range(0, NT - 1)) \
        .affinity(lambda i, V=V: V(i)) \
        .flow("X", "READ",
              IN(DATA(lambda i, V=V: V(i))),
              OUT(TASK("S", "C", lambda i: dict(t=0, i=i)))) \
        .flow("TOP", "WRITE", IN(NEW("halo")),
              OUT(TASK("S", "HR", lambda i, NT=NT: dict(t=0,
                                                        i=(i - 1) % NT)))) \
        .flow("BOT", "WRITE", IN(NEW("halo")),
              OUT(TASK("S", "HL", lambda i, NT=NT: dict(t=0,
                                                        i=(i + 1) % NT))))
    if device in ("tpu", "xla", "gpu"):
        tb.body(halos, device=device)
    tb.body(lambda X: halos(np.asarray(X)))

    def last(t, NB=NB):
        return t == NB - 1

    def inner(t, NB=NB):
        return t < NB - 1

    def halo_in(flow, side):
        """The halo a task reads from its neighbour ``side`` (-1: the
        tile above, whose BOTTOM rows; +1: below, its TOP rows)."""
        def at(i, NT=NT):
            return (i + side) % NT
        return (IN(TASK("INIT", flow, lambda i: dict(i=at(i))),
                   when=lambda t: t == 0),
                IN(TASK("S", flow, lambda t, i: dict(t=t - 1, i=at(i))),
                   when=lambda t: t > 0))

    tb = p.task("S", t=Range(0, NB - 1), i=Range(0, NT - 1), ns=ns_of) \
        .affinity(lambda i, V=V: V(i)) \
        .priority(lambda t, NB=NB: NB - t) \
        .flow("HL", "READ", *halo_in("BOT", -1)) \
        .flow("HR", "READ", *halo_in("TOP", +1)) \
        .flow("C", "RW",
              IN(TASK("INIT", "X", lambda i: dict(i=i)),
                 when=lambda t: t == 0),
              IN(TASK("S", "C", lambda t, i: dict(t=t - 1, i=i)),
                 when=lambda t: t > 0),
              OUT(TASK("S", "C", lambda t, i: dict(t=t + 1, i=i)),
                  when=inner),
              OUT(DATA(lambda i, V=V: V(i)), when=last)) \
        .flow("TOP", "WRITE", IN(NEW("halo")),
              OUT(TASK("S", "HR", lambda t, i, NT=NT: dict(t=t + 1,
                                                           i=(i - 1) % NT)),
                  when=inner)) \
        .flow("BOT", "WRITE", IN(NEW("halo")),
              OUT(TASK("S", "HL", lambda t, i, NT=NT: dict(t=t + 1,
                                                           i=(i + 1) % NT)),
                  when=inner))
    if device in ("tpu", "xla", "gpu"):
        tb.body(_k_sweep(), device=device)
    tb.body(cpu_sweep)
    return p.build()


def stencil_reference(x: np.ndarray, steps: int) -> np.ndarray:
    """Serial reference of the same periodic stencil (along axis 0)."""
    u = x.astype(np.float64)
    for _ in range(steps):
        ext = np.concatenate([u[-1:], u, u[:1]])
        u = (ext[:-2] + ext[2:] + u) / 3.0
    return u
