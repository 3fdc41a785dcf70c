"""Tiles born on the device from the seed, and the hand-over of a job's
operands and results between the benchmark and the runtime.

Copies of the helpers PR 21 proved on the chip (``bench.prestage`` /
``_discard_device_*``, ``chip_smoke._sync_tiles``), kept here so that
the yardstick does not move when those files do.  The generator is the
benchmark's own: a counter hash per element, so that re-staging a job's
operands inside the window costs one cheap elementwise program per tile.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np


def storage_dtype(name: str):
    """numpy dtype of a configuration's ``storage`` string."""
    if name == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.dtype(name).type


def tile_key(seed: int, matrix: str, m: int, n: int) -> np.uint32:
    """The 32-bit key of one tile: every (seed, matrix, tile) its own."""
    return np.uint32(zlib.crc32(f"{seed}/{matrix}/{m}/{n}".encode()))


@functools.lru_cache(maxsize=None)
def generator(mb: int, nb: int, dtype_name: str, symmetric: bool = False):
    """Jitted ``bench_stage_tile(key, diag) -> (mb, nb) tile``: entries
    uniform with mean 0 and variance 1 (a lowbias32 hash of row, column
    and key), plus ``diag`` on the diagonal, rounded to the storage
    dtype.  ``symmetric`` tiles (the diagonal tiles of a symmetric
    matrix) hash the sorted pair, so that a reader of the lower triangle
    and one of the whole tile see the same matrix.  Named so that the
    trace reduction can tell the benchmark's staging from the runtime's
    programs."""
    import jax
    import jax.numpy as jnp
    dtype = storage_dtype(dtype_name)

    def bench_stage_tile(key, diag):
        u32 = jnp.uint32
        r = jax.lax.broadcasted_iota(u32, (mb, nb), 0)
        c = jax.lax.broadcasted_iota(u32, (mb, nb), 1)
        if symmetric:
            r, c = jnp.maximum(r, c), jnp.minimum(r, c)
        h = r * u32(0x9E3779B1) + c * u32(0x85EBCA77) + key.astype(u32)
        h = (h ^ (h >> 16)) * u32(0x7FEB352D)
        h = (h ^ (h >> 15)) * u32(0x846CA68B)
        h = h ^ (h >> 16)
        u = (h >> 8).astype(jnp.float32) * (2.0 ** -24)        # [0, 1)
        out = (u - 0.5) * np.float32(12.0 ** 0.5)
        out = out + diag * jnp.eye(mb, nb, dtype=jnp.float32)
        return out.astype(dtype)

    return jax.jit(bench_stage_tile)


@functools.lru_cache(maxsize=None)
def _tile_args(seed: int, matrix: str, m: int, n: int, diag: float, device):
    """The generator's two scalars, resident on ``device``: re-staging a
    job's tiles inside the window then moves nothing from the host."""
    import jax
    return (jax.device_put(tile_key(seed, matrix, m, n), device),
            jax.device_put(np.float32(diag), device))


def make_tile(M, seed: int, m: int, n: int, diag: float = 0.0, device=None,
              symmetric: bool = False):
    """Tile (m, n) of ``M`` as the seed defines it, on ``device`` (the
    default device when None)."""
    import jax
    gen = generator(M.mb, M.nb, np.dtype(M.dtype).name, symmetric)
    device = device or jax.devices()[0]
    return gen(*_tile_args(seed, M.name, m, n, float(diag), device))


def stage(M, ctx, seed: int, diag: float = 0.0, keep=None,
          symmetric: bool = False) -> None:
    """Give every local tile of ``M`` that ``keep`` admits its seeded
    value as the newest authoritative copy, born on the device the tile
    is pinned to (``distribute_devices``) or on the first accelerator.
    Diagonal tiles get ``diag`` added on their diagonal and, of a
    ``symmetric`` matrix, are symmetric themselves."""
    devs = ctx.device_registry.accelerators
    by_space = {d.space: d for d in devs}
    for (m, n) in M.local_tiles():
        if keep is not None and not keep(m, n):
            continue
        datum = M.data_of(m, n)
        dev = by_space.get(datum.preferred_device, devs[0])
        arr = make_tile(M, seed, m, n, diag if m == n else 0.0, dev.jdev,
                        symmetric and m == n)
        datum.overwrite_on(dev.space, arr)


def newest(M, m: int, n: int):
    """The newest payload of tile (m, n): a device array on the chip
    path, numpy where the tile only ever lived on the host."""
    d = M.data_of(m, n)
    v = d.newest_version()
    for c in d.copies().values():
        if c.version == v and c.payload is not None:
            return c.payload
    return d.pull_to_host().payload


def fence(*Ms) -> None:
    """Block until every tile's newest payload has materialized, tile by
    tile (the tiles of a distributed matrix sit on different devices)."""
    import jax
    for M in Ms:
        for (m, n) in M.local_tiles():
            p = newest(M, m, n)
            if not isinstance(p, np.ndarray):
                jax.block_until_ready(p)


def discard_tiles(*Ms) -> None:
    """Drop device copies without writeback: the data is synthetic, and
    the context-exit flush would bring gigabytes to the host."""
    from parsec_tpu.data.data import Coherency
    for M in Ms:
        for (m, n) in M.local_tiles():
            d = M.data_of(m, n)
            with d._lock:
                for sp, c in list(d.copies().items()):
                    if sp != 0 and c.payload is not None:
                        d.detach_copy(sp)
                        c.payload = None
                        c.coherency = Coherency.INVALID


def discard_scratch(ctx) -> None:
    """Drop the arena temporaries of the last job (potrf's W inverses)."""
    for dev in ctx.device_registry.accelerators:
        dev.discard_scratch()
