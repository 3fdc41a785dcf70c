"""Plain reference for the 1-D stencil cell: the sweep itself, the useful
work of a job, and the comparison that decides ``correct``.  Imports
nothing of the program; its inputs are arrays, callables that hand out
tiles, and the seed.

The deployment: a grid of ``n`` rows x ``nb`` lanes, every lane an
independent 1-D periodic problem along the rows; one sweep replaces
every point by the mean of itself and its two neighbours in the row
direction (radius 1, weights 1/3 each), ``steps`` sweeps a job.  No
matrix product anywhere, so there is no matmul precision to set: the
arithmetic is elementwise float32 on the chip's vector unit, float64
where this file computes on the host.

``check`` is the comparison.  It reads two numbers:

  probe_max_err   max |left by the job - reference| over ALL n rows of a
                  seeded probe of lanes (``probe_lanes``: 64 drawn from
                  the seed, and the two edge lanes).  Lanes do not
                  interact, so the reference of a lane is exact: the
                  lane's seeded values swept ``steps`` times in float64
                  on the host.  Every tile and every tile boundary is
                  crossed by every probe lane.
  lane_sum_drift  max over ALL nb lanes of |sum of the lane after the
                  job - sum of the lane as the seed defines it|.  The
                  boundary is periodic and the weights add up to 1, so a
                  sweep conserves every lane's sum; a tile that is
                  stale, swept once too little or fed a wrong halo
                  breaks it in the lanes outside the probe too.  Tiles
                  are summed on the device in float32 over 128 rows at a
                  time and from there in float64 on the host, the seed's
                  tiles by the same program.
"""

from __future__ import annotations

import functools

import numpy as np

PROBE_LANES = 64
ROWS_PER_PARTIAL = 128


def flops(n: int, nb: int, steps: int) -> float:
    """Useful flop of a job: two adds and one multiply a point update
    (the kernel as written divides; a multiply by 1/3 is the count)."""
    return 3.0 * n * nb * steps


def bytes_moved(n: int, nb: int, steps: int, itemsize: int = 4) -> float:
    """Bytes a job has to move where every sweep's grid is materialised
    in memory: each point read once and written once a sweep.  The work,
    not what an implementation happens to move (halos, copies)."""
    return 2.0 * itemsize * n * nb * steps


def tasks(nt: int, steps: int) -> int:
    """One S task a tile a sweep, and one INIT a tile."""
    return nt * steps + nt


def sweep(u, xp=np):
    """One sweep along axis 0 with the periodic boundary, in u's dtype."""
    return (xp.roll(u, 1, axis=0) + xp.roll(u, -1, axis=0) + u) / 3.0


def probe_lanes(seed: int, nb: int) -> np.ndarray:
    """The lanes the probe reads: the two edge lanes and up to
    ``PROBE_LANES`` more drawn from the seed, sorted, each once."""
    rng = np.random.default_rng(seed)
    k = min(PROBE_LANES, nb)
    drawn = rng.choice(nb, size=k, replace=False)
    return np.unique(np.concatenate([[0, nb - 1], drawn])).astype(np.int32)


def lane_reference(cols: np.ndarray, steps: int) -> np.ndarray:
    """``cols`` (n rows x k lanes) swept ``steps`` times in float64."""
    u = np.asarray(cols, np.float64)
    for _ in range(steps):
        u = sweep(u)
    return u


@functools.lru_cache(maxsize=None)
def _reader():
    import jax
    import jax.numpy as jnp

    def bench_check_tile(T, lanes):
        """(mb, nb) -> the probe's lanes (mb, k) and every lane's sum
        over 128 rows at a time (mb / 128, nb)."""
        mb, nb = T.shape
        T = T.astype(jnp.float32)
        r = ROWS_PER_PARTIAL if mb % ROWS_PER_PARTIAL == 0 else mb
        return (jnp.take(T, lanes, axis=1),
                jnp.sum(T.reshape(mb // r, r, nb), axis=1))

    return jax.jit(bench_check_tile)


def _read(nt: int, tile, lanes) -> tuple:
    """(the probe lanes' columns over all tiles, every lane's sum)."""
    cols, total = [], 0.0
    for i in range(nt):
        c, partial = _reader()(tile(i), lanes)
        cols.append(np.asarray(c))
        total = total + np.asarray(partial, np.float64).sum(axis=0)
    return np.concatenate(cols), total


def check(nt: int, nb: int, steps: int, final_tile, operand_tile,
          seed: int) -> dict:
    """``final_tile(i)`` gives what the job left in tile i (mb x nb),
    ``operand_tile(i)`` the tile as the seed defines it, both as arrays
    on whatever device holds them.  Returns the two numbers and, for the
    log, their parts."""
    lanes = probe_lanes(seed, nb)
    seeded, seeded_sums = _read(nt, operand_tile, lanes)
    got, sums = _read(nt, final_tile, lanes)
    want = lane_reference(seeded, steps)
    err = np.abs(got.astype(np.float64) - want)
    drift = np.abs(sums - seeded_sums)

    def finite(x):
        x = float(x)
        return x if np.isfinite(x) else float("inf")

    worst = np.unravel_index(int(np.argmax(err)), err.shape)
    return {"probe_max_err": finite(err.max()),
            "lane_sum_drift": finite(drift.max()),
            "probe_lanes": int(lanes.size), "probe_rows": int(err.shape[0]),
            "probe_worst_row": int(worst[0]),
            "probe_worst_lane": int(lanes[worst[1]]),
            "probe_rms": finite(np.sqrt(np.mean(want ** 2))),
            "drift_worst_lane": int(np.argmax(drift))}


# ---------------------------------------------------------------------------
# the plain reference over the whole grid: whole lanes, no tiles, no halos
# ---------------------------------------------------------------------------

def round_bf16(x):
    """Round to bfloat16's eight significand bits, staying float32: the
    nearest precision below the float32 the configuration states.
    ``lax.reduce_precision`` does the rounding: a convert to bfloat16 and
    back is folded away by the TPU's compiler (PERF.md section 7)."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.lru_cache(maxsize=None)
def _plain(steps: int, store):
    import jax
    import jax.numpy as jnp

    def bench_ref_sweeps(u):
        def one(_, u):
            u = sweep(u, jnp)
            return u if store is None else store(u)
        return jax.lax.fori_loop(0, steps, one, u)

    return jax.jit(bench_ref_sweeps, donate_argnums=(0,))


def plain_sweeps(columns, steps: int, store=None):
    """``columns`` (n x w, whole lanes, float32; consumed) swept
    ``steps`` times in float32 on the device that holds it, every
    sweep's values rounded through ``store`` where one is given (what a
    grid stored in that precision keeps)."""
    return _plain(steps, store)(columns)
