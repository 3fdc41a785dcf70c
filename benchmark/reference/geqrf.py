"""Plain reference for the tiled QR cell: what a factor has to satisfy,
the useful work of a job, and a straightforward Householder QR to stand
in the program's place.  Imports nothing of the program; its inputs are
arrays and the seed.

``factor_check`` is the comparison that decides ``correct``.  The
program factors A = Q R in place and applies Q without storing it, so
what a caller can hold it to is R alone: R^T R = A^T A fixes R up to the
signs of its rows.  It reads two numbers:

  factor_resid    ||R^T R Z - A^T A Z||_F / ||A^T A Z||_F, Z a Gaussian
                  probe of ``kp`` columns drawn from the seed, A the
                  operand as the seed defines it, re-generated block row
                  by block row (a block row of tiles is all that lives
                  beside the factor's own tiles).  A wrong, stale or
                  missing tile anywhere in R shows here, and so does the
                  rounding of every tile the tiled algorithm stores.
  below_diag_max  the largest |entry| left under the diagonal (the tiles
                  below it and the strict lower part of the diagonal
                  tiles) over the largest |entry| of R: the algorithm
                  zeroes them, so anything else is a tile it left behind.

(DPLASMA's testers hold ||A - Q R|| and ||I - Q^T Q|| with Q formed,
O(n^3) and a second matrix; the probe costs O(n^2 kp).)  All products
are float32 at HIGHEST precision on the device the tile lives on; only
(mb x kp) blocks travel.
"""

from __future__ import annotations

import functools

import numpy as np


def flops(n: int) -> float:
    """Useful flop of the QR factorization of an n x n matrix
    (LAPACK's dgeqrf count, 2mn^2 - 2n^3/3 at m = n): 4 n^3 / 3."""
    return 4.0 * n ** 3 / 3.0


def tasks(nt: int) -> int:
    """Tasks of the flat-tree tile QR over an nt x nt grid: nt GEQRT +
    nt(nt-1)/2 UNMQR + nt(nt-1)/2 TSQRT + (nt-1)nt(2nt-1)/6 TSMQR."""
    return nt + nt * (nt - 1) + (nt - 1) * nt * (2 * nt - 1) // 6


@functools.lru_cache(maxsize=None)
def _kernels():
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def f32(t, upper):
        t = t.astype(jnp.float32)
        return jnp.triu(t) if upper else t

    def bench_check_x(T, x, upper):         # T x
        return jnp.matmul(f32(T, upper), x, precision=hi)

    def bench_check_tx(T, x, upper):        # T^T x
        return jnp.matmul(f32(T, upper).T, x, precision=hi)

    def bench_check_absmax(T, part):
        t = jnp.abs(T.astype(jnp.float32))
        if part == "lower":                 # strictly under the diagonal
            t = jnp.tril(t, -1)
        elif part == "upper":
            t = jnp.triu(t)
        return jnp.max(t)

    return (jax.jit(bench_check_x, static_argnames=("upper",)),
            jax.jit(bench_check_tx, static_argnames=("upper",)),
            jax.jit(bench_check_absmax, static_argnames=("part",)))


def factor_check(nt: int, mb: int, factor_tile, operand_tile, seed: int,
                 kp: int = 32) -> dict:
    """``factor_tile(i, j)`` gives what the job left in tile (i, j),
    ``operand_tile(i, j)`` the operand's tile as the seed defines it,
    both as arrays on whatever device holds them.  Returns the two
    numbers and, for the log, their parts."""
    x_, tx, absmax = _kernels()
    rng = np.random.default_rng(seed)
    Z = [rng.standard_normal((mb, kp)).astype(np.float32)
         for _ in range(nt)]
    zeros = lambda: [np.zeros((mb, kp), np.float64) for _ in range(nt)]

    W = zeros()                              # W = A^T (A Z)
    for i in range(nt):                      # one block row of A at a time
        row = [operand_tile(i, j) for j in range(nt)]
        y = np.zeros((mb, kp), np.float64)
        for j in range(nt):
            y += np.asarray(x_(row[j], Z[j], upper=False))
        y = y.astype(np.float32)
        for j in range(nt):
            W[j] += np.asarray(tx(row[j], y, upper=False))
        del row

    V = zeros()                              # V = R^T (R Z), R upper
    r_max = low_max = 0.0
    for i in range(nt):
        u = np.zeros((mb, kp), np.float64)
        for j in range(i, nt):
            u += np.asarray(x_(factor_tile(i, j), Z[j], upper=(i == j)))
        u = u.astype(np.float32)
        for j in range(nt):
            t = factor_tile(i, j)
            if j < i:
                low_max = max(low_max, float(absmax(t, part="all")))
                continue
            V[j] += np.asarray(tx(t, u, upper=(i == j)))
            if i == j:
                low_max = max(low_max, float(absmax(t, part="lower")))
            r_max = max(r_max, float(absmax(
                t, part="upper" if i == j else "all")))

    num = float(np.sqrt(sum(np.sum((v - w) ** 2) for v, w in zip(V, W))))
    den = float(np.sqrt(sum(np.sum(w ** 2) for w in W)))

    def ratio(a, b):
        r = a / b if b > 0 else np.inf
        return float(r) if np.isfinite(r) else float("inf")

    return {"factor_resid": ratio(num, den),
            "below_diag_max": ratio(low_max, r_max),
            "ata_z_norm": den, "r_absmax": r_max, "below_absmax": low_max}


# ---------------------------------------------------------------------------
# the plain reference: Householder QR, no tiles, no kernels of the program
# ---------------------------------------------------------------------------

def round_bf16(x):
    """Round to bfloat16's eight significand bits, staying float32:
    float32 arithmetic on such values is the nearest precision below
    the configuration's HIGHEST-precision panel construction.
    ``lax.reduce_precision`` does the rounding: a convert to bfloat16
    and back is folded away by the TPU's compiler (PERF.md section 7)."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.lru_cache(maxsize=None)
def _qr_step(n: int, b: int, chunk: int, panel_round, store):
    """Jitted ``step(A, k) -> A``: eliminate columns [k, k + b) of the
    n x n float32 matrix below the diagonal by ``b`` Householder
    reflectors, built one column at a time (LAPACK's dgeqr2 with dlarft
    beside it), and apply them to the columns to the right as one
    compact-WY transform, ``chunk`` columns at a time so that the
    update's temporary stays small beside the matrix.  ``panel_round``
    rounds every number the panel construction produces (None: float32
    throughout); ``store`` rounds the whole matrix after the step (what
    a tiled algorithm's storage does to it), None: never."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    rnd = panel_round or (lambda x: x)
    rows = jnp.arange(n)

    def panel(P, k):
        """Householder vectors V (unit diagonal at row k + j), the
        triangular factor T of Q = I - V T V^T, and the panel itself
        with R on and above that diagonal."""
        def col(j, carry):
            P, V, T = carry
            d = k + j                                   # the diagonal row
            x = jnp.where(rows >= d, lax.dynamic_slice(
                P, (0, j), (n, 1))[:, 0], 0.0)
            xd = lax.dynamic_slice(x, (d,), (1,))[0]
            norm = jnp.sqrt(jnp.sum(x * x))
            beta = jnp.where(xd >= 0, -norm, norm)
            safe = norm > 0
            tau = rnd(jnp.where(safe, (beta - xd)
                                / jnp.where(safe, beta, 1.0), 0.0))
            v = jnp.where(rows == d, 1.0, x / jnp.where(
                safe, xd - beta, 1.0))
            v = rnd(jnp.where(rows >= d, v, 0.0))
            # H P = P - tau v (v^T P)
            P = rnd(P - tau * v[:, None] * (v @ P)[None, :])
            # dlarft: T[:j, j] = -tau T[:j, :j] (V[:, :j]^T v)
            t = -tau * (T @ (V.T @ v))
            T = lax.dynamic_update_slice(
                T, rnd(jnp.where(jnp.arange(b) < j, t,
                                 jnp.where(jnp.arange(b) == j, tau, 0.0))
                       )[:, None], (0, j))
            V = lax.dynamic_update_slice(V, v[:, None], (0, j))
            return P, V, T

        zero = jnp.zeros((n, b), jnp.float32)
        return lax.fori_loop(0, b, col,
                             (P, zero, jnp.zeros((b, b), jnp.float32)))

    def bench_ref_qr_step(A, k):
        P, V, T = panel(lax.dynamic_slice(A, (0, k), (n, b)), k)
        A = lax.dynamic_update_slice(A, P, (0, k))
        cols = jnp.arange(chunk)

        def update(c, A):
            # Q^T C = C - V T^T (V^T C) on the columns right of the panel
            C = lax.dynamic_slice(A, (0, c * chunk), (n, chunk))
            Wc = T.T @ (V.T @ C)
            Wc = jnp.where((c * chunk + cols >= k + b)[None, :], Wc, 0.0)
            return lax.dynamic_update_slice(A, C - V @ Wc, (0, c * chunk))

        A = lax.fori_loop((k + b) // chunk, n // chunk, update, A)
        return A if store is None else store(A)

    def run(A, k):
        with jax.default_matmul_precision("highest"):
            return bench_ref_qr_step(A, k)

    run.__name__ = run.__qualname__ = "bench_ref_qr_step"
    return jax.jit(run, donate_argnums=(0,))


def plain_qr(A, b: int, chunk: int, panel_round=None, store=None,
             store_every: int = 0):
    """Householder QR of the square float32 matrix ``A`` (consumed), in
    float32 under ``jax.default_matmul_precision("highest")``: returns
    the matrix with R on and above the diagonal and zeros below.  ``b``
    columns are eliminated a step (``b`` divides ``chunk``, ``chunk``
    divides n); ``store`` rounds the whole matrix after every
    ``store_every`` columns, as a tiled algorithm with tiles that wide
    rounds what it stores."""
    import jax.numpy as jnp
    n = A.shape[0]
    if n % chunk or chunk % b:
        raise ValueError(f"plain_qr: need b | chunk | n, got {b}, "
                         f"{chunk}, {n}")
    plain = _qr_step(n, b, chunk, panel_round, None)
    stored = _qr_step(n, b, chunk, panel_round, store) \
        if store is not None else plain
    for k in range(0, n, b):
        last = store_every and (k + b) % store_every == 0
        A = (stored if last else plain)(A, jnp.int32(k))
    return _upper(store)(A)


@functools.lru_cache(maxsize=None)
def _upper(store):
    """Jitted ``A -> store(triu(A))`` in A's own buffer: at the cell's
    size there is no room for a second matrix."""
    import jax
    import jax.numpy as jnp

    def bench_ref_upper(A):
        R = jnp.triu(A)
        return R if store is None else store(R)
    return jax.jit(bench_ref_upper, donate_argnums=(0,))


FP8_MAX = 240.0          # largest finite e4m3 with IEEE's exponent range


def store_fp8(t):
    """Round to e4m3 (four exponent bits, three significand bits) under
    one power-of-two scale for the whole matrix (its largest magnitude
    lands in (120, 240]), as an fp8 store would keep it: the nearest
    storage precision below bfloat16.  Stays float32."""
    import jax
    import jax.numpy as jnp
    amax = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30)
    s = jnp.exp2(jnp.ceil(jnp.log2(amax / FP8_MAX)))
    return jax.lax.reduce_precision(t / s, exponent_bits=4,
                                    mantissa_bits=3) * s


@functools.lru_cache(maxsize=None)
def store_as(dtype):
    """Round through ``dtype`` (the configuration's own storage),
    staying float32; one function a dtype (``_qr_step`` is kept by it)."""
    import jax.numpy as jnp

    def store(t):
        if jnp.dtype(dtype) == jnp.bfloat16:
            return round_bf16(t)
        return t.astype(dtype).astype(jnp.float32)
    return store
