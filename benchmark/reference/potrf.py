"""Plain reference for the tiled Cholesky cells: what a factor has to
satisfy, and a straightforward factorization to stand in the program's
place as the control.  Imports nothing of the program; its inputs are
tiles (arrays) and the seed.

``blockrow_residual`` is the comparison that decides ``correct``.  With
R = A - L L^T, A the operand as the seed defines it and L the factor's
tiles, it reads two numbers, each the worst block row's:

  offdiag_resid  ||(R_off X)_i||_F / ||(A_off X)_i||_F, where _off takes
                 the matrix's diagonal entries out and X is a Gaussian
                 probe of ``kp`` columns drawn from the seed.  A wrong,
                 stale or missing tile anywhere in block row or block
                 column i shows here, and so does the rounding of every
                 tile the tiled algorithm stores.
  diag_resid     ||diag(R)_i|| / ||diag(A)_i||: the diagonal alone,
                 computed exactly from the row sums of squares of L.

They are read apart because a diagonal of c sqrt(n) carries c^2 times
the weight of a whole row of off-diagonal entries: stored in bfloat16
it drifts by half a spacing at every update (43 of 1254 after 14
updates at n = 98 304: 3e-2 of the row, measured in PR 24), and under
that drift no off-diagonal fault would show.  (DPLASMA's testers hold
||A - L L^T|| with the full product, O(n^3); the probe costs O(n^2 kp).)
All products are float32 at HIGHEST precision and run on the device
their tile lives on, so a factor spread over four chips is checked where
it lies; only (mb x kp) blocks and (mb) vectors travel.
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _kernels():
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def f32(t, lower):
        t = t.astype(jnp.float32)
        return jnp.tril(t) if lower else t

    def bench_check_tx(T, x, lower):        # T^T x
        return jnp.matmul(f32(T, lower).T, x, precision=hi)

    def bench_check_x(T, x, lower):         # T x
        return jnp.matmul(f32(T, lower), x, precision=hi)

    def bench_check_symx(T, x):             # sym(T) x, T's lower triangle
        t = T.astype(jnp.float32)
        return jnp.matmul(jnp.tril(t) + jnp.tril(t, -1).T, x, precision=hi)

    def bench_check_rowsq(T, lower):        # row sums of squares
        return jnp.sum(f32(T, lower) ** 2, axis=1)

    def bench_check_diag(T):
        return jnp.diag(T.astype(jnp.float32))

    jit = functools.partial(jax.jit, static_argnames=("lower",))
    return (jit(bench_check_tx), jit(bench_check_x), jax.jit(bench_check_symx),
            jit(bench_check_rowsq), jax.jit(bench_check_diag))


def blockrow_residual(nt: int, mb: int, factor_tile, operand_tile,
                      seed: int, kp: int = 32) -> dict:
    """``factor_tile(i, k)`` / ``operand_tile(i, j)`` give lower tiles
    (i >= k) as arrays on whatever device holds them.  Returns the two
    numbers, the block rows they are worst in, and the undivided
    residual of the worst row and of the whole matrix (for the log)."""
    tx, x_, symx, rowsq, diag = _kernels()
    rng = np.random.default_rng(seed)
    X = [rng.standard_normal((mb, kp)).astype(np.float32)
         for _ in range(nt)]
    zeros = lambda *shape: [np.zeros(shape, np.float64) for _ in range(nt)]

    Y, dL = zeros(mb, kp), zeros(mb)         # Y = L^T X, dL = diag(L L^T)
    for k in range(nt):
        for i in range(k, nt):
            L = factor_tile(i, k)
            Y[k] += np.asarray(tx(L, X[i], lower=(i == k)))
            dL[i] += np.asarray(rowsq(L, lower=(i == k)))
    Y32 = [y.astype(np.float32) for y in Y]
    Z = zeros(mb, kp)                        # Z = L Y
    for i in range(nt):
        for k in range(i + 1):
            Z[i] += np.asarray(x_(factor_tile(i, k), Y32[k], lower=(i == k)))
    B, dA = zeros(mb, kp), zeros(mb)         # B = A X, A symmetric
    for i in range(nt):
        for j in range(i + 1):
            A = operand_tile(i, j)
            if i == j:
                B[i] += np.asarray(symx(A, X[i]))
                dA[i] += np.asarray(diag(A))
            else:
                B[i] += np.asarray(x_(A, X[j], lower=False))
                B[j] += np.asarray(tx(A, X[i], lower=False))

    def worst(num, den):
        rel = np.array([np.linalg.norm(a) / max(np.linalg.norm(b), 1e-300)
                        for a, b in zip(num, den)])
        rel = np.where(np.isfinite(rel), rel, np.inf)
        return float(rel.max()), int(rel.argmax())

    R = [B[i] - Z[i] for i in range(nt)]
    dR = [dA[i] - dL[i] for i in range(nt)]
    off, off_row = worst([R[i] - dR[i][:, None] * X[i] for i in range(nt)],
                         [B[i] - dA[i][:, None] * X[i] for i in range(nt)])
    dia, dia_row = worst(dR, dA)
    row, _ = worst(R, B)
    whole = float(np.sqrt(sum(np.sum(r ** 2) for r in R)
                          / max(sum(np.sum(b ** 2) for b in B), 1e-300)))
    return {"offdiag_resid": off, "offdiag_row": off_row,
            "diag_resid": dia, "diag_row": dia_row,
            "row_resid": row, "whole_resid": whole}


# ---------------------------------------------------------------------------
# the control: the same factorization, plainly, at a chosen storage
# ---------------------------------------------------------------------------

FP8_MAX = 240.0          # largest finite e4m3 with IEEE's exponent range


def store_fp8(t):
    """Round a tile to e4m3 (four exponent bits, three mantissa bits)
    with one power-of-two scale a tile (its largest magnitude lands in
    (120, 240]), as an fp8 store would keep it: the nearest precision
    below bfloat16.  ``lax.reduce_precision`` does the rounding: a
    convert to ``float8_e4m3fn`` and back is folded away by the TPU's
    compiler and rounds nothing (seen on the chip, PR 24).  The stored
    values come back exactly in bfloat16 (three mantissa bits and a
    power of two), which keeps a full-size control in the memory the
    program's own tiles take."""
    import jax
    import jax.numpy as jnp
    t = t.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30)
    s = jnp.exp2(jnp.ceil(jnp.log2(amax / FP8_MAX)))
    return (jax.lax.reduce_precision(t / s, exponent_bits=4, mantissa_bits=3)
            * s).astype(jnp.bfloat16)


def store_as(dtype):
    """Round a tile through ``dtype`` (the configuration's own storage)."""
    def store(t):
        return t.astype(dtype)
    return store


def plain_cholesky(tiles: dict, nt: int, store) -> dict:
    """Right-looking tiled lower Cholesky of ``tiles[(i, j)]`` (i >= j),
    float32 arithmetic at HIGHEST precision, every tile passed through
    ``store`` whenever the tiled algorithm writes it back.  Empties
    ``tiles`` as it takes them (a full-size control has no room for two
    copies) and returns the factor's tiles as stored, each on the device
    its operand tile came on."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def potrf(T):
        t = T.astype(jnp.float32)
        return store(jnp.linalg.cholesky(jnp.tril(t) + jnp.tril(t, -1).T))

    @jax.jit
    def trsm(Lkk, C):
        return store(solve_triangular(
            jnp.tril(Lkk.astype(jnp.float32)), C.astype(jnp.float32).T,
            lower=True).T)

    @jax.jit
    def update(C, L, R):
        return store(C.astype(jnp.float32) - jnp.matmul(
            L.astype(jnp.float32), R.astype(jnp.float32).T, precision=hi))

    def at(t, home):
        """``t`` where ``home`` lives: owner computes, operands travel."""
        return t if t.device == home.device else jax.device_put(t, home.device)

    with jax.default_matmul_precision("highest"):
        first = jax.jit(store)
        A = {t: first(tiles.pop(t)) for t in sorted(tiles)}
        for k in range(nt):
            A[(k, k)] = potrf(A[(k, k)])
            for m in range(k + 1, nt):
                A[(m, k)] = trsm(at(A[(k, k)], A[(m, k)]), A[(m, k)])
            for m in range(k + 1, nt):
                for n in range(k + 1, m + 1):
                    C = A[(m, n)]
                    A[(m, n)] = update(C, at(A[(m, k)], C), at(A[(n, k)], C))
    return A
