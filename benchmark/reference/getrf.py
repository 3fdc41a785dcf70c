"""Plain reference for the tile LU cell (no pivoting): the useful work of
a job, what a factor has to satisfy, and a straightforward tiled LU to
stand in the program's place as the control.  Imports nothing of the
program; its inputs are tiles (arrays) and the seed.

``factor_check`` is the comparison that decides ``correct``.  With
R = A - L U, A the operand as the seed defines it, L the unit lower
triangle and U the upper triangle of what the job left packed in the
tiles, it reads the two numbers ``reference/potrf.py`` defines, each the
worst block row's:

  offdiag_resid  ||(R_off X)_i||_F / ||(A_off X)_i||_F, _off taking the
                 matrix's diagonal entries out, X a Gaussian probe of
                 ``kp`` columns drawn from the seed.  A wrong, stale or
                 missing tile anywhere in block row or block column i
                 shows here, and so does the rounding of every tile the
                 tiled algorithm stores.
  diag_resid     ||diag(R)_i|| / ||diag(A)_i||, the diagonal alone,
                 computed exactly from the row-wise products of L's rows
                 with U's columns (logged: a diagonal of 4 sqrt(n) drifts
                 by its own storage rounding, as potrf's does).

All products are float32 at HIGHEST precision on the device the tile
lives on; only (mb x kp) blocks and (mb) vectors travel.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.reference.potrf import store_as, store_fp8  # noqa: F401


def flops(n: int) -> float:
    """Useful flop of the LU factorization of an n x n matrix: 2 n^3 / 3."""
    return 2.0 * n ** 3 / 3.0


def tasks(nt: int) -> int:
    """Tasks of the tile LU over an nt x nt grid: nt GETRF (the last one
    GETRFL) + nt(nt-1)/2 TRSM of each panel family + (nt-1)nt(2nt-1)/6
    GEMM = nt(nt+1)(2nt+1)/6."""
    return nt * (nt + 1) * (2 * nt + 1) // 6


def class_flops(cls: str, mb: int) -> float:
    """Useful flop of ONE task of ``cls`` on mb x mb tiles (LAPACK's
    counts): the diagonal tile's LU 2 mb^3 / 3, a triangular solve
    against it mb^3, the trailing update 2 mb^3."""
    return {"GETRF": 2.0 * mb ** 3 / 3.0, "TRSM": float(mb) ** 3,
            "GEMM": 2.0 * float(mb) ** 3}[cls]


def panel_flops(n: int, mb: int) -> float:
    """Useful flop of a job's panel tasks: nt GETRF (GETRFL among them)
    and nt(nt-1)/2 TRSM of each family."""
    nt = n // mb
    return nt * class_flops("GETRF", mb) + nt * (nt - 1) * class_flops(
        "TRSM", mb)


@functools.lru_cache(maxsize=None)
def _kernels():
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def part(T, which):
        t = T.astype(jnp.float32)
        if which == "upper":                   # U's part of a diagonal tile
            return jnp.triu(t)
        if which == "lower":                   # L's: unit diagonal
            return jnp.tril(t, -1) + jnp.eye(t.shape[0], dtype=t.dtype)
        return t

    def bench_check_x(T, x, which):            # part(T) x
        return jnp.matmul(part(T, which), x, precision=hi)

    def bench_check_rowdot(L, U, diagonal):    # diag(L U) of the pair
        w = ("lower", "upper") if diagonal else ("full", "full")
        return jnp.sum(part(L, w[0]) * part(U, w[1]).T, axis=1)

    def bench_check_diag(T):
        return jnp.diag(T.astype(jnp.float32))

    return (jax.jit(bench_check_x, static_argnames=("which",)),
            jax.jit(bench_check_rowdot, static_argnames=("diagonal",)),
            jax.jit(bench_check_diag))


def factor_check(nt: int, mb: int, factor_tile, operand_tile, seed: int,
                 kp: int = 32) -> dict:
    """``factor_tile(i, j)`` gives what the job left in tile (i, j),
    ``operand_tile(i, j)`` the operand's tile as the seed defines it, each
    an array on whatever device holds it.  Returns the two numbers, the
    block rows they are worst in, and the undivided residual of the worst
    row and of the whole matrix (for the log)."""
    x_, rowdot, diag = _kernels()
    rng = np.random.default_rng(seed)
    X = [rng.standard_normal((mb, kp)).astype(np.float32)
         for _ in range(nt)]
    zeros = lambda *shape: [np.zeros(shape, np.float64) for _ in range(nt)]

    Y = zeros(mb, kp)                        # Y = U X
    for k in range(nt):
        for j in range(k, nt):
            Y[k] += np.asarray(x_(factor_tile(k, j), X[j],
                                  which="upper" if j == k else "full"))
    Y32 = [y.astype(np.float32) for y in Y]
    Z, dLU = zeros(mb, kp), zeros(mb)        # Z = L Y, dLU = diag(L U)
    for i in range(nt):
        for k in range(i + 1):
            L = factor_tile(i, k)
            Z[i] += np.asarray(x_(L, Y32[k],
                                  which="lower" if k == i else "full"))
            dLU[i] += np.asarray(rowdot(L, factor_tile(k, i),
                                        diagonal=(k == i)))
    B, dA = zeros(mb, kp), zeros(mb)         # B = A X
    for i in range(nt):
        for j in range(nt):
            A = operand_tile(i, j)
            B[i] += np.asarray(x_(A, X[j], which="full"))
            if i == j:
                dA[i] += np.asarray(diag(A))

    def worst(num, den):
        rel = np.array([np.linalg.norm(a) / max(np.linalg.norm(b), 1e-300)
                        for a, b in zip(num, den)])
        rel = np.where(np.isfinite(rel), rel, np.inf)
        return float(rel.max()), int(rel.argmax())

    R = [B[i] - Z[i] for i in range(nt)]
    dR = [dA[i] - dLU[i] for i in range(nt)]
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

#: columns a panel of the diagonal tile's blocked elimination
BLOCK = 256


def _lu_panel(P):
    """Unpivoted elimination of a tall panel (rows >= columns), one
    pivot at a time: the multipliers under the diagonal, U above."""
    import jax.numpy as jnp
    from jax import lax
    m, w = P.shape
    r = lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    c = lax.broadcasted_iota(jnp.int32, (1, w), 1)

    def step(j, P):
        row = lax.dynamic_slice_in_dim(P, j, 1, axis=0)
        col = lax.dynamic_slice_in_dim(P, j, 1, axis=1)
        p = lax.dynamic_slice(row, (0, j), (1, 1))
        l = jnp.where(r > j, col / p, 0.0)
        u = jnp.where(c > j, row, 0.0)
        return jnp.where((c == j) & (r > j), l, P - l * u)

    return lax.fori_loop(0, w, step, P)


def _lu_tile(t):
    """Right-looking blocked LU of one float32 tile without pivoting:
    a panel of ``BLOCK`` columns eliminated, the block row to its right
    solved against its unit lower triangle, the trailing block updated."""
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    hi = "highest"
    n = t.shape[0]
    for j0 in range(0, n, BLOCK):
        j1 = min(j0 + BLOCK, n)
        P = _lu_panel(t[j0:, j0:j1])
        t = t.at[j0:, j0:j1].set(P)
        if j1 < n:
            U12 = solve_triangular(P[:j1 - j0], t[j0:j1, j1:], lower=True,
                                   unit_diagonal=True)
            t = t.at[j0:j1, j1:].set(U12)
            t = t.at[j1:, j1:].add(-jnp.matmul(P[j1 - j0:], U12,
                                               precision=hi))
    return t


def plain_lu(tiles: dict, nt: int, store) -> dict:
    """Right-looking tiled LU without pivoting of ``tiles[(i, j)]`` (the
    whole square grid), float32 arithmetic at HIGHEST precision, every
    tile passed through ``store`` whenever the tiled algorithm writes it
    back; the solves are triangular solves against the stored diagonal
    tile.  Empties ``tiles`` as it takes them (a full-size control has no
    room for two copies) and returns the factor's tiles as stored."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def getrf(T):
        return store(_lu_tile(T.astype(jnp.float32)))

    @jax.jit
    def trsm_row(F, C):                       # L^-1 C, L unit lower
        return store(solve_triangular(F.astype(jnp.float32),
                                      C.astype(jnp.float32), lower=True,
                                      unit_diagonal=True))

    @jax.jit
    def trsm_col(F, C):                       # C U^-1, U upper
        return store(solve_triangular(
            F.astype(jnp.float32), C.astype(jnp.float32).T,
            trans=1, lower=False).T)

    @jax.jit
    def update(C, L, U):
        return store(C.astype(jnp.float32) - jnp.matmul(
            L.astype(jnp.float32), U.astype(jnp.float32), precision=hi))

    with jax.default_matmul_precision("highest"):
        first = jax.jit(store)
        A = {t: first(tiles.pop(t)) for t in sorted(tiles)}
        for k in range(nt):
            A[(k, k)] = getrf(A[(k, k)])
            for j in range(k + 1, nt):
                A[(k, j)] = trsm_row(A[(k, k)], A[(k, j)])
                A[(j, k)] = trsm_col(A[(k, k)], A[(j, k)])
            for m in range(k + 1, nt):
                for n in range(k + 1, nt):
                    A[(m, n)] = update(A[(m, n)], A[(m, k)], A[(k, n)])
    return A
