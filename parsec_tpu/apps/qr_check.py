"""Numerical accounting for the tiled QR (apps/qr.py): the mp-QR
accuracy ladder (VERDICT r5 #9 — mirror of apps/potrf_check.py's
HPL-AI story for the dgeqrf-class driver).

The factorization residual (||R^T R z - A^T A z|| / ||A^T A z||,
``factorization_residual`` below) bounds how good the FACTOR is: bf16
tile storage rounds R to ~bf16 epsilon, so the raw residual sits at the
1e-2/1e-3 class. What justifies low-precision storage is the same
contract as potrf's ``refine_solve``: the factor is a fine
PRECONDITIONER, and the accuracy is recovered where it is consumed — the
least-squares/linear solve.

``ls_refine`` solves A x = b through the corrected semi-normal
equations (CSNE; Björck's refinement for QR factors): with R from the
factorization,

    x_0     = R^{-1} R^{-T} (A^T b)
    r_k     = b - A x_k;   d_k = R^{-1} R^{-T} (A^T r_k);   x_{k+1} += d_k

every product in f32 at HIGHEST matmul precision and the triangular
solves on R's tile grid (vector RHS — O(n^2) per step).  Each step
contracts the error by ~the factor's relative error, so a bf16-storage
factor recovers f32-class solution accuracy in 1-3 steps.  The bench
records the per-step relative error history like potrf's
``ir_residuals``.

Operates on the CURRENT tile payloads of a factored TiledMatrix (R in
the upper block triangle; device arrays on the bench path, numpy under
CPU tests) plus a caller-supplied ``orig_tile(m, n)`` regenerating the
pre-factorization tile — nothing here needs a second resident copy.
"""

from __future__ import annotations

from typing import Callable, List

import numpy as np

_jit_cache = {}


def _kernels():
    import jax
    import jax.numpy as jnp
    k = _jit_cache.get("k")
    if k is None:
        hi = jax.lax.Precision.HIGHEST

        def mv(y, O, x):             # y += O @ x  (f32, HIGHEST)
            return y + jnp.matmul(O.astype(jnp.float32), x, precision=hi)

        def mtv(y, O, x):            # y += O^T @ x
            return y + jnp.matmul(O.astype(jnp.float32).T, x,
                                  precision=hi)

        def trsv(R, b, trans):
            # R upper triangular; trans solves R^T z = b
            from jax.scipy.linalg import solve_triangular
            return solve_triangular(R.astype(jnp.float32), b,
                                    lower=False, trans=1 if trans else 0)

        k = _jit_cache["k"] = {
            "mv": jax.jit(mv), "mtv": jax.jit(mtv),
            "trsv": jax.jit(trsv, static_argnames=("trans",)),
        }
    return k


def _tile(A, m, n):
    """Current newest payload of tile (m, n) — device array or numpy."""
    d = A.data_of(m, n)
    v = d.newest_version()
    for _sp, c in d.copies().items():
        if c.version == v and c.payload is not None:
            return c.payload
    c = d.pull_to_host()
    return c.payload


def _r_tile(A, i, j):
    import jax.numpy as jnp
    t = jnp.asarray(_tile(A, i, j)).astype(jnp.float32)
    return jnp.triu(t) if i == j else t


def _matvec(orig_tile, NT, x):
    """y = A @ x with A regenerated tile-wise."""
    import jax.numpy as jnp
    k = _kernels()
    y = [jnp.zeros_like(x[0], dtype=jnp.float32) for _ in range(NT)]
    for i in range(NT):
        for j in range(NT):
            y[i] = k["mv"](y[i], jnp.asarray(orig_tile(i, j)), x[j])
    return y


def _matvec_t(orig_tile, NT, x):
    """y = A^T @ x with A regenerated tile-wise."""
    import jax.numpy as jnp
    k = _kernels()
    y = [jnp.zeros_like(x[0], dtype=jnp.float32) for _ in range(NT)]
    for i in range(NT):
        for j in range(NT):
            y[j] = k["mtv"](y[j], jnp.asarray(orig_tile(i, j)), x[i])
    return y


def _rtr_solve(A, b):
    """z = R^{-1} R^{-T} b over the upper-block-triangular tile grid
    (vector RHS: O(n^2) tiled forward+backward substitution in f32)."""
    import jax.numpy as jnp
    k = _kernels()
    NT = A.mt
    # forward: R^T y = b  (R^T lower block triangular: R^T[i][j] =
    # R[j][i]^T, j <= i)
    y: List[object] = []
    for i in range(NT):
        rhs = b[i].astype(jnp.float32)
        for j in range(i):
            rhs = rhs - jnp.matmul(_r_tile(A, j, i).T, y[j])
        y.append(k["trsv"](_r_tile(A, i, i), rhs, trans=True))
    # backward: R x = y
    x: List[object] = [None] * NT
    for i in range(NT - 1, -1, -1):
        rhs = y[i]
        for j in range(i + 1, NT):
            rhs = rhs - jnp.matmul(_r_tile(A, i, j), x[j])
        x[i] = k["trsv"](_r_tile(A, i, i), rhs, trans=False)
    return x


def factorization_residual(A, orig_tile: Callable[[int, int], object],
                           seed: int = 123) -> float:
    """Stochastic factorization check WITHOUT storing Q: an orthogonal
    QR satisfies R^T R = A^T A, so compare the two quadratic forms on a
    random probe vector — ||R^T R z - A^T A z|| / ||A^T A z||, O(n^2)
    matvecs, tile-streamed.  R is the result sitting in A's tiles (upper
    block triangle; TSQRT zeroed the rest); the original A regenerates
    from ``orig_tile``."""
    import jax.numpy as jnp
    k = _kernels()
    NT, mb = A.mt, A.mb
    rng = np.random.default_rng(seed)
    z = [jnp.asarray(rng.standard_normal(mb).astype(np.float32))
         for _ in range(NT)]
    zero = jnp.zeros(mb, jnp.float32)
    v = [zero] * NT                      # R z
    for i in range(NT):
        for j in range(i, NT):
            v[i] = k["mv"](v[i], _r_tile(A, i, j), z[j])
    y1 = [zero] * NT                     # R^T (R z)
    for i in range(NT):
        for j in range(i, NT):
            y1[j] = k["mtv"](y1[j], _r_tile(A, i, j), v[i])
    y2 = _matvec_t(orig_tile, NT, _matvec(orig_tile, NT, z))
    num = float(jnp.sqrt(sum(jnp.sum((a - b) ** 2)
                             for a, b in zip(y1, y2))))
    den = float(jnp.sqrt(sum(jnp.sum(b ** 2) for b in y2)))
    return num / den if den else float("nan")


def ls_refine(A, orig_tile: Callable[[int, int], object],
              steps: int = 3, seed: int = 0):
    """The mp-QR accuracy ladder: solve A x = b (b = A x_true for a
    deterministic random x_true, so the truth is known without storing
    Q) through CSNE with the factored R as preconditioner and ``steps``
    refinement rounds.  Returns the per-iterate relative error history
    ||x_k - x_true||_2 / ||x_true||_2 (entry 0 = the direct CSNE
    solve) — the geqrf analog of potrf's ``ir_residuals``."""
    import jax.numpy as jnp
    NT, mb = A.mt, A.mb
    rng = np.random.default_rng(seed)
    x_true = [jnp.asarray(rng.standard_normal(mb).astype(np.float32))
              for _ in range(NT)]
    tn = float(np.sqrt(sum(float(jnp.sum(t ** 2)) for t in x_true)))
    b = _matvec(orig_tile, NT, x_true)
    # x_0 via CSNE
    x = _rtr_solve(A, _matvec_t(orig_tile, NT, b))
    hist = []
    for it in range(steps + 1):
        en = float(np.sqrt(sum(
            float(jnp.sum((xx - tt) ** 2))
            for xx, tt in zip(x, x_true))))
        hist.append(en / max(tn, 1e-300))
        if it == steps:
            break
        ax = _matvec(orig_tile, NT, x)
        r = [bb - aa for bb, aa in zip(b, ax)]
        d = _rtr_solve(A, _matvec_t(orig_tile, NT, r))
        x = [xx + dd for xx, dd in zip(x, d)]
    return hist
