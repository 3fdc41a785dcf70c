"""Numerical accounting for the tiled Cholesky (apps/potrf.py).

Two checks the bench publishes alongside the GFLOP/s number:

- ``backward_error``: the exact normwise backward error
  ||A - L L^T||_F / ||A||_F over the factored tile grid, computed
  tile-wise on device (one f32-accumulated matmul per (i,j,k) triple —
  n^3 flops, a ~3x-the-factorization one-off).  This is the bound the
  mixed-precision (bf16-storage) mode must report to claim anything:
  bf16 storage rounds every intermediate tile, so the factor's backward
  error sits at bf16 epsilon (~4e-3), not f32 (~6e-8).

- ``refine_solve``: the HPL-AI-style justification for the mp mode
  (reference metric context: BASELINE.json names DPLASMA dpotrf; the
  HPL-AI benchmark's contract is "factor in low precision, recover
  accuracy by iterative refinement on the solve").  Solves A x = b with
  the (possibly bf16) factor as the preconditioner of a fixed-point
  refinement iteration run in f32: x += (LL^T)^{-1} (b - A x).  Each
  step contracts the error by ~the factor's backward error, so a bf16
  factor reaches f32-class solution accuracy in 2-4 steps, at O(n^2)
  cost per step.

Both operate on the CURRENT tile payloads of a factored TiledMatrix
(device arrays on the bench path, numpy under the CPU tests) plus a
caller-supplied ``orig_tile(m, n)`` regenerating the pre-factorization
tile, so nothing here needs a second resident copy of A.
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
        # f32-accumulated residual accumulation: R -= L1 @ L2^T.
        # HIGHEST precision so the CHECK itself does not round through
        # bf16 passes on TPU — the measurement must be sharper than the
        # error it measures (inputs upcast to f32 first).
        def acc(R, L1, L2):
            return R - jnp.matmul(L1.astype(jnp.float32),
                                  L2.astype(jnp.float32).T,
                                  precision=jax.lax.Precision.HIGHEST)

        def symm(O):
            o = O.astype(jnp.float32)
            return jnp.tril(o) + jnp.tril(o, -1).T

        def sqn(R):
            return jnp.sum(R.astype(jnp.float32) ** 2)

        def mv(y, O, x):             # y += O @ x  (f32)
            return y + jnp.matmul(O.astype(jnp.float32), x,
                                  precision=jax.lax.Precision.HIGHEST)

        def mtv(y, O, x):            # y += O^T @ x
            return y + jnp.matmul(O.astype(jnp.float32).T, x,
                                  precision=jax.lax.Precision.HIGHEST)

        def trsv(L, b, lower, trans):
            from jax.scipy.linalg import solve_triangular
            return solve_triangular(L.astype(jnp.float32), b,
                                    lower=lower, trans=1 if trans else 0)

        k = _jit_cache["k"] = {
            "acc": jax.jit(acc), "symm": jax.jit(symm),
            "sqn": jax.jit(sqn), "mv": jax.jit(mv), "mtv": jax.jit(mtv),
            "trsv": jax.jit(trsv, static_argnames=("lower", "trans")),
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


def backward_error(A, orig_tile: Callable[[int, int], object],
                   device=None) -> float:
    """Exact ||A - L L^T||_F / ||A||_F over the lower triangle of the
    factored tile grid (the effective symmetric A: lower tiles as
    generated, diagonal tiles symmetrized from their lower triangle —
    Cholesky never read anything else).

    ``device``: a jax device every tile is brought to as it is used —
    needed when the factor is spread over several devices (one jitted
    product cannot take operands committed to different devices)."""
    import jax
    import jax.numpy as jnp
    k = _kernels()
    NT = A.mt
    num = 0.0
    den = 0.0

    def here(t):
        t = jnp.asarray(t)
        return t if device is None else jax.device_put(t, device)

    def L_of(i, j):
        # diagonal factor tiles are lower-triangularized ON USE (the
        # tile's upper triangle holds stale A values chol never wrote);
        # no f32 copies are cached — at bench scale (nt=16, mb=6144)
        # cached trils would cost GBs of HBM next to the resident grid
        t = here(_tile(A, i, j))
        return jnp.tril(t.astype(jnp.float32)) if i == j else t

    for i in range(NT):
        for j in range(i + 1):
            O = here(orig_tile(i, j))
            A0 = k["symm"](O) if i == j else O.astype(jnp.float32)
            den += float(k["sqn"](A0))
            if i != j:
                den += float(k["sqn"](A0))    # the mirrored upper tile
            R = A0
            for kk in range(j + 1):
                R = k["acc"](R, L_of(i, kk), L_of(j, kk))
            s = float(k["sqn"](R))
            num += s if i == j else 2.0 * s
    return float(np.sqrt(num) / max(np.sqrt(den), 1e-300))


def _solve_factored(A, b_blocks):
    """x = (L L^T)^{-1} b via tiled forward+backward substitution in f32
    (diagonal trsv per tile, matvec updates — O(n^2))."""
    k = _kernels()
    NT = A.mt
    # forward: L y = b
    import jax.numpy as jnp
    y: List[object] = []
    for i in range(NT):
        rhs = b_blocks[i].astype(jnp.float32)
        for j in range(i):
            rhs = rhs - jnp.matmul(
                jnp.asarray(_tile(A, i, j)).astype(jnp.float32), y[j])
        y.append(k["trsv"](jnp.tril(
            jnp.asarray(_tile(A, i, i)).astype(jnp.float32)), rhs,
            lower=True, trans=False))
    # backward: L^T x = y
    x: List[object] = [None] * NT
    for i in range(NT - 1, -1, -1):
        rhs = y[i]
        for j in range(i + 1, NT):
            rhs = rhs - jnp.matmul(
                jnp.asarray(_tile(A, j, i)).astype(jnp.float32).T, x[j])
        x[i] = k["trsv"](jnp.tril(
            jnp.asarray(_tile(A, i, i)).astype(jnp.float32)), rhs,
            lower=True, trans=True)
    return x


def _matvec(orig_tile, NT, x_blocks):
    """y = A_eff @ x with the effective symmetric A regenerated tile-wise
    (lower tiles + symmetrized diagonal + mirrored upper)."""
    import jax.numpy as jnp
    k = _kernels()
    y = [jnp.zeros_like(x_blocks[0], dtype=jnp.float32)
         for _ in range(NT)]
    for i in range(NT):
        for j in range(i + 1):
            O = jnp.asarray(orig_tile(i, j))
            if i == j:
                y[i] = k["mv"](y[i], k["symm"](O), x_blocks[i])
            else:
                y[i] = k["mv"](y[i], O, x_blocks[j])
                y[j] = k["mtv"](y[j], O, x_blocks[i])
    return y


def refine_solve(A, orig_tile: Callable[[int, int], object],
                 steps: int = 3, seed: int = 0):
    """Solve A x = b with the factored tiles as preconditioner and
    ``steps`` rounds of f32 iterative refinement.  Returns the list of
    normwise relative residuals ||b - A x||_2 / ||b||_2, one entry per
    iterate (entry 0 = the direct solve with the factor)."""
    import jax.numpy as jnp
    NT, mb = A.mt, A.mb
    rng = np.random.default_rng(seed)
    b = [jnp.asarray(rng.standard_normal(mb).astype(np.float32))
         for _ in range(NT)]
    bn = float(np.sqrt(sum(float(jnp.sum(bb ** 2)) for bb in b)))
    x = _solve_factored(A, b)
    hist = []
    for it in range(steps + 1):
        ax = _matvec(orig_tile, NT, x)
        r = [bb - aa for bb, aa in zip(b, ax)]
        rn = float(np.sqrt(sum(float(jnp.sum(rr ** 2)) for rr in r)))
        hist.append(rn / max(bn, 1e-300))
        if it == steps:
            break            # the last residual is recorded; a further
                             # solve+update would never be observed
        dx = _solve_factored(A, r)
        x = [xx + dd for xx, dd in zip(x, dx)]
    return hist
