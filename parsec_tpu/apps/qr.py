"""Tiled QR factorization (dgeqrf): the irregular-DAG driver.

The DPLASMA-style tiled QR (reference: BASELINE.json names "DPLASMA
dgeqrf tiled QR (irregular DAG, pod-scale comm/compute overlap)" as a
headline config).  Classic flat-tree tile algorithm:

    GEQRT(k)    : QR of the diagonal tile; R stays in A[k,k], the
                  orthogonal factor Q1 (mb x mb) travels on a dataflow
                  edge.
    UNMQR(k,n)  : A[k,n] = Q1^T @ A[k,n]                     (n > k)
    TSQRT(m,k)  : QR of [R; A[m,k]] stacked — updates R in A[k,k] and
                  zeroes A[m,k]; the compact-WY pair (V, T^T)
                  travels on an edge.                         (m > k)
    TSMQR(m,n,k): applies the WY transform to [A(k,n); A(m,n)].
                  (m > k, n > k)

TPU-first design of the tall-skinny kernels: XLA's QR expander (and
especially ``mode="complete"`` — an extra (2mb)^3 of Q formation) runs
far below matmul peak on TPU, so TSQRT computes the stacked QR by
CHOLESKY-QR on the mb x mb Gram matrix and derives an EXACT compact-WY
representation in closed form:

    G  = R^T R + B^T B;   R' = +-chol(G)^T   (Householder sign choice:
                                sign(R'_jj) = -sign(R_jj), no
                                cancellation in S)
    S  = R - R';   V = B S^-1;   T^T = I - R'^-T R^T

so the 2mb x 2mb orthogonal transform is Phi^T = I - [I;V] T^T [I;V]^T
(annihilation AND orthogonality hold identically — the general inverse
in the textbook T^T = S (R + V^T B)^-1 collapses to triangular ones via
M = -S^-T R'^T S).  TSQRT is then one mb-sized Cholesky + two
triangular inverses (recursive Newton, apps/potrf.tri_inv) + matmuls,
and TSMQR is five mb^3-class matmuls:

    Z = T^T (C1 + V^T C2);   C1 -= Z;   C2 -= V Z

Everything lowers to the systolic array; the Q edges shrink from
(2mb)^2 dense factors to the (2mb x mb) [V; T^T] pair.  R ends in the
upper triangle; tiles below are zeroed.

INNER BLOCKING (ib; the DPLASMA dgeqrf panel discipline, r6): the
panel CONSTRUCTION is cond^2-sensitive and must run at HIGHEST matmul
precision (true f32 — DEFAULT's bf16 passes destroy the factorization,
measured residual 1.19 on the r5 remote chip), but HIGHEST is ~3x
DEFAULT on the MXU.  Factoring the panel in ib-wide column blocks
confines the HIGHEST-precision math (per-block Gram, Cholesky,
triangular inverses, WY assembly) to O(mb^2*ib) per panel instead of
O(mb^3), while the O(mb^3) intra-panel trailing updates — where errors
enter the data LINEARLY, like TSMQR — run at DEFAULT precision:

    GEQRT: blocked CholeskyQR2 (BCGS2-flavored: each block is
           re-projected once against the accumulated basis at HIGHEST
           before its own two Cholesky-QR passes), trailing columns
           updated at DEFAULT.
    TSQRT: per-block compact-WY from the ib x ib Gram of [R_jj; B_j],
           trailing columns of [R; B] updated by the 5-matmul WY
           application at DEFAULT, and the per-block (V_j, T_j^T)
           pairs aggregated into ONE panel-wide (V, T^T) with the
           standard T-accumulation
               T^T[J, :s] = -T_j^T (V_j^T V[:, :s]) T^T[:s, :s]
           (block lower triangular), so TSMQR's 5-matmul application
           and the q2 edge layout are UNCHANGED.

Knobs: --mca qr_ib N (0 = unblocked; ignored unless 0 < ib < mb and
ib | mb) and --mca qr_update_precision {default,highest} for the
intra-panel trailing updates.  Per-block Cholesky failures fall back
to the unblocked construction (which carries its own Householder-QR
guard), keeping LAPACK-class robustness behind the fast path.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from parsec_tpu.apps.potrf import tri_inv
from parsec_tpu.core.taskpool import ParameterizedTaskpool
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.dsl.ptg.api import DATA, IN, NEW, OUT, PTG, Range, TASK
from parsec_tpu.utils.mca import params

params.register("qr_ib", 512,
                "inner blocking of the QR panel construction: the "
                "HIGHEST-precision work per panel drops from O(mb^3) "
                "to O(mb^2*ib) (DPLASMA dgeqrf ib discipline); 0 "
                "disables — ignored unless 0 < ib < mb and ib | mb")
params.register("qr_update_precision", "default",
                "matmul precision of the intra-panel trailing updates "
                "(errors enter linearly there): 'default' rides the "
                "MXU's fast path, 'highest' forces true f32")

_kernels = {}


def effective_ib(mb: int) -> int:
    """The inner blocking actually used for an mb-wide panel: the
    ``qr_ib`` MCA param, clamped to 0 (unblocked) when it does not
    evenly block the panel."""
    try:
        ib = int(params.get("qr_ib", 512))
    except (TypeError, ValueError):
        return 0
    if ib <= 0 or ib >= mb or mb % ib:
        return 0
    return ib


def _update_precision():
    """Precision of intra-panel trailing updates (None = DEFAULT)."""
    import jax
    val = str(params.get("qr_update_precision", "default")).lower()
    return jax.lax.Precision.HIGHEST if val == "highest" else None


def _cholqr2(cols, jnp, hi, gram=None):
    """CholeskyQR2 of one mb x ib column block at HIGHEST precision:
    returns (Q, R) with Q orthonormal (two Gram+Cholesky passes — one
    pass loses orthogonality as cond^2*eps) and R = L2^T L^T upper
    triangular.  NaNs from an ill-conditioned block propagate to the
    caller's finiteness guard.  ``gram`` swaps the Gram products for a
    hand-written kernel (apps/pallas_kernels.pallas_gram_tile)."""
    gram = gram or (lambda X: jnp.matmul(X.T, X, precision=hi))
    G = gram(cols)
    dg = jnp.sqrt(jnp.clip(jnp.diagonal(G), 1e-30, None))
    L = jnp.linalg.cholesky(G / dg[:, None] / dg[None, :]) * dg[:, None]
    Q1 = jnp.matmul(cols, tri_inv(L, precision=hi).T, precision=hi)
    G2 = gram(Q1)
    L2 = jnp.linalg.cholesky(G2)
    Q = jnp.matmul(Q1, tri_inv(L2, precision=hi).T, precision=hi)
    R = jnp.matmul(L2.T, L.T, precision=hi)
    return Q, R


def _k(name, maker):
    fn = _kernels.get(name)
    if fn is None:
        fn = maker()
        _kernels[name] = fn
    return fn


def _mk_geqrt(ib: int = 0, gram=None):
    """``gram``: replacement for the panel blocks' Gram products
    (apps/pallas_kernels.pallas_gram_tile); None = the fused XLA
    matmul."""
    def fn(T, Q):
        import jax
        import jax.numpy as jnp
        from jax import lax
        # factor in f32 even under bf16 tile storage (mp mode); results
        # land back in the storage dtype (kernels are dtype-FOLLOWING,
        # same discipline as apps/potrf.py).
        # Cholesky-QR fast path (r5): XLA's QR expander runs at ~13 TF/s
        # on this chip (measured) — ~43ms per diagonal tile — while
        # gram+chol+tri_inv+matmul is matmul-class; the same
        # equilibrate-then-guard discipline as TSQRT keeps LAPACK-class
        # stability behind the cold fallback.  Construction at HIGHEST
        # precision (cond^2-sensitive; see _mk_tsqrt).
        hi = jax.lax.Precision.HIGHEST
        Tf = T.astype(jnp.float32)
        mb = Tf.shape[0]

        def stable(_):
            return jnp.linalg.qr(Tf, mode="reduced")[::-1]

        if 0 < ib < mb and mb % ib == 0:
            # inner-blocked panel (module docstring): per-block
            # CholeskyQR2 + one re-projection against the accumulated
            # basis at HIGHEST (O(mb^2*ib) total), trailing columns
            # updated at DEFAULT (errors enter linearly).  Q comes out
            # explicit — the blocks ARE its orthonormal columns — so
            # the q1 edge and UNMQR are unchanged.
            up = _update_precision()
            A = Tf
            R = jnp.zeros((mb, mb), jnp.float32)
            Qacc = None
            for s in range(0, mb, ib):
                cols = A[:, s:s + ib]
                if Qacc is not None:
                    # BCGS2-flavored reorthogonalization: the trailing
                    # updates already projected this block, but rounding
                    # reintroduces ~eps*cond components; one extra
                    # HIGHEST-precision pass restores inter-block
                    # orthogonality.  The coefficients fold into R
                    # exactly.
                    prj = jnp.matmul(Qacc.T, cols, precision=hi)
                    cols = cols - jnp.matmul(Qacc, prj, precision=hi)
                    R = R.at[:s, s:s + ib].add(prj)
                Qj, Rjj = _cholqr2(cols, jnp, hi, gram=gram)
                R = R.at[s:s + ib, s:s + ib].set(Rjj)
                if s + ib < mb:
                    rest = A[:, s + ib:]
                    Rjk = jnp.matmul(Qj.T, rest, precision=up)
                    A = A.at[:, s + ib:].set(
                        rest - jnp.matmul(Qj, Rjk, precision=up))
                    R = R.at[s:s + ib, s + ib:].set(Rjk)
                Qacc = Qj if Qacc is None else \
                    jnp.concatenate([Qacc, Qj], axis=1)
            ok = jnp.logical_and(jnp.all(jnp.isfinite(R)),
                                 jnp.all(jnp.isfinite(Qacc)))
            R, Qm = lax.cond(ok, lambda o: o, stable, operand=(R, Qacc))
            return {"T": R.astype(T.dtype), "Q": Qm.astype(T.dtype)}

        G = jnp.matmul(Tf.T, Tf, precision=hi)
        dg = jnp.sqrt(jnp.clip(jnp.diagonal(G), 1e-30, None))
        Ls = jnp.linalg.cholesky(G / dg[:, None] / dg[None, :])
        L = Ls * dg[:, None]
        # CholeskyQR2: one Cholesky-QR pass loses orthogonality as
        # cond^2*eps — tiles with cond in ~1e2..3e3 pass the finite
        # check yet come out visibly non-orthogonal in f32.  A second
        # Gram+chol pass on Q1 (whose cond is ~1+cond^2*eps, so its
        # Cholesky is unconditionally benign whenever L was finite)
        # restores eps-level orthogonality; still pure matmul+chol, so
        # the whole fast path stays on the MXU.  R folds exactly:
        # A = Q1 L^T, Q1 = Q2 L2^T  =>  A = Q2 (L2^T L^T).
        Q1 = jnp.matmul(Tf, tri_inv(L, precision=hi).T, precision=hi)
        G2 = jnp.matmul(Q1.T, Q1, precision=hi)
        L2 = jnp.linalg.cholesky(G2)

        def fast(_):
            R = jnp.matmul(L2.T, L.T, precision=hi)
            Qm = jnp.matmul(Q1, tri_inv(L2, precision=hi).T,
                            precision=hi)
            return R, Qm

        ok = jnp.logical_and(jnp.all(jnp.isfinite(L)),
                             jnp.all(jnp.isfinite(L2)))
        R, Qm = lax.cond(ok, fast, stable, operand=None)
        return {"T": R.astype(T.dtype), "Q": Qm.astype(T.dtype)}
    return fn


def _mk_unmqr():
    def fn(Q, C):
        import jax.numpy as jnp
        acc = jnp.matmul(Q.T, C, preferred_element_type=jnp.float32)
        return {"C": acc.astype(C.dtype)}
    return fn


def _wy_from_L(R, B, L, xp, ti, precision=None):
    """Closed-form compact-WY pair from ANY lower-triangular L with
    L L^T = R^T R + B^T B (Cholesky of the Gram matrix, however it was
    obtained): returns (R', V, T^T).

    ``precision``: matmul precision for the CONSTRUCTION (numpy path
    ignores it).  On TPU this must be HIGHEST: the construction is
    cond^2-sensitive, and XLA's DEFAULT f32 matmul (bf16 passes, ~1e-3
    relative) amplifies through the triangular inverses to a DESTROYED
    factorization — measured residual 1.19 at bench scale vs the
    algorithm's true-f32 level of ~5e-3 (r5 diagnostic)."""
    mb = R.shape[0]
    mm = (xp.matmul if precision is None
          else (lambda a, b: xp.matmul(a, b, precision=precision)))
    # Householder sign choice: R'_jj = -sign(R_jj) * |R'_jj| makes
    # S = R - R' diagonally safe (|S_jj| >= |R'_jj|)
    d = xp.where(xp.diagonal(R) >= 0, -1.0, 1.0).astype(R.dtype)
    Rp = d[:, None] * L.T
    S = R - Rp
    Sinv = ti(S.T).T                  # S upper-tri -> invert transpose
    V = mm(B, Sinv)
    Linv = ti(L)
    # R'^-T = (R'^T)^-1 = (L d)^-1 ... with the sign fold:
    # R' = D L^T  =>  R'^T = L D  =>  R'^-T = D^-1 L^-1 = D L^-1
    Tt = xp.eye(mb, dtype=R.dtype) - mm(d[:, None] * Linv, R.T)
    return Rp, V, Tt


def _tsqrt_wy(R, B, xp, chol, ti):
    """Shared TSQRT math (jax and numpy incarnations): returns
    (R', V, T^T) of the compact-WY Cholesky-QR above."""
    G = R.T @ R + B.T @ B
    return _wy_from_L(R, B, chol(G), xp, ti)


def _tsqrt_blocked(T, B, ib, jnp, hi, up):
    """Inner-blocked TSQRT construction (module docstring): returns the
    panel-wide (R', V, T^T) with T^T block lower triangular.  HIGHEST
    work is O(mb^2*ib); the trailing updates of [R; B] run at ``up``
    precision.  NaNs from an ill-conditioned block propagate to the
    caller's finiteness guard."""
    mb = T.shape[0]
    Rc, Bc = T, B
    V = jnp.zeros((mb, mb), jnp.float32)
    Tt = jnp.zeros((mb, mb), jnp.float32)
    for s in range(0, mb, ib):
        Rjj = Rc[s:s + ib, s:s + ib]
        Bj = Bc[:, s:s + ib]
        G = (jnp.matmul(Rjj.T, Rjj, precision=hi)
             + jnp.matmul(Bj.T, Bj, precision=hi))
        dg = jnp.sqrt(jnp.clip(jnp.diagonal(G), 1e-30, None))
        L = jnp.linalg.cholesky(G / dg[:, None] / dg[None, :]) \
            * dg[:, None]
        Rpjj, Vj, Tjt = _wy_from_L(Rjj, Bj, L, jnp,
                                   lambda M: tri_inv(M, precision=hi),
                                   precision=hi)
        Rc = Rc.at[s:s + ib, s:s + ib].set(Rpjj)
        if s + ib < mb:
            # 5-matmul WY application to the trailing columns of the
            # stacked panel (same shape as TSMQR, errors enter linearly)
            C1 = Rc[s:s + ib, s + ib:]
            C2 = Bc[:, s + ib:]
            Z = jnp.matmul(Tjt,
                           C1 + jnp.matmul(Vj.T, C2, precision=up),
                           precision=up)
            Rc = Rc.at[s:s + ib, s + ib:].set(C1 - Z)
            Bc = Bc.at[:, s + ib:].set(
                C2 - jnp.matmul(Vj, Z, precision=up))
        if s:
            # T-accumulation: Q^T = Q_j^T Q_prev^T collapses to one
            # compact-WY pair with the block-lower-triangular
            # T^T[J, :s] = -T_j^T (W_j^T W_prev) T^T[:s, :s]; the unit
            # tops of W are disjoint identity columns, so W_j^T W_prev
            # = V_j^T V[:, :s]
            cross = jnp.matmul(Vj.T, V[:, :s], precision=hi)
            Tt = Tt.at[s:s + ib, :s].set(
                -jnp.matmul(Tjt, jnp.matmul(cross, Tt[:s, :s],
                                            precision=hi),
                            precision=hi))
        V = V.at[:, s:s + ib].set(Vj)
        Tt = Tt.at[s:s + ib, s:s + ib].set(Tjt)
    return Rc, V, Tt


def _mk_tsqrt(ib: int = 0):
    def fn(T, B, Q):
        import jax
        import jax.numpy as jnp
        from jax import lax
        T = T.astype(jnp.float32)      # WY construction runs in f32
        B = B.astype(jnp.float32)
        # Fast path: Cholesky of the Gram matrix (pure matmul + chol,
        # rides the MXU).  Cholesky-QR squares cond(panel), so chol(G)
        # yields NaNs for ill-conditioned stacked panels; guard with a
        # Householder QR of the stacked panel (LAPACK-class stability,
        # reference TSQRT's algorithm: dplasma CORE_dtsqrt) that
        # produces the SAME triangular factor, then rebuild the
        # identical closed-form WY pair from it.
        #
        # The whole panel CONSTRUCTION runs at HIGHEST matmul precision
        # (true f32): the Gram matrix, the triangular inverses, and the
        # WY products are cond^2-sensitive, and DEFAULT's bf16 passes
        # destroy the factorization (residual 1.19 measured).  Only the
        # O(nt^2)-many panel tasks pay the ~3x; the O(nt^3) TSMQR bulk
        # stays at DEFAULT, where errors enter the data linearly.
        # Jacobi equilibration before the factor: D G D with unit
        # diagonal keeps the decaying-R dynamic range out of the chol;
        # the exact factor is recovered as L = D^-1 chol(D G D).
        hi = jax.lax.Precision.HIGHEST
        mb = T.shape[0]

        def unblocked(_):
            G = (jnp.matmul(T.T, T, precision=hi)
                 + jnp.matmul(B.T, B, precision=hi))
            dg = jnp.sqrt(jnp.clip(jnp.diagonal(G), 1e-30, None))
            L = jnp.linalg.cholesky(G / dg[:, None] / dg[None, :]) \
                * dg[:, None]

            def stable_L(_):
                Rh = jnp.linalg.qr(jnp.concatenate([T, B], axis=0),
                                   mode="r")
                s = jnp.where(jnp.diagonal(Rh) >= 0, 1.0,
                              -1.0).astype(T.dtype)
                return (s[:, None] * Rh).T   # positive-diag lower factor

            L = lax.cond(jnp.all(jnp.isfinite(L)), lambda _: L, stable_L,
                         operand=None)
            return _wy_from_L(T, B, L, jnp,
                              lambda M: tri_inv(M, precision=hi),
                              precision=hi)

        if 0 < ib < mb and mb % ib == 0:
            # inner-blocked fast path; an ill-conditioned BLOCK (NaN
            # anywhere in the result) falls back to the unblocked
            # construction, which carries its own Householder-QR guard
            res = _tsqrt_blocked(T, B, ib, jnp, hi, _update_precision())
            ok = jnp.all(jnp.array([jnp.all(jnp.isfinite(x))
                                    for x in res]))
            Rp, V, Tt = lax.cond(ok, lambda o: o, unblocked, operand=res)
        else:
            Rp, V, Tt = unblocked(None)
        dt = Q.dtype                    # NEW-flow arena dtype = storage
        return {"T": Rp.astype(dt), "B": jnp.zeros_like(B, dtype=dt),
                "Q": jnp.concatenate([V, Tt], axis=0).astype(dt)}
    return fn


def _mk_tsmqr():
    def fn(Q, C1, C2):
        import jax.numpy as jnp
        mb = C1.shape[0]
        V, Tt = Q[:mb, :], Q[mb:, :]
        # f32 accumulation through the 5-matmul WY application; outputs
        # round back to the tile storage dtype (bf16 in mp mode)
        inner = (C1.astype(jnp.float32)
                 + jnp.matmul(V.T, C2, preferred_element_type=jnp.float32))
        Z = jnp.matmul(Tt, inner.astype(Tt.dtype),
                       preferred_element_type=jnp.float32)
        C2n = C2.astype(jnp.float32) - jnp.matmul(
            V, Z.astype(V.dtype), preferred_element_type=jnp.float32)
        return {"C1": (C1.astype(jnp.float32) - Z).astype(C1.dtype),
                "C2": C2n.astype(C2.dtype)}
    return fn


def _np_tri_inv(L):
    import scipy.linalg as sl
    return sl.solve_triangular(L, np.eye(L.shape[0], dtype=L.dtype),
                               lower=True)


def qr_taskpool(A: TiledMatrix, device: str = "tpu") -> ParameterizedTaskpool:
    """Factor A in place: R in the upper triangle (Q is applied, not
    stored).  Requires a square tile grid evenly dividing A."""
    if A.mt != A.nt:
        raise ValueError("qr driver needs a square tile grid")
    if A.lm % A.mb or A.ln % A.nb:
        raise ValueError("qr tiles must divide the matrix evenly")
    NT = A.mt
    mb = A.mb
    use_device = device in ("tpu", "xla", "gpu")
    # inner blocking + trailing-update precision resolve ONCE per build;
    # they key the kernel memo so an MCA change cannot alias a stale jit
    ib = effective_ib(mb)
    upd = str(params.get("qr_update_precision", "default")).lower()
    from parsec_tpu.apps.pallas_kernels import (pallas_gram_tile,
                                                use_pallas_qr_gram)
    pg = use_pallas_qr_gram()
    # Owner-computes discipline for the final R tiles: the LAST TSQRT of
    # column k (and the last TSMQR of each row-k tile) runs where
    # A(NT-1, k) lives, but its R output belongs home at A(k, *).  On
    # one rank the write-back is local; across ranks it is routed
    # through a store task pinned to the home tile, so the payload rides
    # a normal dataflow edge (remote-dep protocol) instead of a
    # cross-rank direct write (reference counterpart: remote output
    # deps land via the ACTIVATE/GET protocol, remote_dep_mpi.c, never
    # by writing another rank's memory).
    routed = A.nodes > 1

    def bodies(tb, kernel, cpu_fn):
        if use_device:
            tb.body(kernel, device=device)
        tb.body(cpu_fn)
        return tb

    p = PTG("geqrf", NT=NT)
    p.arena("q1", (mb, mb), dtype=A.dtype)
    p.arena("q2", (2 * mb, mb), dtype=A.dtype)   # stacked [V; T^T]

    # GEQRT(k): diagonal QR
    tb = p.task("GEQRT", k=Range(0, NT - 1)) \
        .affinity(lambda k, A=A: A(k, k)) \
        .priority(lambda k, NT=NT: 4 * (NT - k) + 3) \
        .flow("T", "RW",
              IN(DATA(lambda k, A=A: A(k, k)), when=lambda k: k == 0),
              IN(TASK("TSMQR", "C2", lambda k: dict(m=k, n=k, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("TSQRT", "T", lambda k, NT=NT: dict(m=k + 1, k=k)),
                  when=lambda k, NT=NT: k < NT - 1),
              OUT(DATA(lambda k, A=A: A(k, k)),
                  when=lambda k, NT=NT: k == NT - 1)) \
        .flow("Q", "RW",
              IN(NEW("q1")),
              OUT(TASK("UNMQR", "Q",
                       lambda k, NT=NT: [dict(k=k, n=n)
                                         for n in range(k + 1, NT)]),
                  when=lambda k, NT=NT: k < NT - 1))

    def cpu_geqrt(T, Q):
        q, r = np.linalg.qr(np.asarray(T), mode="complete")
        return {"T": r, "Q": q}
    bodies(tb, _k(("geqrt", ib, upd, pg),
                  lambda: _mk_geqrt(ib, pallas_gram_tile() if pg else None)),
           cpu_geqrt)

    # UNMQR(k, n): apply Q1^T across the k-th block row
    tb = p.task("UNMQR", k=Range(0, NT - 2), n=Range(lambda k: k + 1,
                                                     NT - 1)) \
        .affinity(lambda k, n, A=A: A(k, n)) \
        .priority(lambda k, NT=NT: 4 * (NT - k) + 2) \
        .flow("Q", "READ", IN(TASK("GEQRT", "Q", lambda k: dict(k=k)))) \
        .flow("C", "RW",
              IN(DATA(lambda k, n, A=A: A(k, n)), when=lambda k: k == 0),
              IN(TASK("TSMQR", "C2", lambda k, n: dict(m=k, n=n, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("TSMQR", "C1", lambda k, n: dict(m=k + 1, n=n, k=k))))

    def cpu_unmqr(Q, C):
        return {"C": np.asarray(Q).T @ np.asarray(C)}
    bodies(tb, _k("unmqr", _mk_unmqr), cpu_unmqr)

    # TSQRT(m, k): fold block-column tile m into R(k)
    tb = p.task("TSQRT", k=Range(0, NT - 2), m=Range(lambda k: k + 1,
                                                     NT - 1)) \
        .affinity(lambda m, k, A=A: A(m, k)) \
        .priority(lambda k, NT=NT: 4 * (NT - k) + 1) \
        .flow("T", "RW",
              IN(TASK("GEQRT", "T", lambda k: dict(k=k)),
                 when=lambda m, k: m == k + 1),
              IN(TASK("TSQRT", "T", lambda m, k: dict(m=m - 1, k=k)),
                 when=lambda m, k: m > k + 1),
              OUT(TASK("TSQRT", "T", lambda m, k: dict(m=m + 1, k=k)),
                  when=lambda m, NT=NT: m < NT - 1),
              (OUT(TASK("RSTORE", "X", lambda k: dict(k=k)),
                   when=lambda m, NT=NT: m == NT - 1) if routed else
               OUT(DATA(lambda k, A=A: A(k, k)),
                   when=lambda m, NT=NT: m == NT - 1))) \
        .flow("B", "RW",
              IN(DATA(lambda m, k, A=A: A(m, k)), when=lambda k: k == 0),
              IN(TASK("TSMQR", "C2", lambda m, k: dict(m=m, n=k, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(DATA(lambda m, k, A=A: A(m, k)))) \
        .flow("Q", "RW",
              IN(NEW("q2")),
              OUT(TASK("TSMQR", "Q",
                       lambda m, k, NT=NT: [dict(m=m, n=n, k=k)
                                            for n in range(k + 1, NT)]),
                  when=lambda k, NT=NT: k < NT - 1))

    def cpu_tsqrt(T, B, Q):
        # same compact-WY math as the device kernel, in float64 for
        # stability (Cholesky-QR squares the condition number)
        R64 = np.asarray(T, dtype=np.float64)
        B64 = np.asarray(B, dtype=np.float64)
        try:
            Rp, V, Tt = _tsqrt_wy(R64, B64, np, np.linalg.cholesky,
                                  _np_tri_inv)
        except np.linalg.LinAlgError:
            # non-PD Gram matrix: Householder QR of the stacked panel
            # gives the same triangular factor, unconditionally stably
            Rh = np.linalg.qr(np.concatenate([R64, B64], axis=0),
                              mode="r")
            s = np.where(np.diagonal(Rh) >= 0, 1.0, -1.0)
            Rp, V, Tt = _wy_from_L(R64, B64, (s[:, None] * Rh).T, np,
                                   _np_tri_inv)
        dt = np.asarray(T).dtype
        return {"T": Rp.astype(dt), "B": np.zeros_like(np.asarray(B)),
                "Q": np.concatenate([V, Tt], axis=0).astype(dt)}
    bodies(tb, _k(("tsqrt", ib, upd), lambda: _mk_tsqrt(ib)), cpu_tsqrt)

    # TSMQR(m, n, k): apply Q2^T to the [A(k,n); A(m,n)] pair
    tb = p.task("TSMQR", k=Range(0, NT - 2),
                m=Range(lambda k: k + 1, NT - 1),
                n=Range(lambda k: k + 1, NT - 1)) \
        .affinity(lambda m, n, A=A: A(m, n)) \
        .priority(lambda k, NT=NT: 4 * (NT - k)) \
        .flow("Q", "READ", IN(TASK("TSQRT", "Q", lambda m, k: dict(m=m,
                                                                   k=k)))) \
        .flow("C1", "RW",
              IN(TASK("UNMQR", "C", lambda n, k: dict(k=k, n=n)),
                 when=lambda m, k: m == k + 1),
              IN(TASK("TSMQR", "C1", lambda m, n, k: dict(m=m - 1, n=n,
                                                          k=k)),
                 when=lambda m, k: m > k + 1),
              OUT(TASK("TSMQR", "C1", lambda m, n, k: dict(m=m + 1, n=n,
                                                           k=k)),
                  when=lambda m, NT=NT: m < NT - 1),
              (OUT(TASK("CSTORE", "X", lambda k, n: dict(k=k, n=n)),
                   when=lambda m, NT=NT: m == NT - 1) if routed else
               OUT(DATA(lambda k, n, A=A: A(k, n)),
                   when=lambda m, NT=NT: m == NT - 1))) \
        .flow("C2", "RW",
              IN(DATA(lambda m, n, A=A: A(m, n)), when=lambda k: k == 0),
              IN(TASK("TSMQR", "C2", lambda m, n, k: dict(m=m, n=n,
                                                          k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("GEQRT", "T", lambda m: dict(k=m)),
                  when=lambda m, n, k: m == k + 1 and n == k + 1),
              OUT(TASK("TSQRT", "B", lambda m, n, k: dict(m=m, k=k + 1)),
                  when=lambda m, n, k: m > k + 1 and n == k + 1),
              OUT(TASK("UNMQR", "C", lambda m, n, k: dict(k=k + 1, n=n)),
                  when=lambda m, n, k: m == k + 1 and n > k + 1),
              OUT(TASK("TSMQR", "C2", lambda m, n, k: dict(m=m, n=n,
                                                           k=k + 1)),
                  when=lambda m, n, k: m > k + 1 and n > k + 1))
    def cpu_tsmqr(Q, C1, C2):
        mb_ = np.asarray(C1).shape[0]
        Qn = np.asarray(Q)
        V, Tt = Qn[:mb_, :], Qn[mb_:, :]
        C1n, C2n = np.asarray(C1), np.asarray(C2)
        Z = Tt @ (C1n + V.T @ C2n)
        return {"C1": C1n - Z, "C2": C2n - V @ Z}
    bodies(tb, _k("tsmqr", _mk_tsmqr), cpu_tsmqr)

    if routed:
        tb = p.task("RSTORE", k=Range(0, NT - 2)) \
            .affinity(lambda k, A=A: A(k, k)) \
            .flow("X", "RW",
                  IN(TASK("TSQRT", "T", lambda k, NT=NT: dict(m=NT - 1,
                                                              k=k))),
                  OUT(DATA(lambda k, A=A: A(k, k))))
        bodies(tb, _k("store", lambda: (lambda X: X)),
               lambda X: np.asarray(X))
        tb = p.task("CSTORE", k=Range(0, NT - 2),
                    n=Range(lambda k: k + 1, NT - 1)) \
            .affinity(lambda k, n, A=A: A(k, n)) \
            .flow("X", "RW",
                  IN(TASK("TSMQR", "C1",
                          lambda k, n, NT=NT: dict(m=NT - 1, n=n, k=k))),
                  OUT(DATA(lambda k, n, A=A: A(k, n))))
        bodies(tb, _k("store", lambda: (lambda X: X)),
               lambda X: np.asarray(X))

    tp = p.build()
    for name, tc in tp.task_classes.items():
        # executed-flop weights for device load balancing (stores move
        # a tile, no flops)
        tc.properties["flops"] = {"GEQRT": 2.0 * mb ** 3,
                                  "UNMQR": 2.0 * mb ** 3,
                                  "TSQRT": 6.0 * mb ** 3,
                                  "TSMQR": 10.0 * mb ** 3}.get(name, 1.0)
    # cross-panel fused dispatch (devices/xla.py chain fusion): the
    # GEQRT(k) -> TSQRT(k+1,k) -> ... -> TSQRT(NT-1,k) column is the
    # serial spine of the DAG — each link's only missing input is its
    # predecessor's T, so the device layer holds the head and traces the
    # whole column INTO its consumers' launch (one dispatch round trip
    # instead of one per link).  TSQRT co-locates on the diagonal tile's
    # device so the column is a chain on ONE device.
    tp.task_classes["GEQRT"].properties["fuse_chain"] = ("T", "TSQRT")
    tp.task_classes["TSQRT"].properties["fuse_chain"] = ("T", "TSQRT")
    tp.task_classes["TSQRT"].properties["coaffinity"] = \
        lambda loc, A=A: A(loc["k"], loc["k"])
    return tp


def geqrf_flops(m: int, n: int) -> float:
    """Useful FLOPs of an m x n QR factorization (2mn^2 - 2n^3/3;
    = 4n^3/3 when square)."""
    return 2.0 * m * n * n - 2.0 * n ** 3 / 3.0
