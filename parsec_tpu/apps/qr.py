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
and TSMQR three mb^3-class products, 6 mb^3 flop for the 4 that LAPACK
counts:

    Z = T^T (C1 + V^T C2);   C1 -= Z;   C2 -= V Z

Everything lowers to the systolic array; R ends in the upper triangle;
tiles below are zeroed.

THE Q EDGE IN COLUMN GROUPS OF W (PR 32).  The reflector travels as mb /
W compact-WY reflectors, one a group of W columns, Q^T = Q_last^T ...
Q_0^T: arena ``q2`` is (mb + W, mb), V (mb x mb) over the W x mb strip
of the groups' W x W factors T_G^T side by side — the diagonal blocks of
what a panel-wide T^T would be; nothing outside them is computed or
stored.  TSMQR applies the groups in turn, group 0 first,

    Z = T_G^T (C1[G] + V_G^T C2);   C1[G] -= Z;   C2 -= V_G Z

at 4 mb^3 + 2 W mb^2 flop: V^T C2 and V Z cost what they did, the
product with T^T shrinks from mb wide to W wide (4.33 mb^3 at W = mb /
6, against 6).  The running C2 stays in f32 across the groups of a task
and rounds to the tiles' storage dtype once, on output; the operands go
to the MXU in the storage dtype.  W is ``group_width(mb, ib)``, a
function of the shapes alone, and TSQRT and TSMQR (device and CPU
bodies alike) read it off the edge they are handed: narrow enough that
T^T's product and TSQRT's accumulation (below) stay small, wide enough
that the rank-W update of an f32 tile — W / 4 flop a byte read and
written — does not fall under the chip's ridge (240 on a v5e): at mb =
6144, ib = 512 the cell ran fastest at W = 1024, TSMQR alone 7.2 ms a
task there against 10.3 at W = 512 and 8.8 panel-wide (PERF.md section
6, PR 32: the table over W that fixed ``_GROUP_MIN``).  W = mb,
one group, is the panel-wide factor and the (2 mb, mb) edge: what the
unblocked construction emits, and a pool built for the CPU.

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
           trailing columns of [R; B] updated by the WY application at
           DEFAULT, and the per-block (V_j, T_j^T) pairs of ONE GROUP
           of W columns aggregated into the group's (V_G, T_G^T) with
           the standard T-accumulation, against the blocks of the same
           group alone (s0 = the group's first column)
               T_G^T[J, s0:s] = -T_j^T (V_j^T V[:, s0:s]) T_G^T[s0:s, s0:s]
           (block lower triangular; W-wide HIGHEST products, mb / ib *
           (W / ib - 1) / 2 of each kind a panel: 6 + 6 at mb = 6144,
           W = 1024, where a panel-wide T takes 66 + 66, mb wide;
           DPLASMA's -i never forms one either).

Knobs: --mca qr_ib N (0 = unblocked; ignored unless 0 < ib < mb and
ib | mb) and --mca qr_update_precision {default,highest} for the
intra-panel trailing updates.

ONE TRACED BODY (PR 31): the mb / ib column blocks run as a
``lax.fori_loop``, so the ib-sized Choleskys, triangular inverses and
WY assembly — what the TPU compiler is slow on — compile once a kernel
and not once a block.  What follows the block's place in the panel
(re-projection / T-accumulation against the blocks before it, the
trailing update of the blocks after it) runs as inner loops over those
blocks, one ib-wide column block a turn: the same flop as the
full-extent products of an unrolled panel, no flop spent on padding,
and the loop carries, kept as stacks of column blocks
(``_col_blocks``), are updated in place (a ``lax.switch`` over
full-extent branches copied them at every turn: 22 of TSQRT's 53 ms on
a v5e, PERF.md PR 31).  A block whose Gram
matrix is not positive definite in f32 takes shifted Cholesky-QR passes
until the plain ones hold (``_cholqr_passes``, behind ``_cholqr2`` and
``_gram_factor``): a ``lax.while_loop`` of one pass, so one more
ib-sized Cholesky to compile whatever the block's condition; the
blocked kernels carry no Householder fall-back (XLA's QR expander at
panel size was two thirds of their compile time and never ran).  The UNBLOCKED construction
(qr_ib 0, or an ib that does not block mb) keeps its Householder guard.
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

#: the group width W every traced blocked TSQRT took, keyed
#: ("TSQRT", mb, ib): for tests and PERF.md, as apps/potrf.py's
selected = {}


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


#: narrowest group of reflector columns TSQRT accumulates and TSMQR
#: applies at once: the width at which the cell ran fastest on a v5e at
#: mb = 6144, ib = 512 (PERF.md section 6, PR 32: 90.9 TF/s at 1024,
#: 89.6 at 1536, 84.6 at 2048, 75.8 at 512, 65.4 panel-wide)
_GROUP_MIN = 1024


def group_width(mb: int, ib: int) -> int:
    """Columns W a group of the TSQRT -> TSMQR reflector holds, for a
    device pool: the narrowest multiple of ib that divides mb and
    reaches ``_GROUP_MIN``; mb (one group, the panel-wide factor) where
    none does, or the panel is unblocked (ib 0)."""
    if ib:
        for W in range(ib, mb, ib):
            if mb % W == 0 and W >= _GROUP_MIN:
                return W
    return mb


def qr_executed_flops(cls: str, mb: int, ib: int, W: int) -> float:
    """Flop one task of class ``cls`` executes on mb x mb tiles (the
    device load-balancing weights; benchmark/reference/geqrf.py counts
    the USEFUL flop), the matmul-class products alone.  TSMQR: V^T C2
    and V Z whole, T^T a group at a time.  TSQRT, blocked: per block the
    Gram matrix and V (4 mb ib^2), the trailing update of every block
    after it (4 mb ib^2 a pair), the accumulation against the blocks of
    its group before it (2 ib^2 (mb + W) a pair); unblocked: the two
    Gram products, V and T^T."""
    if cls == "TSMQR":
        return 4.0 * mb ** 3 + 2.0 * W * mb ** 2
    if cls == "TSQRT" and ib:
        nblk, bpg = mb // ib, W // ib
        return ib ** 2 * (4.0 * mb * nblk + 2.0 * mb * nblk * (nblk - 1)
                          + (mb + W) * nblk * (bpg - 1.0))
    whole = {"GEQRT": 2.0, "UNMQR": 2.0, "TSQRT": 8.0}
    # the stores of a routed pool move a tile, no flops
    return whole[cls] * mb ** 3 if cls in whole else 1.0


def _update_precision():
    """Precision of intra-panel trailing updates (None = DEFAULT)."""
    import jax
    val = str(params.get("qr_update_precision", "default")).lower()
    return jax.lax.Precision.HIGHEST if val == "highest" else None


def _shift(m: int, n: int) -> float:
    """Diagonal shift of the stable Cholesky-QR pass for an m x n block
    whose Gram matrix is scaled to unit diagonal: 11 (m + n + 1) u, the
    shifted-CholeskyQR bound (Fukaya et al., SISC 2020) taken a column
    — enough for the factor to exist whatever the block's condition,
    small enough that the passes after it reach working accuracy."""
    return 11.0 * (m + n + 1) * float(np.finfo(np.float32).eps) / 2.0


def _scaled_chol(G, jnp):
    """Cholesky of ``G`` scaled to unit diagonal (Jacobi equilibration
    keeps a decaying R's dynamic range out of the factor): (Ls, Gs, dg)
    with G = (dg Ls)(dg Ls)^T; NaN where G is not positive definite in
    f32 (cond of the block beyond ~1/sqrt(eps))."""
    dg = jnp.sqrt(jnp.clip(jnp.diagonal(G), 1e-30, None))
    Gs = G / dg[:, None] / dg[None, :]
    return jnp.linalg.cholesky(Gs), Gs, dg


_MAX_PASSES = 6     # Cholesky-QR passes a block takes at most


def _cholqr_passes(blocks, jnp, hi, gram):
    """Cholesky-QR passes over the stacked block ``[blocks...]`` (a
    tuple of row blocks with the same columns) at HIGHEST precision,
    until it is orthonormal: (Q blocks, R) with R upper triangular and
    [blocks...] = [Q blocks...] R.

    One pass: G = gram(X), G = L L^T, X <- X L^-T, R <- L^T R.  Where G
    is not positive definite in f32 the pass factors the SHIFTED Gram
    matrix instead (shifted Cholesky-QR): L still folds into R exactly,
    and X's condition drops by ~sqrt(shift) a pass, so a block of any
    condition comes down to where the plain passes hold.  The last pass
    is the one whose input is already near orthonormal (||G - I||_F
    < 1/2: its cond^2 is under 3, so what it leaves is orthonormal to
    working accuracy).  A well-conditioned block takes two passes
    (CholeskyQR2), a square Gaussian ib x ib block (the last of every
    GEQRT panel; cond over 1/sqrt(eps) one time in four) three or
    four, and ``_MAX_PASSES`` bounds a rank-deficient one, whose R is
    still a factor of its Gram matrix to the shift's accuracy.  One
    traced body whatever the block: an ib-sized Cholesky, its shifted
    twin behind a ``lax.cond``, one triangular inverse."""
    from jax import lax
    mm = lambda a, b: jnp.matmul(a, b, precision=hi)
    n = blocks[0].shape[1]
    eye = jnp.eye(n, dtype=blocks[0].dtype)
    shift = _shift(sum(b.shape[0] for b in blocks), n)

    def one(state):
        X, R, _last, it = state
        G = gram(X)
        Ls, Gs, dg = _scaled_chol(G, jnp)
        Ls = lax.cond(jnp.all(jnp.isfinite(Ls)), lambda _: Ls,
                      lambda _: jnp.linalg.cholesky(Gs + shift * eye), None)
        L = Ls * dg[:, None]
        W = tri_inv(L, precision=hi).T
        return (tuple(mm(x, W) for x in X), mm(L.T, R),
                jnp.sum((G - eye) ** 2) < 0.25, it + 1)

    X, R, _last, _it = lax.while_loop(
        lambda st: jnp.logical_and(~st[2], st[3] < _MAX_PASSES), one,
        (tuple(blocks), eye, jnp.bool_(False), jnp.int32(0)))
    return X, R


def _cholqr2(cols, jnp, hi, gram=None):
    """Cholesky-QR of one mb x ib column block at HIGHEST precision:
    returns (Q, R) with Q orthonormal and R upper triangular
    (``_cholqr_passes``: two passes on a well-conditioned block — one
    loses orthogonality as cond^2*eps — shifted ones first on a block
    whose Gram matrix is not positive definite in f32).  ``gram`` swaps
    the Gram products for a hand-written kernel
    (apps/pallas_kernels.pallas_gram_tile)."""
    gram = gram or (lambda X: jnp.matmul(X.T, X, precision=hi))
    (Q,), R = _cholqr_passes((cols,), jnp, hi, lambda X: gram(X[0]))
    return Q, R


def _gram_factor(top, bot, jnp, hi):
    """Lower-triangular L with L L^T = top^T top + bot^T bot, the Gram
    matrix of the stacked block [top; bot], at HIGHEST precision: its
    scaled Cholesky factor, or — where that is not positive definite in
    f32 — the triangular factor of ``_cholqr_passes`` over the stacked
    block: the same factor a Householder QR of it gives, from matmuls
    and ib-sized Choleskys alone."""
    from jax import lax
    mm = lambda a, b: jnp.matmul(a, b, precision=hi)
    gram = lambda X: mm(X[0].T, X[0]) + mm(X[1].T, X[1])
    Ls, _Gs, dg = _scaled_chol(gram((top, bot)), jnp)
    return lax.cond(
        jnp.all(jnp.isfinite(Ls)), lambda _: Ls * dg[:, None],
        lambda _: _cholqr_passes((top, bot), jnp, hi, gram)[1].T, None)


def _col_blocks(M, ib):
    """(mb, n) -> (n / ib, mb, ib): the matrix as a stack of its ib-wide
    column blocks.  A panel loop reads and rewrites one column block a
    turn; as a slab of a row-major array that is a strided update the
    compiler runs as an op of its own (80 us for 12.6 MB on a v5e, 5 ms
    a panel kernel), as an index of the leading axis it is contiguous
    and fused in place."""
    mb, n = M.shape
    return M.reshape(mb, n // ib, ib).transpose(1, 0, 2)


def _from_col_blocks(Mb):
    nblk, mb, ib = Mb.shape
    return Mb.transpose(1, 0, 2).reshape(mb, nblk * ib)


def _blk(Mb, k):
    """Column block ``k`` of a stack of column blocks."""
    from jax import lax
    return lax.dynamic_index_in_dim(Mb, k, 0, keepdims=False)


def _put(Mb, X, k):
    """The stack with column block ``k`` replaced by ``X``."""
    from jax import lax
    return lax.dynamic_update_index_in_dim(Mb, X, k, 0)


def _geqrt_blocked(Tf, ib, jnp, hi, up, gram=None):
    """Inner-blocked GEQRT (module docstring): per-block CholeskyQR2 +
    one re-projection against the accumulated basis at HIGHEST
    (O(mb^2*ib) total), trailing columns updated at ``up`` precision
    (errors enter linearly).  Q comes out explicit — the blocks ARE its
    orthonormal columns.  Returns (R, Q)."""
    from jax import lax
    mb = Tf.shape[0]
    nblk = mb // ib
    mm = lambda a, b, p: jnp.matmul(a, b, precision=p)
    blk, put = _blk, _put

    def step(j, carry):
        A, R, Q = carry                 # A, Q as stacks of column blocks
        s = j * ib
        cols = blk(A, j)

        # BCGS2-flavored reorthogonalization against the blocks before
        # this one: the trailing updates already projected it, but
        # rounding reintroduces ~eps*cond components; one extra HIGHEST
        # pass restores inter-block orthogonality.  The coefficients
        # fold into R exactly.
        def coeff(k, prj):
            return lax.dynamic_update_slice(
                prj, mm(blk(Q, k).T, cols, hi), (k * ib, 0))
        prj = lax.fori_loop(0, j, coeff, jnp.zeros((mb, ib), jnp.float32))

        def project(k, c):
            return c - mm(blk(Q, k), lax.dynamic_slice(
                prj, (k * ib, 0), (ib, ib)), hi)
        Qj, Rjj = _cholqr2(lax.fori_loop(0, j, project, cols), jnp, hi,
                           gram=gram)

        # the blocks after this one: A_k -= Qj (Qj^T A_k), the
        # coefficients are R[J, K]
        def trailing(k, AR):
            A, Rrow = AR
            Ak = blk(A, k)
            Rjk = mm(Qj.T, Ak, up)
            return (put(A, Ak - mm(Qj, Rjk, up), k),
                    lax.dynamic_update_slice(Rrow, Rjk, (0, k * ib)))
        A, Rrow = lax.fori_loop(j + 1, nblk, trailing,
                                (A, jnp.zeros((ib, mb), jnp.float32)))
        # R's column block takes the projection coefficients on top of
        # what the earlier blocks' trailing updates left there, its row
        # block the diagonal factor and this block's coefficients
        R = lax.dynamic_update_slice(
            R, lax.dynamic_slice(R, (0, s), (mb, ib)) + prj, (0, s))
        R = lax.dynamic_update_slice(
            R, lax.dynamic_update_slice(Rrow, Rjj, (0, s)), (s, 0))
        return A, R, put(Q, Qj, j)

    zeros = jnp.zeros((mb, mb), jnp.float32)
    _A, R, Q = lax.fori_loop(
        0, nblk, step, (_col_blocks(Tf, ib), zeros,
                        jnp.zeros((nblk, mb, ib), jnp.float32)))
    return R, _from_col_blocks(Q)


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

        if 0 < ib < mb and mb % ib == 0:
            # inner-blocked panel: the blocks run as one traced body; an
            # ill-conditioned block takes shifted passes (_cholqr_passes)
            R, Qm = _geqrt_blocked(Tf, ib, jnp, hi, _update_precision(),
                                   gram=gram)
            return {"T": R.astype(T.dtype), "Q": Qm.astype(T.dtype)}

        Ls, _Gs, dg = _scaled_chol(jnp.matmul(Tf.T, Tf, precision=hi), jnp)
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

        def stable(_):
            return jnp.linalg.qr(Tf, mode="reduced")[::-1]

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


def _tsqrt_blocked(T, B, ib, W, jnp, hi, up):
    """Inner-blocked TSQRT construction (module docstring): returns
    (R', V, T^T) with T^T the W x mb strip of the grouped compact-WY
    factor's mb / W diagonal blocks side by side, each block lower
    triangular.  The per-block Gram factor and WY assembly run at
    HIGHEST; the trailing updates of [R; B] run at ``up`` precision.
    An ill-conditioned block takes ``_gram_factor``'s shifted passes."""
    from jax import lax
    mb = T.shape[0]
    nblk, bpg = mb // ib, W // ib
    mm = lambda a, b, p: jnp.matmul(a, b, precision=p)
    ti = lambda M: tri_inv(M, precision=hi)
    # B and V as stacks of column blocks (_col_blocks), T^T as a stack
    # of its groups' diagonal blocks, R's row block and the
    # accumulation's ib x W rows as they are
    blk, put = _blk, _put
    col = lambda M, k: lax.dynamic_slice(M, (0, k * ib), (ib, ib))
    putcol = lambda M, X, k: lax.dynamic_update_slice(M, X, (0, k * ib))

    def step(j, carry):
        Rc, Bc, V, Tt = carry
        s = j * ib
        Rrow = lax.dynamic_slice(Rc, (s, 0), (ib, mb))
        Rjj, Bj = col(Rrow, j), blk(Bc, j)
        L = _gram_factor(Rjj, Bj, jnp, hi)
        Rpjj, Vj, Tjt = _wy_from_L(Rjj, Bj, L, jnp, ti, precision=hi)

        # WY application to the blocks after this one of the stacked
        # panel (same shape as TSMQR, errors enter linearly)
        def trailing(k, RB):
            Rrow, Bc = RB
            C1, C2 = col(Rrow, k), blk(Bc, k)
            Z = mm(Tjt, C1 + mm(Vj.T, C2, up), up)
            return putcol(Rrow, C1 - Z, k), put(Bc, C2 - mm(Vj, Z, up), k)
        Rrow, Bc = lax.fori_loop(j + 1, nblk, trailing, (Rrow, Bc))

        # T-accumulation inside the block's GROUP (blocks g0 .. j-1,
        # columns g0 * ib on): Q^T = Q_j^T Q_prev^T collapses to one
        # compact-WY pair with the block-lower-triangular
        # T^T[J, :s] = -T_j^T (W_j^T W_prev) T^T[:s, :s]; the unit tops
        # of W are disjoint identity columns, so W_j^T W_prev
        # = V_j^T V[:, :s], taken a block at a time.  The groups before
        # this one stay reflectors of their own: TSMQR applies them in
        # turn.  V takes V_j BEFORE the blocks before it are read: one
        # live version of the carry (read first, the compiler kept two
        # and copied 151 MB twice a block, 11 of 48 ms on a v5e)
        V = put(V, Vj, j)
        g = j // bpg
        g0 = g * bpg

        def cross(k, X):
            return putcol(X, mm(Vj.T, blk(V, k), hi), k - g0)
        zrow = jnp.zeros((ib, W), jnp.float32)
        X = lax.fori_loop(g0, j, cross, zrow)

        def times_tt(k, Y):
            return Y + mm(col(X, k - g0), lax.dynamic_slice(
                Tt, (g, (k - g0) * ib, 0), (1, ib, W))[0], hi)
        Trow = -mm(Tjt, lax.fori_loop(g0, j, times_tt, zrow), hi)

        Rc = lax.dynamic_update_slice(Rc, putcol(Rrow, Rpjj, j), (s, 0))
        Tt = lax.dynamic_update_slice(
            Tt, putcol(Trow, Tjt, j - g0)[None], (g, (j - g0) * ib, 0))
        return Rc, Bc, V, Tt

    Rc, _Bc, V, Tt = lax.fori_loop(
        0, nblk, step, (T, _col_blocks(B, ib),
                        jnp.zeros((nblk, mb, ib), jnp.float32),
                        jnp.zeros((mb // W, W, W), jnp.float32)))
    return Rc, _from_col_blocks(V), _from_col_blocks(Tt)


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

        def unblocked():
            Ls, _Gs, dg = _scaled_chol(
                jnp.matmul(T.T, T, precision=hi)
                + jnp.matmul(B.T, B, precision=hi), jnp)
            L = Ls * dg[:, None]

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

        # the group width is the edge's: Q is (mb + W, mb)
        W = Q.shape[0] - mb
        if 0 < ib < mb and mb % ib == 0:
            if W <= 0 or W % ib or mb % W:
                raise ValueError(f"TSQRT: a Q edge of {Q.shape} groups "
                                 f"no mb={mb} panel of ib={ib} blocks")
            selected[("TSQRT", mb, ib)] = W
            Rp, V, Tt = _tsqrt_blocked(T, B, ib, W, jnp, hi,
                                       _update_precision())
        elif W != mb:
            raise ValueError(f"TSQRT: the unblocked panel is one group, "
                             f"its Q edge (2 mb, mb), not {Q.shape}")
        else:
            Rp, V, Tt = unblocked()
        dt = Q.dtype                    # NEW-flow arena dtype = storage
        return {"T": Rp.astype(dt), "B": jnp.zeros_like(B, dtype=dt),
                "Q": jnp.concatenate([V, Tt], axis=0).astype(dt)}
    return fn


def _mk_tsmqr():
    def fn(Q, C1, C2):
        import jax.numpy as jnp
        mb = C1.shape[0]
        W = Q.shape[0] - mb             # the edge's group width
        f32 = jnp.float32
        mm = lambda a, b: jnp.matmul(a, b, preferred_element_type=f32)
        # Q^T = Q_last^T ... Q_0^T, one compact-WY reflector a group of
        # W columns, group 0 first.  Operands go to the MXU in the
        # tiles' storage dtype; the running C2 stays in f32 across the
        # groups and rounds to it once, on output (rows G of C1 are
        # touched by group G alone)
        acc, op, top = C2.astype(f32), C2, []
        for lo in range(0, mb, W):
            V, Tt = Q[:mb, lo:lo + W], Q[mb:, lo:lo + W]
            rows = C1[lo:lo + W].astype(f32)
            Z = mm(Tt, (rows + mm(V.T, op)).astype(Tt.dtype))
            top.append((rows - Z).astype(C1.dtype))
            acc = acc - mm(V, Z.astype(V.dtype))
            op = acc.astype(C2.dtype)
        return {"C1": jnp.concatenate(top, axis=0), "C2": op}
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
    # the reflector's group width follows the shapes; CPU bodies alone
    # keep the panel-wide factor (float64 products have no ridge to miss)
    W = group_width(mb, ib) if use_device else mb
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
    # V over the W x mb strip of T^T's mb / W diagonal blocks
    p.arena("q2", (mb + W, mb), dtype=A.dtype)

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

    def cpu_wy(R64, B64):
        try:
            return _tsqrt_wy(R64, B64, np, np.linalg.cholesky, _np_tri_inv)
        except np.linalg.LinAlgError:
            # non-PD Gram matrix: Householder QR of the stacked panel
            # gives the same triangular factor, unconditionally stably
            Rh = np.linalg.qr(np.concatenate([R64, B64], axis=0),
                              mode="r")
            s = np.where(np.diagonal(Rh) >= 0, 1.0, -1.0)
            return _wy_from_L(R64, B64, (s[:, None] * Rh).T, np,
                              _np_tri_inv)

    def cpu_tsqrt(T, B, Q):
        # same compact-WY math as the device kernel, in float64 for
        # stability (Cholesky-QR squares the condition number), a group
        # of the edge's W columns at a time: the group's pair from its
        # own columns of [R; B], then applied to the columns after it —
        # the pair the device's blocks accumulate to (one QR, one sign
        # choice, one unit-top form)
        R64 = np.array(T, dtype=np.float64)
        B64 = np.array(B, dtype=np.float64)
        W_ = np.asarray(Q).shape[0] - mb
        V, Tt = np.empty((mb, mb)), np.empty((W_, mb))
        for lo in range(0, mb, W_):
            G, rest = slice(lo, lo + W_), slice(lo + W_, mb)
            R64[G, G], V[:, G], Tt[:, G] = cpu_wy(R64[G, G], B64[:, G])
            Z = Tt[:, G] @ (R64[G, rest] + V[:, G].T @ B64[:, rest])
            R64[G, rest] -= Z
            B64[:, rest] -= V[:, G] @ Z
        dt = np.asarray(T).dtype
        return {"T": R64.astype(dt), "B": np.zeros_like(np.asarray(B)),
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
        Qn = np.asarray(Q)
        W_ = Qn.shape[0] - mb
        C1n, C2n = np.array(C1), np.array(C2)
        for lo in range(0, mb, W_):
            G = slice(lo, lo + W_)
            V, Tt = Qn[:mb, G], Qn[mb:, G]
            Z = Tt @ (C1n[G] + V.T @ C2n)
            C1n[G] -= Z
            C2n -= V @ Z
        return {"C1": C1n, "C2": C2n}
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
        # executed-flop weights for device load balancing
        tc.properties["flops"] = qr_executed_flops(name, mb, ib, W)
    # cross-panel fused dispatch (devices/xla.py chain fusion): the
    # GEQRT(k) -> TSQRT(k+1,k) -> ... -> TSQRT(NT-1,k) column is the
    # serial spine of the DAG — each link's only missing input is its
    # predecessor's T.  Both classes name TSQRT as the successor on T, so
    # the device layer takes the column two links at a time: a head is
    # held and traced INTO the launch of the link after it (one dispatch
    # round trip for the pair), and the UNMQR / TSMQR waves that read a
    # held head's Q follow that launch.  Four programs hold a panel
    # kernel whatever NT is: jit_parsec_GEQRT, jit_parsec_TSQRT,
    # jit_parsec_chain_GEQRT__TSQRT_x1, jit_parsec_chain_TSQRT__TSQRT_x1.
    # TSQRT co-locates on the diagonal tile's device so the column is a
    # chain on ONE device.
    tp.task_classes["GEQRT"].properties["fuse_chain"] = ("T", "TSQRT")
    tp.task_classes["TSQRT"].properties["fuse_chain"] = ("T", "TSQRT")
    tp.task_classes["TSQRT"].properties["coaffinity"] = \
        lambda loc, A=A: A(loc["k"], loc["k"])
    return tp


def geqrf_flops(m: int, n: int) -> float:
    """Useful FLOPs of an m x n QR factorization (2mn^2 - 2n^3/3;
    = 4n^3/3 when square)."""
    return 2.0 * m * n * n - 2.0 * n ** 3 / 3.0
