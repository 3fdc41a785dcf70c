"""Tiled LU factorization without pivoting (DPLASMA dgetrf_nopiv), the
factorization phase of HPL-MxP.

The right-looking tile algorithm over the WHOLE square tile grid: no
symmetry halves the trailing update, and every step has two panel
families.

    GETRF(k)     : A[k,k] = L\\U (in place);  WL[k] = L^-1, WU[k] = U^-T
    TRSMR(k,n)   : A[k,n] = WL[k] @ A[k,n]            row panel U[k,n]
    TRSMC(m,k)   : A[m,k] = A[m,k] @ WU[k]^T          column panel L[m,k]
    GEMM(m,n,k)  : A[m,n] -= A[m,k] @ A[k,n]          (m, n > k)

with ``GETRFL`` for the last diagonal tile, which has no panel and so
needs no inverse (as potrf's POTRFL).  L is unit lower triangular; the
diagonal tile keeps L's strict lower part and U's upper part packed.

The matrix must not need pivoting (HPL-MxP builds its operand so that it
does not): no row is ever exchanged, and a zero or tiny pivot is not
detected.

TPU-first, as potrf: every TRSM is a matmul against an inverse that
GETRF emits in float32.  The GETRF kernel is a recursive blocked LU
whose halves are matmuls (the off-diagonal blocks of the factor by the
inverses of the leading block's triangles, the trailing block by one
product) and whose leaves, 512 or less, are factored in float32 by
XLA's own LU where its partial pivoting exchanges no row — then its
factor IS the unpivoted one — and by a plain elimination where it
would (on a v5e the elimination's loop costs about 3 us a pivot, 23
ms a 6144 tile, against 14 ms for the whole kernel with XLA's LU at the
leaves).  The leaves' triangles are inverted by
``potrf.tri_inv`` and the recursion assembles L^-1 and U^-T from them
by the same 2x2 block formula, from the inverses every level already
holds for its own solves.

Chain fusion (devices/xla.py): GETRF(k)'s outputs feed TWO classes,
ready at the same instant; its ``fuse_chain`` names the row panel
(WL, TRSMR).  The held head rides the row family's first wave; column
tasks that a race made ready first read a held output of a class the
head does not declare, so they wait for that launch and read the
resolved inverses.  A run builds the (GETRF, TRSMR, width) chain
programs alone.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from parsec_tpu.apps.pallas_kernels import PALLAS, pallas_update, update_form
from parsec_tpu.apps.potrf import _cpu_trsm, _k_trsm, tri_blocks, tri_inv
from parsec_tpu.core.taskpool import ParameterizedTaskpool
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.dsl.ptg.api import DATA, IN, NEW, OUT, PTG, Range, TASK

_kernels = {}

#: which form ran the update for every traced ("GEMM", tile order):
#: "pallas" / "xla" (potrf.selected's rule)
selected = {}

#: a recursion node of this order or less is a leaf, factored directly
#: (its triangles are inverted by ``potrf.tri_inv``)
_LU_LEAF = 512
#: the recursion splits an order at a multiple of this where it can, so
#: that the halves' products keep MXU-aligned shapes
_LU_ALIGN = 128


def _split(n: int) -> int:
    """Order of the leading block when an order-n block is halved."""
    h = (n // 2) // _LU_ALIGN * _LU_ALIGN
    return h if h else n // 2


def _eliminate(A):
    """Unpivoted LU of a small float32 block, one pivot at a time: the
    packed L\\U."""
    import jax.numpy as jnp
    from jax import lax
    n = A.shape[0]
    r = lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    c = lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def pivot(j, F):
        row = lax.dynamic_slice_in_dim(F, j, 1, axis=0)          # (1, n)
        col = lax.dynamic_slice_in_dim(F, j, 1, axis=1)          # (n, 1)
        p = lax.dynamic_slice(row, (0, j), (1, 1))
        below = r > j
        l = jnp.where(below, col / p, 0.0)
        u = jnp.where(c > j, row, 0.0)
        return jnp.where((c == j) & below, l, F - l * u)

    return lax.fori_loop(0, n, pivot, A)


def _lu_leaf(A):
    """Unpivoted LU of a leaf, packed L\\U: XLA's LU where its partial
    pivoting kept every row in place (the same factor), the elimination
    where it did not."""
    import jax.numpy as jnp
    from jax import lax
    lu, _pivots, perm = lax.linalg.lu(A)
    kept = jnp.all(perm == jnp.arange(A.shape[0], dtype=perm.dtype))
    return lax.cond(kept, lambda: lu, lambda: _eliminate(A))


def lu_inv(A, precision=None, inverses: bool = True):
    """Unpivoted LU of a float32 block (jax-traceable), recursively:
    returns (F, Li, G) — F the packed L\\U, Li = L^-1 (unit lower) and
    G = U^-T (lower) — or (F, None, None) where ``inverses`` is False
    (the leading half's inverses are computed all the same: the
    recursion solves with them).

        A = [[A11, A12], [A21, A22]]:  A11 = L11 U11;
        U12 = L11^-1 A12;  L21 = A21 U11^-1;  A22 - L21 U12 = L22 U22;
        L^-1 = [[L11^-1, 0], [-L22^-1 L21 L11^-1, L22^-1]]
        U^-T = [[U11^-T, 0], [-U22^-T U12^T U11^-T, U22^-T]]
    """
    import jax.numpy as jnp
    n = A.shape[0]
    if n <= _LU_LEAF:
        F = _lu_leaf(A)
        if not inverses:
            return F, None, None
        L = jnp.tril(F, -1) + jnp.eye(n, dtype=F.dtype)
        return F, tri_inv(L, precision), tri_inv(jnp.triu(F).T, precision)

    def mm(x, y):
        return jnp.matmul(x, y, precision=precision)

    h = _split(n)
    F11, Li11, G11 = lu_inv(A[:h, :h], precision)
    U12 = mm(Li11, A[:h, h:])
    L21 = mm(A[h:, :h], G11.T)
    F22, Li22, G22 = lu_inv(A[h:, h:] - mm(L21, U12), precision, inverses)
    F = jnp.concatenate([jnp.concatenate([F11, U12], axis=1),
                         jnp.concatenate([L21, F22], axis=1)], axis=0)
    if not inverses:
        return F, None, None
    zero = jnp.zeros((h, n - h), A.dtype)
    Li = jnp.concatenate(
        [jnp.concatenate([Li11, zero], axis=1),
         jnp.concatenate([-mm(Li22, mm(L21, Li11)), Li22], axis=1)], axis=0)
    G = jnp.concatenate(
        [jnp.concatenate([G11, zero], axis=1),
         jnp.concatenate([-mm(G22, mm(U12.T, G11)), G22], axis=1)], axis=0)
    return F, Li, G


def getrf_executed_flops(cls: str, mb: int) -> float:
    """Flop one task of class ``cls`` executes on an mb x mb tile (the
    device load-balancing weights; benchmark/reference/getrf.py counts
    the USEFUL flop): GETRF with its two inverses about 8 mb^3 / 3 and
    GETRFL 3 mb^3 / 2 (the TPU compiler's counts at mb = 6144), a TRSM
    (b+1)/(2b) of the full product against its triangle."""
    if cls in ("TRSMR", "TRSMC"):
        b = tri_blocks("TRSM", mb)
        return mb ** 3 * (b + 1.0) / b
    return {"GETRF": 8.0 * mb ** 3 / 3.0, "GETRFL": 1.5 * mb ** 3,
            "GEMM": 2.0 * mb ** 3}[cls]


def _k_getrf(precision):
    fn = _kernels.get(("getrf", precision))
    if fn is None:
        def fn(T, WL, WU):
            import jax.numpy as jnp
            # factored in f32 whatever the tile stores; the two inverses
            # stay f32: they multiply every tile of the step's panels
            F, Li, G = lu_inv(T.astype(jnp.float32), precision)
            return {"T": F.astype(T.dtype), "WL": Li, "WU": G}
        _kernels[("getrf", precision)] = fn
    return fn


def _k_getrf_last(precision):
    # the last diagonal tile has no panel: no inverse leaves the task
    fn = _kernels.get(("getrf_last", precision))
    if fn is None:
        def fn(T):
            import jax.numpy as jnp
            F, _li, _g = lu_inv(T.astype(jnp.float32), precision,
                                inverses=False)
            return F.astype(T.dtype)
        _kernels[("getrf_last", precision)] = fn
    return fn


def _k_trsmr(precision):
    fn = _kernels.get(("trsmr", precision))
    if fn is None:
        def fn(W, C):
            import jax.numpy as jnp
            from jax import lax
            # C <- L^-1 C == W @ C (W from GETRF).  W is lower
            # triangular, so block row i needs the first i+1 block rows
            # of C alone.  Last block first, each reading rows that no
            # later block has written: the donated tile is updated in
            # place (potrf's TRSM, transposed)
            mb = C.shape[0]
            s = mb // tri_blocks("TRSM", mb)
            out = C
            for i in reversed(range(mb // s)):
                lo, hi = i * s, (i + 1) * s
                acc = jnp.matmul(W[lo:hi, :hi], out[:hi, :],
                                 precision=precision,
                                 preferred_element_type=jnp.float32)
                out = lax.dynamic_update_slice(out, acc.astype(C.dtype),
                                               (lo, 0))
            return out
        _kernels[("trsmr", precision)] = fn
    return fn


def _k_gemm(precision):
    fn = _kernels.get(("gemm", precision))
    if fn is None:
        def fn(C, L, R):
            import jax.numpy as jnp
            # potrf's rule: a large tile streams its own operand blocks
            form = selected[("GEMM", C.shape[0])] = \
                update_form(C, L, R, precision)
            if form == PALLAS:
                return pallas_update(C, L, R, nt=False)
            acc = jnp.matmul(L, R, precision=precision,
                             preferred_element_type=jnp.float32)
            return (C.astype(jnp.float32) - acc).astype(C.dtype)
        _kernels[("gemm", precision)] = fn
    return fn


# -- the host bodies: the fall-back incarnation of every class

def _np_lu(T):
    """Unpivoted LU of a float32 array, packed L\\U."""
    F = np.array(T, dtype=np.float32)
    for j in range(F.shape[0] - 1):
        F[j + 1:, j] /= F[j, j]
        F[j + 1:, j + 1:] -= np.outer(F[j + 1:, j], F[j, j + 1:])
    return F


def _cpu_getrf(T, WL, WU):
    import scipy.linalg as sl
    F = _np_lu(T)
    eye = np.eye(F.shape[0], dtype=np.float32)
    Li = sl.solve_triangular(np.tril(F, -1) + eye, eye, lower=True,
                             unit_diagonal=True)
    G = sl.solve_triangular(np.triu(F).T, eye, lower=True)
    return {"T": F.astype(np.asarray(T).dtype), "WL": Li, "WU": G}


def _cpu_getrf_last(T):
    return _np_lu(T).astype(np.asarray(T).dtype)


def _cpu_trsmr(W, C):
    out = np.asarray(W, dtype=np.float32) @ np.asarray(C, dtype=np.float32)
    return out.astype(np.asarray(C).dtype)


def _cpu_gemm(C, L, R):
    acc = np.asarray(L, dtype=np.float32) @ np.asarray(R, dtype=np.float32)
    return (np.asarray(C, dtype=np.float32) - acc).astype(np.asarray(C).dtype)


def _priority(cls: str, NT: int, k: int, m: int = 0, n: int = 0) -> int:
    """The critical path first, with a lookahead of one step: at step
    k, GETRF(k) > its row panel > its column panel > GEMM(k+1, k+1, k)
    > GETRF(k+1) > the next row panel's GEMMs (tile row k+1) = TRSMR(k+1)
    > the next column panel's (tile column k+1) = TRSMC(k+1) > the rest
    of step k's GEMMs; an earlier step over a later one.  The row family
    goes ahead of the column family at every step: its wave carries the
    held GETRF, and column tasks ready before it wait for its launch."""
    base = 5 * (NT - k)
    rank = {"GETRF": 5, "GETRFL": 5, "TRSMR": 4, "TRSMC": 3}.get(cls)
    if rank is not None:
        return base + rank
    if m == k + 1:
        return base + 1 if n == k + 1 else base - 1
    return base - 2 if n == k + 1 else base - 3


def getrf_nopiv_taskpool(A: TiledMatrix, device: str = "tpu",
                         precision: Optional[str] = None
                         ) -> ParameterizedTaskpool:
    """Factor A in place without pivoting: A = L @ U, L unit lower
    triangular in A's strict lower part, U in its upper part."""
    if A.mt != A.nt or A.mb != A.nb:
        raise ValueError("getrf_nopiv needs a square grid of square tiles")
    if A.lm % A.mb or A.ln % A.nb:
        raise ValueError("getrf_nopiv tiles must divide the matrix evenly")
    NT, mb = A.mt, A.mb
    use_device = device in ("tpu", "xla", "gpu")

    def add_bodies(tb, kernel, cpu_fn):
        if use_device:
            tb.body(kernel, device=device)
        tb.body(cpu_fn)

    def after(m, n, k, NT=NT):
        """The consumer of GEMM(m, n, k)'s tile at step k + 1."""
        if m == k + 1 and n == k + 1:
            return ("GETRF", dict(k=k + 1)) if k + 1 < NT - 1 \
                else ("GETRFL", dict())
        if m == k + 1:
            return ("TRSMR", dict(k=k + 1, n=n))
        if n == k + 1:
            return ("TRSMC", dict(m=m, k=k + 1))
        return ("GEMM", dict(m=m, n=n, k=k + 1))

    p = PTG("getrf_nopiv", NT=NT)
    # the panel inverses are always f32, even when tiles store bf16
    p.arena("w", (mb, mb), dtype=np.float32)

    tb = p.task("GETRF", k=Range(0, NT - 2)) \
        .affinity(lambda k, A=A: A(k, k)) \
        .priority(lambda k, NT=NT: _priority("GETRF", NT, k)) \
        .flow("T", "RW",
              IN(DATA(lambda k, A=A: A(k, k)), when=lambda k: k == 0),
              IN(TASK("GEMM", "C", lambda k: dict(m=k, n=k, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(DATA(lambda k, A=A: A(k, k)))) \
        .flow("WL", "RW",
              IN(NEW("w")),
              OUT(TASK("TRSMR", "W",
                       lambda k, NT=NT: [dict(k=k, n=n)
                                         for n in range(k + 1, NT)]))) \
        .flow("WU", "RW",
              IN(NEW("w")),
              OUT(TASK("TRSMC", "W",
                       lambda k, NT=NT: [dict(m=m, k=k)
                                         for m in range(k + 1, NT)])))
    add_bodies(tb, _k_getrf(precision), _cpu_getrf)

    tb = p.task("GETRFL") \
        .affinity(lambda A=A, NT=NT: A(NT - 1, NT - 1)) \
        .priority(lambda NT=NT: _priority("GETRFL", NT, NT - 1)) \
        .flow("T", "RW",
              IN(DATA(lambda A=A, NT=NT: A(NT - 1, NT - 1)),
                 when=lambda NT=NT: NT == 1),
              IN(TASK("GEMM", "C",
                      lambda NT=NT: dict(m=NT - 1, n=NT - 1, k=NT - 2)),
                 when=lambda NT=NT: NT > 1),
              OUT(DATA(lambda A=A, NT=NT: A(NT - 1, NT - 1))))
    add_bodies(tb, _k_getrf_last(precision), _cpu_getrf_last)

    tb = p.task("TRSMR", k=Range(0, NT - 2),
                n=Range(lambda k: k + 1, NT - 1)) \
        .affinity(lambda k, n, A=A: A(k, n)) \
        .priority(lambda k, NT=NT: _priority("TRSMR", NT, k)) \
        .flow("W", "READ", IN(TASK("GETRF", "WL", lambda k: dict(k=k)))) \
        .flow("C", "RW",
              IN(DATA(lambda k, n, A=A: A(k, n)), when=lambda k: k == 0),
              IN(TASK("GEMM", "C", lambda k, n: dict(m=k, n=n, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("GEMM", "R",
                       lambda k, n, NT=NT: [dict(m=m, n=n, k=k)
                                            for m in range(k + 1, NT)])),
              OUT(DATA(lambda k, n, A=A: A(k, n))))
    add_bodies(tb, _k_trsmr(precision), _cpu_trsmr)

    tb = p.task("TRSMC", k=Range(0, NT - 2),
                m=Range(lambda k: k + 1, NT - 1)) \
        .affinity(lambda m, k, A=A: A(m, k)) \
        .priority(lambda k, NT=NT: _priority("TRSMC", NT, k)) \
        .flow("W", "READ", IN(TASK("GETRF", "WU", lambda k: dict(k=k)))) \
        .flow("C", "RW",
              IN(DATA(lambda m, k, A=A: A(m, k)), when=lambda k: k == 0),
              IN(TASK("GEMM", "C", lambda m, k: dict(m=m, n=k, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("GEMM", "L",
                       lambda m, k, NT=NT: [dict(m=m, n=n, k=k)
                                            for n in range(k + 1, NT)])),
              OUT(DATA(lambda m, k, A=A: A(m, k))))
    # L[m,k] = A[m,k] U^-1 = A[m,k] @ WU^T: potrf's TRSM, kernel and body
    add_bodies(tb, _k_trsm(precision), _cpu_trsm)

    tb = p.task("GEMM", k=Range(0, NT - 2),
                m=Range(lambda k: k + 1, NT - 1),
                n=Range(lambda k: k + 1, NT - 1)) \
        .affinity(lambda m, n, A=A: A(m, n)) \
        .priority(lambda k, m, n, NT=NT: _priority("GEMM", NT, k, m, n)) \
        .flow("C", "RW",
              IN(DATA(lambda m, n, A=A: A(m, n)), when=lambda k: k == 0),
              IN(TASK("GEMM", "C", lambda m, n, k: dict(m=m, n=n, k=k - 1)),
                 when=lambda k: k > 0),
              *[OUT(TASK(cls, flow, lambda m, n, k, cls=cls:
                         after(m, n, k)[1]),
                    when=lambda m, n, k, cls=cls: after(m, n, k)[0] == cls)
                for cls, flow in (("GETRF", "T"), ("GETRFL", "T"),
                                  ("TRSMR", "C"), ("TRSMC", "C"),
                                  ("GEMM", "C"))]) \
        .flow("L", "READ", IN(TASK("TRSMC", "C",
                                   lambda m, k: dict(m=m, k=k)))) \
        .flow("R", "READ", IN(TASK("TRSMR", "C",
                                   lambda n, k: dict(k=k, n=n))))
    add_bodies(tb, _k_gemm(precision), _cpu_gemm)

    tp = p.build()
    for name, tc in tp.task_classes.items():
        # executed-flop weights for device load balancing
        tc.properties["flops"] = getrf_executed_flops(name, mb)
    # cross-panel fused dispatch (devices/xla.py chain fusion): GETRF(k)
    # is held and traced into the launch of its row panel (the column
    # panel waits for it and reads the resolved inverses); both panels
    # co-locate with the diagonal tile where the context drives several
    # chips
    tp.task_classes["GETRF"].properties["fuse_chain"] = ("WL", "TRSMR")
    for cls in ("TRSMR", "TRSMC"):
        tp.task_classes[cls].properties["coaffinity"] = \
            lambda loc, A=A: A(loc["k"], loc["k"])
    tp.recovery_collections = [A]
    return tp
