"""Tiled Cholesky factorization (dpotrf, lower): the north-star driver.

The DPLASMA-style dpotrf_L dataflow (reference: BASELINE.md/BASELINE.json
name DPLASMA tiled Cholesky as the headline target; the JDF structure
follows the classic four-kernel tiled algorithm the reference's PTG model
was built for — README.rst:22-27 "compact problem-size-independent
representation"):

    POTRF(k)    : L[k,k]  = chol(A[k,k]);  W[k] = L[k,k]^-1
    TRSM(m,k)   : A[m,k]  = A[m,k] @ W[k]^T                 (m > k)
    SYRK(k,m)   : A[m,m] -= A[m,k] @ A[m,k]^T               (k < m)
    GEMM(m,n,k) : A[m,n] -= A[m,k] @ A[n,k]^T               (m > n > k)

Every flow is task-to-task except the first touch of each tile, so the
same taskpool runs single-chip or distributed (the W panel broadcasts down
its block column through the comm layer's bcast trees).

TPU-first design of the solve step: XLA's ``triangular_solve`` runs an
order of magnitude below matmul peak on TPU (it serializes block
back-substitution), so POTRF additionally emits the tile inverse W =
L^-1 — computed by recursive block inversion whose leaves use the Newton
iteration X <- X(2I - LX).  For triangular L with X0 = diag(L)^-1 the
residual I - LX0 is strictly lower triangular, i.e. NILPOTENT, and the
iteration SQUARES it, so ceil(log2(n)) iterations reach the exact
inverse — everything is matmuls on the MXU.  Each TRSM then becomes
matmuls A[m,k] @ W^T at full systolic-array rate instead of a
triangular solve.  The extra mb^3/3 inverse flops per panel are ~1% of
the factorization and buy back a >4x faster panel wave (measured on
v5e: jsl trsm ~18 TF/s vs matmul ~150 TF/s).

The panel kernels use the triangle (``tri_blocks``): with the tile cut
into b x b blocks, TRSM leaves out the products with the zero blocks
above W's diagonal and SYRK the blocks above the tile's own, which
nobody reads — POTRF factors the lower triangle alone, as DPLASMA's
dpotrf_L does.  Both then execute (b+1)/(2b) of the full product's
flop; ``selected`` says which b every traced tile order got.

Two front ends, one set of kernels: ``potrf_taskpool`` declares the
dataflow as a PTG, ``potrf_dtd_taskpool`` inserts the same tasks one by
one (DPLASMA's ``testing_dpotrf_dtd``) and lets the runtime discover
it; both run the same device programs under the same names.

The priority schedule drives the critical path (POTRF > TRSM > SYRK >
GEMM at equal k) exactly like DPLASMA's priority hints, and same-class
waves (the TRSM panel, the SYRK/GEMM trailing updates) are fused into
single XLA launches by the device layer's wavefront launch fusion
(devices/xla.py) so the runtime amortizes per-launch latency.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np

from parsec_tpu.core.taskpool import ParameterizedTaskpool
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.dsl.ptg.api import DATA, IN, NEW, OUT, PTG, Range, TASK

_kernels = {}

#: recursive-inversion leaf: below this order the Newton iteration runs
#: directly (log2(leaf) matmuls of leaf x leaf — MXU noise)
_INV_LEAF = 512

#: the number of blocks b an edge of the tile was cut into for every
#: traced (class, tile order), 1 = the full product: what says the
#: block-triangular form engaged (apps/pallas_kernels.py keeps such a
#: map on its factories)
selected = {}

#: blocks an edge, and the smallest block edge at which the blocked form
#: of each class ran faster a task than the full product on the v5e
#: (PERF.md §6, PR 28: SYRK at mb = 2048 and 6144 alike; TRSM at 6144,
#: while at 2048 its 8-wide wave ran no faster at any b)
_TRI_BLOCKS = 8
_TRI_EDGE_MIN = {"SYRK": 256, "TRSM": 768}


def tri_blocks(cls: str, mb: int) -> int:
    """Blocks an edge of an mb x mb tile is cut into by the SYRK or TRSM
    kernel: ``_TRI_BLOCKS`` where they divide mb into edges of the
    class's ``_TRI_EDGE_MIN`` or more, else 1 (the full product)."""
    edge, rest = divmod(mb, _TRI_BLOCKS)
    return _TRI_BLOCKS if not rest and edge >= _TRI_EDGE_MIN[cls] else 1


def potrf_executed_flops(cls: str, mb: int) -> float:
    """Flop one task of class ``cls`` executes on an mb x mb tile (the
    device load-balancing weights; benchmark/work.py counts the USEFUL
    flop): SYRK and TRSM run (b+1)/(2b) of the full 2 mb^3 product."""
    if cls in _TRI_EDGE_MIN:
        b = tri_blocks(cls, mb)
        return mb ** 3 * (b + 1.0) / b
    return {"POTRF": mb ** 3, "POTRFL": mb ** 3 / 3.0,
            "GEMM": 2.0 * mb ** 3}[cls]


def tri_inv(L, precision=None):
    """Lower-triangular inverse as pure matmuls (jax-traceable).

    Recursive 2x2 block inversion
        [[L11, 0], [L21, L22]]^-1 =
        [[X11, 0], [-X22 @ L21 @ X11, X22]]
    with Newton--Schulz leaves: X <- X(2I - LX) starting from
    X0 = diag(L)^-1 converges EXACTLY in ceil(log2(n)) steps because the
    initial residual I - LX0 is strictly triangular (nilpotent) and each
    step squares it.  No triangular solve anywhere: everything lowers to
    the systolic array.
    """
    import jax.numpy as jnp
    n = L.shape[0]
    if n <= _INV_LEAF:
        X = jnp.diag(1.0 / jnp.diag(L))
        I = jnp.eye(n, dtype=L.dtype)
        for _ in range(int(math.ceil(math.log2(max(n, 2)))) + 1):
            X = jnp.matmul(X, 2.0 * I - jnp.matmul(L, X,
                                                   precision=precision),
                           precision=precision)
        return X
    h = n // 2
    X11 = tri_inv(L[:h, :h], precision)
    X22 = tri_inv(L[h:, h:], precision)
    X21 = -jnp.matmul(X22, jnp.matmul(L[h:, :h], X11, precision=precision),
                      precision=precision)
    top = jnp.concatenate([X11, jnp.zeros((h, n - h), L.dtype)], axis=1)
    return jnp.concatenate([top, jnp.concatenate([X21, X22], axis=1)],
                           axis=0)


def _k_potrf(precision):
    fn = _kernels.get(("potrf", precision))
    if fn is None:
        def fn(T, W):
            import jax.numpy as jnp
            # factor in f32 even under bf16 tile storage (the mp mode):
            # the inverse W always stays f32 — it multiplies every panel
            # the lower triangle alone (dpotrf_L): SYRK leaves the blocks
            # above the diagonal behind
            L = jnp.linalg.cholesky(T.astype(jnp.float32),
                                    symmetrize_input=False)
            return {"T": L.astype(T.dtype), "W": tri_inv(L, precision)}
        _kernels[("potrf", precision)] = fn
    return fn


def _k_potrf_last(precision):
    # the last diagonal tile has no TRSM consumers: plain cholesky, no
    # inverse flops and no W scratch on the critical path's final task
    fn = _kernels.get(("potrf_last", precision))
    if fn is None:
        def fn(T):
            import jax.numpy as jnp
            return jnp.linalg.cholesky(
                T.astype(jnp.float32),
                symmetrize_input=False).astype(T.dtype)
        _kernels[("potrf_last", precision)] = fn
    return fn


def _k_trsm(precision):
    # Kernels are dtype-FOLLOWING: products always accumulate in f32
    # (preferred_element_type), the Cholesky itself runs in f32 (upcast
    # in _k_potrf), and results land back in the tile's STORAGE dtype —
    # so the same code path serves full-f32 tiles and the bf16-storage
    # mixed-precision mode (HPL-AI-style: all tiles stored bf16, halving
    # HBM footprint+traffic, results rounded to bf16 between steps; the
    # panel inverse W alone stays f32; benchmark/configs/ "storage").
    fn = _kernels.get(("trsm", precision))
    if fn is None:
        def fn(W, C):
            import jax.numpy as jnp
            from jax import lax
            # C <- C @ L^-T  ==  C @ W^T  (W = L^-1 from POTRF).  W is
            # lower triangular, so column block j needs the first j+1
            # block columns of C alone: the products left out are with
            # tri_inv's exact zeros.  Last block first, each read from
            # the tile as written so far: block j reads nothing a later
            # block has written, so the donated tile is updated in place.
            mb = C.shape[1]
            b = selected[("TRSM", mb)] = tri_blocks("TRSM", mb)
            s = mb // b
            out = C
            for j in reversed(range(b)):
                lo, hi = j * s, (j + 1) * s
                acc = jnp.matmul(out[:, :hi], W[lo:hi, :hi].T,
                                 precision=precision,
                                 preferred_element_type=jnp.float32)
                out = lax.dynamic_update_slice(out, acc.astype(C.dtype),
                                               (0, lo))
            return out
        _kernels[("trsm", precision)] = fn
    return fn


def _k_syrk(precision):
    fn = _kernels.get(("syrk", precision))
    if fn is None:
        def fn(T, R):
            import jax.numpy as jnp
            from jax import lax
            # block row i of the update, up to the block diagonal; the
            # blocks above it are not computed, keep what they held and
            # are never read (POTRF reads the lower triangle alone)
            mb = T.shape[0]
            b = selected[("SYRK", mb)] = tri_blocks("SYRK", mb)
            s = mb // b
            out = T
            for i in range(b):
                lo, hi = i * s, (i + 1) * s
                acc = jnp.matmul(R[lo:hi], R[:hi].T, precision=precision,
                                 preferred_element_type=jnp.float32)
                blk = out[lo:hi, :hi].astype(jnp.float32) - acc
                out = lax.dynamic_update_slice(out, blk.astype(T.dtype),
                                               (lo, 0))
            return out
        _kernels[("syrk", precision)] = fn
    return fn


def _k_gemm(precision):
    fn = _kernels.get(("gemm", precision))
    if fn is None:
        def fn(C, L, R):
            import jax.numpy as jnp
            acc = jnp.matmul(L, R.T, precision=precision,
                             preferred_element_type=jnp.float32)
            return (C.astype(jnp.float32) - acc).astype(C.dtype)
        _kernels[("gemm", precision)] = fn
    return fn


# -- the host bodies: the fall-back incarnation of every class, whatever
# -- front end inserts it

def _cpu_potrf(T, W):
    import scipy.linalg as sl
    L = np.linalg.cholesky(np.asarray(T, dtype=np.float32))
    Winv = sl.solve_triangular(L, np.eye(L.shape[0], dtype=L.dtype),
                               lower=True)
    return {"T": L.astype(np.asarray(T).dtype), "W": Winv}


def _cpu_potrf_last(T):
    return np.linalg.cholesky(
        np.asarray(T, dtype=np.float32)).astype(np.asarray(T).dtype)


def _cpu_trsm(W, C):
    out = np.asarray(C, dtype=np.float32) @ \
        np.asarray(W, dtype=np.float32).T
    return out.astype(np.asarray(C).dtype)


def _cpu_syrk(T, R):
    r = np.asarray(R, dtype=np.float32)
    return (np.asarray(T, dtype=np.float32) -
            r @ r.T).astype(np.asarray(T).dtype)


def _cpu_gemm(C, L, R):
    acc = np.asarray(L, dtype=np.float32) @ \
        np.asarray(R, dtype=np.float32).T
    return (np.asarray(C, dtype=np.float32) -
            acc).astype(np.asarray(C).dtype)


def _check_tiling(A: TiledMatrix) -> None:
    if A.mt != A.nt:
        raise ValueError("potrf needs a square tile grid")
    if A.lm % A.mb or A.ln % A.nb:
        raise ValueError("potrf tiles must divide the matrix evenly")


_RANK = {"POTRF": 3, "TRSM": 2, "SYRK": 1, "GEMM": 0}


def _priority(cls: str, NT: int, k: int) -> int:
    """The critical path first: POTRF > TRSM > SYRK > GEMM at equal k,
    an earlier panel over a later one (DPLASMA's priority hints)."""
    return 6 if cls == "POTRFL" else 3 * NT - 3 * k + _RANK[cls]


def potrf_taskpool(A: TiledMatrix, device: str = "tpu",
                   precision: Optional[str] = None) -> ParameterizedTaskpool:
    """Factor the lower triangle of A in place: A = L @ L^T."""
    _check_tiling(A)
    NT = A.mt
    mb = A.mb
    use_device = device in ("tpu", "xla", "gpu")

    def add_bodies(tb, kernel, cpu_fn):
        if use_device:
            tb.body(kernel, device=device)
        tb.body(cpu_fn)
        return tb

    p = PTG("potrf", NT=NT)
    # the panel inverse is always f32, even when tiles store bf16 (mp)
    p.arena("w", (mb, mb), dtype=np.float32)

    tb = p.task("POTRF", k=Range(0, NT - 2)) \
        .affinity(lambda k, A=A: A(k, k)) \
        .priority(lambda k, NT=NT: _priority("POTRF", NT, k)) \
        .flow("T", "RW",
              IN(DATA(lambda k, A=A: A(k, k)), when=lambda k: k == 0),
              IN(TASK("SYRK", "T", lambda k: dict(k=k - 1, m=k)),
                 when=lambda k: k > 0),
              OUT(DATA(lambda k, A=A: A(k, k)))) \
        .flow("W", "RW",
              IN(NEW("w")),
              OUT(TASK("TRSM", "W",
                       lambda k, NT=NT: [dict(m=m, k=k)
                                         for m in range(k + 1, NT)])))

    add_bodies(tb, _k_potrf(precision), _cpu_potrf)

    # the final diagonal tile: no panel below it, so no inverse is needed
    tb = p.task("POTRFL") \
        .affinity(lambda A=A, NT=NT: A(NT - 1, NT - 1)) \
        .priority(lambda NT=NT: _priority("POTRFL", NT, NT - 1)) \
        .flow("T", "RW",
              IN(DATA(lambda A=A, NT=NT: A(NT - 1, NT - 1)),
                 when=lambda NT=NT: NT == 1),
              IN(TASK("SYRK", "T", lambda NT=NT: dict(k=NT - 2, m=NT - 1)),
                 when=lambda NT=NT: NT > 1),
              OUT(DATA(lambda A=A, NT=NT: A(NT - 1, NT - 1))))
    add_bodies(tb, _k_potrf_last(precision), _cpu_potrf_last)

    tb = p.task("TRSM", k=Range(0, NT - 2),
                m=Range(lambda k: k + 1, NT - 1)) \
        .affinity(lambda m, k, A=A: A(m, k)) \
        .priority(lambda k, NT=NT: _priority("TRSM", NT, k)) \
        .flow("W", "READ", IN(TASK("POTRF", "W", lambda k: dict(k=k)))) \
        .flow("C", "RW",
              IN(DATA(lambda m, k, A=A: A(m, k)), when=lambda k: k == 0),
              IN(TASK("GEMM", "C", lambda m, k: dict(m=m, n=k, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("SYRK", "R", lambda m, k: dict(k=k, m=m))),
              OUT(TASK("GEMM", "L",
                       lambda m, k: [dict(m=m, n=n, k=k)
                                     for n in range(k + 1, m)]),
                  when=lambda m, k: m > k + 1),
              OUT(TASK("GEMM", "R",
                       lambda m, k, NT=NT: [dict(m=m2, n=m, k=k)
                                            for m2 in range(m + 1, NT)]),
                  when=lambda m, NT=NT: m < NT - 1),
              OUT(DATA(lambda m, k, A=A: A(m, k))))

    add_bodies(tb, _k_trsm(precision), _cpu_trsm)

    tb = p.task("SYRK", m=Range(1, NT - 1), k=Range(0, lambda m: m - 1)) \
        .affinity(lambda m, A=A: A(m, m)) \
        .priority(lambda k, NT=NT: _priority("SYRK", NT, k)) \
        .flow("T", "RW",
              IN(DATA(lambda m, A=A: A(m, m)), when=lambda k: k == 0),
              IN(TASK("SYRK", "T", lambda m, k: dict(m=m, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("POTRF", "T", lambda m: dict(k=m)),
                  when=lambda m, k, NT=NT: k == m - 1 and m < NT - 1),
              OUT(TASK("POTRFL", "T", lambda: dict()),
                  when=lambda m, k, NT=NT: k == m - 1 and m == NT - 1),
              OUT(TASK("SYRK", "T", lambda m, k: dict(m=m, k=k + 1)),
                  when=lambda m, k: k < m - 1)) \
        .flow("R", "READ", IN(TASK("TRSM", "C", lambda m, k: dict(m=m,
                                                                  k=k))))
    add_bodies(tb, _k_syrk(precision), _cpu_syrk)

    tb = p.task("GEMM", n=Range(1, NT - 2),
                m=Range(lambda n: n + 1, NT - 1),
                k=Range(0, lambda n: n - 1)) \
        .affinity(lambda m, n, A=A: A(m, n)) \
        .priority(lambda k, NT=NT: _priority("GEMM", NT, k)) \
        .flow("C", "RW",
              IN(DATA(lambda m, n, A=A: A(m, n)), when=lambda k: k == 0),
              IN(TASK("GEMM", "C", lambda m, n, k: dict(m=m, n=n, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("TRSM", "C", lambda m, n: dict(m=m, k=n)),
                  when=lambda n, k: k == n - 1),
              OUT(TASK("GEMM", "C", lambda m, n, k: dict(m=m, n=n, k=k + 1)),
                  when=lambda n, k: k < n - 1)) \
        .flow("L", "READ", IN(TASK("TRSM", "C", lambda m, k: dict(m=m,
                                                                  k=k)))) \
        .flow("R", "READ", IN(TASK("TRSM", "C", lambda n, k: dict(m=n,
                                                                  k=k))))
    add_bodies(tb, _k_gemm(precision), _cpu_gemm)

    tp = p.build()
    for name, tc in tp.task_classes.items():
        # executed-flop weights for device load balancing
        tc.properties["flops"] = potrf_executed_flops(name, mb)
    # cross-panel fused dispatch (devices/xla.py chain fusion): the
    # POTRF(k) -> TRSM(*,k) panel is the dispatch-latency-bound spine of
    # the DAG (each TRSM's only missing input is W) — the device layer
    # holds POTRF(k) and traces it INTO the TRSM wave's launch, so the
    # panel chain costs ONE dispatch round trip instead of two plus the
    # Python scheduling latency between them.  TRSM co-locates on the
    # diagonal tile's device so the whole panel is one wave there.
    # A/B knob: PARSEC_MCA_DEVICE_FUSE_PANEL=0 restores the per-kernel
    # panel path.
    tp.task_classes["POTRF"].properties["fuse_chain"] = ("W", "TRSM")
    tp.task_classes["TRSM"].properties["coaffinity"] = \
        lambda loc, A=A: A(loc["k"], loc["k"])
    # recovery spec (core/recovery.py): the whole dataflow reads and
    # writes A, so a peer death can re-map A's lost partition onto the
    # survivors and re-enumerate this pool from the restored tiles —
    # give A an init_fn (A.set_init) so ADOPTED tiles have a
    # re-runnable source, and the pool recovers instead of failing
    tp.recovery_collections = [A]
    return tp


#: (device, precision, mb) -> the five DTD task classes, made once a process
_dtd_classes = {}


def _potrf_dtd_classes(device: str, precision: Optional[str], mb: int):
    """The PTG's classes declared for insertion: the same kernels, host
    bodies, executed-flop weights and POTRF -> TRSM chain, under the
    same names, so that both front ends run the same device programs."""
    from parsec_tpu.dsl.dtd import INOUT, INPUT, OUTPUT, create_task_class
    key = (device, precision, mb)
    classes = _dtd_classes.get(key)
    if classes is None:
        classes = {}
        for name, args, modes, kernel, cpu_fn in (
                ("POTRF", ("T", "W"), (INOUT, OUTPUT),
                 _k_potrf(precision), _cpu_potrf),
                ("POTRFL", ("T",), (INOUT,),
                 _k_potrf_last(precision), _cpu_potrf_last),
                ("TRSM", ("W", "C"), (INPUT, INOUT),
                 _k_trsm(precision), _cpu_trsm),
                ("SYRK", ("T", "R"), (INOUT, INPUT),
                 _k_syrk(precision), _cpu_syrk),
                ("GEMM", ("C", "L", "R"), (INOUT, INPUT, INPUT),
                 _k_gemm(precision), _cpu_gemm)):
            cls = create_task_class(
                name, args, modes,
                properties={"flops": potrf_executed_flops(name, mb)})
            if device in ("tpu", "xla", "gpu"):
                cls.add_chore(device, kernel)
            classes[name] = cls.add_chore("cpu", cpu_fn)
        classes["POTRF"].properties["fuse_chain"] = ("W", "TRSM")
        _dtd_classes[key] = classes
    return classes


def potrf_dtd_taskpool(A: TiledMatrix, device: str = "tpu",
                       precision: Optional[str] = None):
    """The same factorization written as DPLASMA's ``testing_dpotrf_dtd``
    writes it: a DTD taskpool whose inserter, once the pool is attached
    and started, inserts the right-looking tile Cholesky task by task —
    POTRF(A[k][k] INOUT), TRSM(A[k][k]'s inverse INPUT, A[m][k] INOUT),
    SYRK(A[m][k] INPUT, A[m][m] INOUT), GEMM(A[m][k] INPUT, A[n][k]
    INPUT, A[m][n] INOUT) — then flushes.  The runtime finds the DAG
    from the order of the inserts and each tile's access mode; every
    tile sees its updates in insert order, which is the PTG's k order."""
    from parsec_tpu.dsl.dtd import DTDTaskpool
    _check_tiling(A)
    NT, mb = A.mt, A.mb
    cls = _potrf_dtd_classes(device, precision, mb)

    def inserter(tp):
        insert = tp.insert_task
        T = {(m, n): tp.tile_of(A, m, n)
             for m in range(NT) for n in range(m + 1)}
        # every tile is inserted under the mode its class declares for it
        (potrf, mp), (trsm, mt), (syrk, ms), (gemm, mg) = (
            (cls[c], cls[c].modes) for c in ("POTRF", "TRSM", "SYRK", "GEMM"))
        for k in range(NT - 1):
            # the panel inverse is always f32, even when tiles store bf16
            W = tp.tile_arena((mb, mb), np.float32)
            insert(potrf, (T[k, k], mp[0]), (W, mp[1]),
                   priority=_priority("POTRF", NT, k))
            pr = _priority("TRSM", NT, k)
            for m in range(k + 1, NT):
                insert(trsm, (W, mt[0]), (T[m, k], mt[1]), priority=pr)
            ps, pg = _priority("SYRK", NT, k), _priority("GEMM", NT, k)
            for m in range(k + 1, NT):
                insert(syrk, (T[m, m], ms[0]), (T[m, k], ms[1]), priority=ps)
                for n in range(k + 1, m):
                    insert(gemm, (T[m, n], mg[0]), (T[m, k], mg[1]),
                           (T[n, k], mg[2]), priority=pg)
        # the last diagonal tile has no panel below it: no inverse
        last = cls["POTRFL"]
        insert(last, (T[NT - 1, NT - 1], last.modes[0]),
               priority=_priority("POTRFL", NT, NT - 1))
        tp.data_flush_all()

    return DTDTaskpool("potrf_dtd", inserter=inserter)


def potrf_flops(n: int) -> float:
    """Useful FLOPs of an n x n Cholesky (n^3/3)."""
    return n ** 3 / 3.0
