"""Tile LU without pivoting (apps/getrf.py) on the device path, held to
the benchmark's plain reference (benchmark/reference/getrf.py, which
imports nothing of the program), and the chain head that feeds two
classes (devices/xla.py: the launch of the class its ``fuse_chain``
declares carries the head, a reader of the other class waits for it,
and ``heads_forced`` counts the heads that went out alone)."""

import inspect
import json
import os
import re
import threading
import time

import numpy as np
import pytest

from benchmark.reference import getrf as reference
from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.dsl.ptg.api import DATA, IN, NEW, OUT, PTG, Range, TASK
from parsec_tpu.utils.mca import params

N, MB = 768, 128
NT = N // MB
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _limit() -> float:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dplasma_getrf_nopiv_bf16.json")) as f:
        return json.load(f)["limits"]["offdiag_resid"]


def _operand(n, seed, dtype=np.float32):
    """The cell's kind of operand: entries uniform with mean 0 and
    variance 1, (i, j) and (j, i) independent, plus 4 sqrt(n) on the
    diagonal; as stored in ``dtype``."""
    rng = np.random.default_rng(seed)
    a = (rng.random((n, n)) - 0.5) * 12.0 ** 0.5 + 4.0 * n ** 0.5 * np.eye(n)
    return a.astype(dtype)


def _factor(a, mb, device="tpu", programs=None):
    """One factorization of ``a`` (tiles stored in its dtype) through
    ``getrf_nopiv_taskpool`` on a context with ONE accelerator; returns
    the tiles it left, as float32 arrays, and the device's counters.
    ``programs`` collects the ``program`` of every ``mgr.dispatch``."""
    from parsec_tpu.apps.getrf import getrf_nopiv_taskpool
    n = a.shape[0]
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, dtype=a.dtype)
    for i, j in A.local_tiles():
        A.data_of(i, j).overwrite_host(
            a[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb].copy())
    params.set("device_max", 1)
    try:
        with Context(nb_cores=4) as ctx:
            if programs is not None:
                ctx._span_live = lambda: True
                ctx.pins_register("span_begin", lambda es, event, span: (
                    programs.append(span.args["program"])
                    if span.name == "mgr.dispatch" else None))
            ctx.add_taskpool(getrf_nopiv_taskpool(A, device=device))
            ctx.wait(timeout=300)
            accel = ctx.device_registry.accelerators
            stats = accel[0].stats.as_dict() if accel else {}
    finally:
        params.unset("device_max")
    tiles = {(i, j): np.asarray(A.data_of(i, j).pull_to_host().payload,
                                dtype=np.float32)
             for i, j in A.local_tiles()}
    return tiles, stats


def _check(tiles, a, mb, seed=5):
    nt = a.shape[0] // mb
    op = {(i, j): a[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb]
          .astype(np.float32) for i in range(nt) for j in range(nt)}
    return reference.factor_check(nt, mb, lambda i, j: tiles[(i, j)],
                                  lambda i, j: op[(i, j)], seed)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [96, 640, 1280])
def test_lu_kernel_gives_the_factor_and_both_inverses(n):
    """The recursion (halves at multiples of 128, leaves of 512 or less:
    1280 -> 640 + 640, 640 -> 256 + 384) against the plain blocked
    elimination of the reference; float32 throughout, so the two agree
    to float32 rounding of entries up to 4 sqrt(n)."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps.getrf import lu_inv
    a = _operand(n, n)
    with jax.default_matmul_precision("highest"):
        F, Li, G = (np.asarray(x) for x in jax.jit(lu_inv)(jnp.asarray(a)))
        ref = np.asarray(jax.jit(reference._lu_tile)(jnp.asarray(a)))
    assert np.abs(F - ref).max() <= 1e-5 * np.abs(ref).max()
    L, U = np.tril(F, -1) + np.eye(n), np.triu(F)
    assert np.abs(L @ U - a).max() <= 1e-5 * np.abs(a).max()
    assert np.abs(Li @ L - np.eye(n)).max() < 1e-5
    assert np.abs(G @ U.T - np.eye(n)).max() < 1e-5
    assert np.abs(np.triu(Li, 1)).max() == 0.0 == np.abs(np.triu(G, 1)).max()


def test_a_leaf_that_partial_pivoting_would_reorder_is_eliminated_unpivoted():
    """A leaf whose multipliers exceed 1 (XLA's LU would exchange rows)
    takes the elimination: the factor is still L and U of A itself."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps.getrf import _lu_leaf, lu_inv
    n = 64
    rng = np.random.default_rng(8)
    L0 = np.tril(rng.uniform(1.5, 2.5, (n, n)), -1) / n ** 0.5 + np.eye(n)
    L0[1, 0] = 3.0                      # row 1 would be the first pivot
    U0 = np.triu(rng.standard_normal((n, n))) + 8.0 * np.eye(n)
    a = (L0 @ U0).astype(np.float32)
    want = (np.tril(L0, -1) + U0).astype(np.float32)
    lu, _piv, perm = jax.lax.linalg.lu(jnp.asarray(a))
    assert not np.array_equal(np.asarray(perm), np.arange(n))
    with jax.default_matmul_precision("highest"):
        leaf = np.asarray(jax.jit(_lu_leaf)(jnp.asarray(a)))
        F, Li, _G = (np.asarray(x) for x in jax.jit(lu_inv)(jnp.asarray(a)))
    for got in (leaf, F):
        assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    assert np.abs(Li @ (np.tril(F, -1) + np.eye(n)) - np.eye(n)).max() < 1e-4


def test_getrfl_factors_without_inverses():
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps.getrf import _k_getrf, _k_getrf_last
    a = jnp.asarray(_operand(384, 3))
    full = jax.jit(_k_getrf(None))(a, a, a)
    last = jax.jit(_k_getrf_last(None))(a)
    np.testing.assert_array_equal(np.asarray(last), np.asarray(full["T"]))


# ---------------------------------------------------------------------------
# the taskpool against the plain reference
# ---------------------------------------------------------------------------

def test_taskpool_agrees_with_plain_lu_tile_for_tile():
    """float32 storage on the XLA device path at n = 768, mb = 128.  The
    program solves by multiplying with GETRF's inverses and the plain
    reference by triangular solves against the stored diagonal tile, so
    the tiles differ by float32 rounding alone: a tile's entries reach
    4 sqrt(768) = 111 (U's diagonal) and its error a few units of the
    last place of that after six steps; 2e-6 of the largest entry of
    the whole factor is ten times what is seen, and a tile left one
    update behind is off by 2e-2 of it."""
    import jax.numpy as jnp
    a = _operand(N, 11)
    tiles, stats = _factor(a, MB)
    ref = reference.plain_lu(
        {(i, j): jnp.asarray(a[i * MB:(i + 1) * MB, j * MB:(j + 1) * MB])
         for i in range(NT) for j in range(NT)}, NT,
        reference.store_as(np.float32))
    scale = max(np.abs(np.asarray(t)).max() for t in ref.values())
    worst = max(np.abs(tiles[k] - np.asarray(ref[k])).max() for k in ref)
    assert worst <= 2e-6 * scale, worst / scale
    assert stats["executed_tasks"] + stats["held_tasks"] == \
        reference.tasks(NT) == NT * (NT + 1) * (2 * NT + 1) // 6 == 91
    assert _check(tiles, a, MB)["offdiag_resid"] < 1e-5


def test_bf16_storage_is_within_the_configurations_limit():
    from ml_dtypes import bfloat16
    a = _operand(N, 12, bfloat16)
    tiles, _stats = _factor(a, MB)
    r = _check(tiles, a, MB)
    # bfloat16 rounding of every stored tile: far above float32's, well
    # under the limit the configuration sets
    assert 1e-4 < r["offdiag_resid"] < _limit() / 3


def test_one_dropped_update_fails_the_limit(monkeypatch):
    """The host bodies, one GEMM update of the middle of the DAG left
    out: the tile it should have updated carries L_mk U_kn into every
    later step, and its block row reads over the limit."""
    from parsec_tpu.apps import getrf
    real = getrf._cpu_gemm

    def gemm(C, L, R, task):
        if (task.locals["m"], task.locals["n"], task.locals["k"]) == (3, 4, 1):
            return np.asarray(C)
        return real(C, L, R)
    monkeypatch.setattr(getrf, "_cpu_gemm", gemm)
    a = _operand(N, 13)
    tiles, _stats = _factor(a, MB, device="cpu")
    r = _check(tiles, a, MB)
    assert r["offdiag_resid"] > _limit() and r["offdiag_row"] == 3


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 9])
def test_fp8_control_fails_the_limit_and_the_configurations_passes(seed):
    from benchmark.apps import getrf as app
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dplasma_getrf_nopiv_bf16.json")) as f:
        cfg = json.load(f)
    traffic = {"n": N, "mb": MB}
    sound = app.control(cfg, traffic, seed, "config")["offdiag_resid"]
    lower = app.control(cfg, traffic, seed, "fp8")["offdiag_resid"]
    assert sound < cfg["limits"]["offdiag_resid"] < lower
    assert lower >= 10 * sound


def test_task_counts_and_host_bodies(monkeypatch):
    """Every class runs as often as the tile algorithm says, and the
    host bodies give the factor the device path gives."""
    from parsec_tpu.apps import getrf
    counts = {}
    for name in ("_cpu_getrf", "_cpu_getrf_last", "_cpu_trsmr", "_cpu_trsm",
                 "_cpu_gemm"):
        real = getattr(getrf, name)

        def counted(real=real, name=name, **kw):
            counts[name] = counts.get(name, 0) + 1
            return real(**kw)
        counted.__signature__ = inspect.signature(real)
        monkeypatch.setattr(getrf, name, counted)
    a = _operand(N, 14)
    host, _ = _factor(a, MB, device="cpu")
    monkeypatch.undo()
    dev, _ = _factor(a, MB)
    assert counts == {"_cpu_getrf": NT - 1, "_cpu_getrf_last": 1,
                      "_cpu_trsmr": NT * (NT - 1) // 2,
                      "_cpu_trsm": NT * (NT - 1) // 2,
                      "_cpu_gemm": (NT - 1) * NT * (2 * NT - 1) // 6}
    assert sum(counts.values()) == reference.tasks(NT)
    scale = max(np.abs(t).max() for t in dev.values())
    assert max(np.abs(host[k] - dev[k]).max() for k in dev) <= 2e-6 * scale


def test_priorities_put_the_lookahead_first():
    from parsec_tpu.apps.getrf import _priority as pr
    nt, k = 11, 3
    order = [pr("GETRF", nt, k), pr("TRSMR", nt, k), pr("TRSMC", nt, k),
             pr("GEMM", nt, k, k + 1, k + 1), pr("GETRF", nt, k + 1),
             pr("GEMM", nt, k, k + 1, k + 5), pr("GEMM", nt, k, k + 5, k + 1),
             pr("GEMM", nt, k, k + 2, k + 2),
             pr("GEMM", nt, k + 1, k + 2, k + 2), pr("GETRF", nt, k + 2)]
    assert all(a > b for a, b in zip(order, order[1:])), order
    # the next panels' GEMMs rank with the panels they feed
    assert pr("GEMM", nt, k, k + 1, k + 5) == pr("TRSMR", nt, k + 1)
    assert pr("GEMM", nt, k, k + 5, k + 1) == pr("TRSMC", nt, k + 1)
    assert pr("GETRFL", nt, nt - 1) == pr("GETRF", nt, nt - 1)


# ---------------------------------------------------------------------------
# chain fusion: a head that feeds two classes
# ---------------------------------------------------------------------------

def test_lu_chain_programs_are_the_pools_triples():
    """Every GETRF goes out inside the first wave of its row panel
    (none alone), the chain programs the pool asks for are (GETRF,
    TRSMR, width) triples, ``chain_programs`` counts the distinct ones
    dispatched, and a second pool builds none."""
    from parsec_tpu.apps import getrf
    getrf._kernels.clear()           # the chain cache is keyed by them
    a = _operand(N, 15)
    first, second = [], []
    _tiles, st1 = _factor(a, MB, programs=first)
    _tiles, st2 = _factor(a, MB, programs=second)
    chains = {p for p in first if p.startswith("jit_parsec_chain_")}
    assert chains and all(re.fullmatch(
        r"jit_parsec_chain_GETRF__TRSMR_x[1248]", p) for p in chains)
    assert st1["chain_programs"] == len(chains)
    assert st2["chain_programs"] == 0
    for st in (st1, st2):
        assert st["held_tasks"] == st["chained_launches"] == NT - 1
        assert st["heads_forced"] == 0
    assert {p for p in second if p.startswith("jit_parsec_chain_")} <= chains


def _two_families(fuse_chain, late, host_only=(), w=3):
    """H writes X (read by every FA) and Y (read by every FB); the
    class named ``late`` also waits for a slow host task, so the other
    family reaches the device first (None: both families are ready at
    H's release, FA queued first by its priority).  Classes in
    ``host_only`` have no device body.  Returns the sums FA and FB left,
    the device counters and the programs dispatched."""
    mb = 8
    M = TwoDimBlockCyclic(mb=mb, nb=mb, lm=3 * mb, ln=w * mb,
                          dtype=np.float32)
    for i, j in M.local_tiles():
        M.data_of(i, j).overwrite_host(np.full((mb, mb), 1.0 + i, np.float32))
    gate = threading.Event()

    def slow(C):
        gate.wait(1.0)
        return np.asarray(C)

    p = PTG("families", W=w)
    p.arena("w", (mb, mb), dtype=np.float32)
    p.task("H").affinity(lambda M=M: M(0, 0)) \
        .flow("T", "RW", IN(DATA(lambda M=M: M(0, 0))),
              OUT(DATA(lambda M=M: M(0, 0)))) \
        .flow("X", "RW", IN(NEW("w")),
              OUT(TASK("FA", "X", lambda W=w: [dict(n=n) for n in range(W)]))) \
        .flow("Y", "RW", IN(NEW("w")),
              OUT(TASK("FB", "Y", lambda W=w: [dict(n=n) for n in range(W)]))) \
        .body(lambda T, X, Y: {"T": T, "X": 2.0 * T, "Y": 3.0 * T},
              device="tpu")
    for cls, row, flow in (("FA", 1, "X"), ("FB", 2, "Y")):
        first = IN(DATA(lambda n, M=M, row=row: M(row, n)))
        if late is not None:
            p.task("S" + cls, n=Range(0, w - 1)) \
                .affinity(lambda n, M=M, row=row: M(row, n)) \
                .flow("C", "RW", first,
                      OUT(TASK(cls, "C", lambda n: dict(n=n)))) \
                .body(slow if cls == late else (lambda C: np.asarray(C)))
            first = IN(TASK("S" + cls, "C", lambda n: dict(n=n)))
        tb = p.task(cls, n=Range(0, w - 1)) \
            .affinity(lambda n, M=M, row=row: M(row, n)) \
            .priority(lambda row=row: 3 - row) \
            .flow(flow, "READ", IN(TASK("H", flow, lambda: dict()))) \
            .flow("C", "RW", first,
                  OUT(DATA(lambda n, M=M, row=row: M(row, n))))
        if flow == "X":
            kernel, host = (lambda X, C: C + X,
                            lambda X, C: np.asarray(C) + np.asarray(X))
        else:
            kernel, host = (lambda Y, C: C + Y,
                            lambda Y, C: np.asarray(C) + np.asarray(Y))
        if cls not in host_only:
            tb.body(kernel, device="tpu")
        tb.body(host)
    tp = p.build()
    tp.task_classes["H"].properties["fuse_chain"] = fuse_chain
    programs = []
    params.set("device_max", 1)
    params.set("device_fuse", 4)
    try:
        # a worker for every host task: the fast family's never queue
        # behind the slow one's
        with Context(nb_cores=2 * w + 2) as ctx:
            ctx._span_live = lambda: True
            ctx.pins_register("span_begin", lambda es, event, span: (
                programs.append(span.args["program"])
                if span.name == "mgr.dispatch" else None))
            ctx.add_taskpool(tp)
            ctx.wait(timeout=120)
            stats = ctx.device_registry.accelerators[0].stats.as_dict()
    finally:
        params.unset("device_max")
        params.unset("device_fuse")
        gate.set()
    out = M.to_array()
    return (out[mb:2 * mb].mean(), out[2 * mb:].mean()), stats, programs


ROW = ("X", "FA")


@pytest.mark.parametrize("late", ["FA", "FB", None])
def test_one_declared_pair_keeps_its_single_successor(late):
    """A (flow, class) pair, as potrf's, QR's and GETRF's: whichever
    family a slow host task lets reach the device first (``late`` the
    other; None: both ready at H's release, FA queued first), the head
    rides the wave of FA, the class it declares.  FB, ready before it,
    waits for that launch while the head is held and reads the resolved
    output through its own program.  No head goes out alone, and a run
    builds FA's chain program only."""
    sums, st, programs = _two_families(ROW, late)
    assert sums == (2.0 + 2.0, 3.0 + 3.0)      # C + 2 T, C + 3 T
    chains = [p for p in programs if p.startswith("jit_parsec_chain_")]
    assert len(chains) == 1 and chains[0].startswith("jit_parsec_chain_H__FA")
    assert st["held_tasks"] == st["chained_launches"] == 1
    assert st["heads_forced"] == 0
    # the other family read the resolved outputs through its own program
    assert any(p.startswith("jit_parsec_FB") for p in programs)


def test_the_declared_family_carries_the_head_whatever_the_managers_do(
        monkeypatch):
    """Both families ready at H's release, FA queued first: FA's wave is
    popped first, and its manager is then held up before it can claim H
    while the second manager is free.  FB, which H does not declare,
    stays parked until FA's launch has resolved H instead of forcing H
    out alone, so the program H goes out in does not follow the
    managers' timing."""
    from parsec_tpu.devices.xla import XlaDevice
    real = XlaDevice._launch

    def slow_fa(self, batch, seq=0):
        if batch[0][0].task_class.name == "FA":
            time.sleep(0.1)
        return real(self, batch, seq)
    monkeypatch.setattr(XlaDevice, "_launch", slow_fa)
    # four tiles a family: each family is ONE wave (device_fuse 4)
    sums, st, programs = _two_families(ROW, None, w=4)
    assert sums == (4.0, 6.0)
    chains = [p for p in programs if p.startswith("jit_parsec_chain_")]
    assert len(chains) == 1 and chains[0].startswith("jit_parsec_chain_H__FA")
    assert st["held_tasks"] == st["chained_launches"] == 1
    assert st["heads_forced"] == 0


def test_a_head_a_host_consumer_forces_is_counted_alone():
    """FB has a host body only and reaches the head first: it forces H
    out alone (``jit_parsec_H``), which ``heads_forced`` counts; FA then
    reads the resolved X and no chain program is built."""
    sums, st, programs = _two_families(ROW, "FA", host_only=("FB",))
    assert sums == (4.0, 6.0)
    assert st["held_tasks"] == 1 and st["heads_forced"] == 1
    assert st["chained_launches"] == 0
    assert "jit_parsec_H" in programs
    assert not [p for p in programs if p.startswith("jit_parsec_chain_")]


def test_declared_successor():
    from parsec_tpu.devices.xla import _declared_successor
    assert _declared_successor("W") is None
    assert _declared_successor(("T", "TSQRT")) == "TSQRT"
    assert _declared_successor(ROW) == "FA"


def test_discovery_holds_a_head_for_its_declared_class():
    """Under discovery (DTD) a head is held only where a task of its
    declared class is known or may still come (``XlaDevice.
    _chain_eligible`` asks the pool's ``expects_successor``): a head that
    only another class reads goes out as a plain task."""
    from parsec_tpu.devices.xla import XlaDevice
    from parsec_tpu.dsl.dtd import INOUT, DTDTaskpool, create_task_class
    head = create_task_class("LU_HEAD", ("T",), (INOUT,),
                             properties={"fuse_chain": ("T", "LU_FA")})
    head.add_chore("cpu", lambda T: T)
    fa = create_task_class("LU_FA", ("T",), (INOUT,))
    fa.add_chore("cpu", lambda T: T)
    fb = create_task_class("LU_FB", ("T",), (INOUT,))
    fb.add_chore("cpu", lambda T: T)
    A = TwoDimBlockCyclic(mb=4, nb=4, lm=4, ln=12, dtype=np.float32)
    gate = threading.Event()
    params.set("device_max", 1)      # one chip: no ICI
    try:
        with Context(nb_cores=2) as ctx:
            tp = DTDTaskpool("expects-first")
            ctx.add_taskpool(tp)
            ctx.start()
            for n in range(3):       # no head runs before the checks
                tp.insert_task(lambda T: (gate.wait(30), T)[1],
                               (A(0, n), INOUT))
            heads = [tp.insert_task(head, (A(0, n), INOUT))
                     for n in range(3)]
            tp.insert_task(fa, (A(0, 0), INOUT))
            tp.insert_task(fb, (A(0, 1), INOUT))
            # the stream still open: any successor may still come
            assert all(XlaDevice._chain_eligible(None, h, None)
                       for h in heads)
            tp._end_of_stream()
            assert [XlaDevice._chain_eligible(None, h, None)
                    for h in heads] == [True, False, False]
            gate.set()
            tp.wait()
    finally:
        gate.set()
        params.unset("device_max")
