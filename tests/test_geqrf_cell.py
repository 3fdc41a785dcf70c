"""The tiled QR on the device path, held to the benchmark's plain
reference (benchmark/reference/geqrf.py, which imports nothing of the
program), and the bounded set of chain programs its columns ride
(devices/xla.py: one held head a program, traced into the launch of its
declared successor; PR 31)."""

import numpy as np
import pytest

from benchmark.reference import geqrf as reference
from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.utils.mca import params

CHAINS = {"jit_parsec_chain_GEQRT__TSQRT_x1",
          "jit_parsec_chain_TSQRT__TSQRT_x1"}


def _operand(n, seed):
    """Entries uniform with mean 0 and variance 1, no structure: what
    the cell's generator makes (benchmark/tiles.py)."""
    rng = np.random.default_rng(seed)
    return ((rng.random((n, n)) - 0.5) * 12.0 ** 0.5).astype(np.float32)


def _factor(a, mb, ib, jobs=1, spans=None):
    """``jobs`` factorizations of ``a`` on one Context through
    ``qr_taskpool(A, device="tpu")``; returns the last factor, the
    device's counters after every job and the ``program`` of every
    ``mgr.dispatch`` span."""
    from parsec_tpu.apps import qr
    n = a.shape[0]
    programs, stats = [], []
    params.set("qr_ib", ib)
    params.set("device_max", 1)
    try:
        with Context(nb_cores=4) as ctx:
            if not ctx.device_registry.accelerators:
                pytest.skip("no accelerator attached")
            cb = lambda es, event, span: (           # noqa: E731
                programs.append(span.args["program"])
                if span.name == "mgr.dispatch" else None)
            ctx._span_live = lambda: True
            ctx.pins_register("span_begin", cb)
            for _ in range(jobs):
                A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n,
                                      ln=n).from_array(a.copy())
                ctx.add_taskpool(qr.qr_taskpool(A, device="tpu"))
                ctx.wait(timeout=120)
                stats.append(
                    ctx.device_registry.accelerators[0].stats.as_dict())
            ctx.pins_unregister("span_begin", cb)
            out = A.to_array()
    finally:
        params.unset("qr_ib")
        params.unset("device_max")
    return out, stats, programs


@pytest.mark.parametrize("nt, ib, W", [
    (3, 0, None), (3, 8, None), (4, 0, None), (4, 8, None),
    (3, 8, 16), (4, 8, 8)])
def test_qr_device_path_against_the_plain_reference(nt, ib, W, monkeypatch):
    """R of the tiled algorithm, blocked and unblocked, against the
    plain Householder QR of the same seeded operand: the comparison that
    decides the cell's ``correct`` under the configuration's own limits,
    and R itself up to the signs of its rows.  ``W``: the reflector in
    column groups that narrow (two and mb / ib groups a panel; the
    rule's own choice at this size is one)."""
    import json
    import os
    import jax.numpy as jnp
    from benchmark import harness
    from parsec_tpu.apps import qr
    mb = 32
    n = nt * mb
    a = _operand(n, 100 * nt + ib)
    assert qr.group_width(mb, ib) == mb
    if W:
        monkeypatch.setattr(qr, "group_width", lambda mb, ib: W)
    qr._kernels.clear()
    qr.selected.clear()
    out, stats, _programs = _factor(a, mb, ib)
    assert qr.selected == ({("TSQRT", mb, ib): W or mb} if ib else {})
    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           "dplasma_geqrf_bf16.json")) as f:
        limits = json.load(f)["limits"]
    tile = lambda M: (lambda i, j: jnp.asarray(  # noqa: E731
        M[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb]))
    got = reference.factor_check(nt, mb, tile(out), tile(a), seed=nt)
    assert got["factor_resid"] < 1e-5 < limits["factor_resid"]
    assert got["below_diag_max"] == 0.0 <= limits["below_diag_max"]
    R_ref = np.asarray(reference.plain_qr(jnp.asarray(a), 8, mb))
    assert np.abs(np.tril(R_ref, -1)).max() == 0.0
    sign = lambda R: np.where(np.diag(R) < 0, -1.0, 1.0)[:, None]  # noqa
    R = np.triu(out)
    assert np.abs(sign(R) * R - sign(R_ref) * R_ref).max() \
        < 1e-3 * np.abs(R_ref).max()
    # the reference itself satisfies the statement it is held to
    ref = reference.factor_check(nt, mb, tile(R_ref), tile(a), seed=nt)
    assert ref["factor_resid"] < 1e-5 and ref["below_diag_max"] == 0.0
    assert stats[-1]["chained_launches"] > 0 and stats[-1]["faults"] == 0


def test_chain_programs_do_not_follow_nt_and_are_built_once():
    """The chain programs a QR taskpool asks for are its (declaring
    class, declared successor) pairs — two, the same two at nt = 4 and
    nt = 8 — ``chain_programs`` counts exactly the distinct chain
    programs that were dispatched, every held head goes out with its
    successor, and a second job of the same pool builds none."""
    from parsec_tpu.apps import qr
    from parsec_tpu.devices import xla
    # kernel functions of this test's own: the chain cache is keyed by
    # them, and another test of this process may have built the pair
    qr._kernels.clear()
    before = set(xla._chain_jit_cache)
    mb = 8
    seen = {}
    for nt in (4, 8):
        _out, stats, programs = _factor(_operand(nt * mb, nt), mb, 0,
                                        jobs=2)
        chains = {p for p in programs if p.startswith("jit_parsec_chain_")}
        seen[nt] = chains
        assert chains == CHAINS
        first, second = stats
        assert second["chain_programs"] == first["chain_programs"]
        # one head a launch: the column goes two links at a time
        links = nt * (nt - 1) // 2 + nt - 1      # TSQRT + GEQRT with one
        assert second["held_tasks"] == second["chained_launches"]
        assert second["chained_tasks"] == 2 * second["chained_launches"]
        assert second["held_tasks"] <= links
    built = {jf.__name__ for k, jf in xla._chain_jit_cache.items()
             if k not in before}
    assert {"jit_" + b for b in built} == CHAINS == seen[4] == seen[8]


def test_first_pool_counts_the_chain_programs_it_builds():
    from parsec_tpu.apps import qr
    qr._kernels.clear()
    _out, stats, programs = _factor(_operand(32, 5), 8, 0)
    chains = {p for p in programs if p.startswith("jit_parsec_chain_")}
    assert stats[0]["chain_programs"] == len(chains) == 2


def _tsmqr_gap(edge, top, bot, Rp):
    """TSMQR's group loop on the panel its edge came from: how far
    Q^T [top; bot] lies from [R'; 0], in units of the largest R'."""
    import jax.numpy as jnp
    from parsec_tpu.apps.qr import _mk_tsmqr
    got = _mk_tsmqr()(edge, jnp.asarray(top), jnp.asarray(bot))
    return max(np.abs(np.asarray(got["C1"], np.float64) - Rp).max(),
               np.abs(np.asarray(got["C2"])).max()) / np.abs(Rp).max()


@pytest.mark.parametrize("W", [32, 16])
@pytest.mark.parametrize("cond", [1e3, 1e6])
def test_blocked_panel_kernels_hold_on_an_ill_conditioned_panel(cond, W):
    """The blocked GEQRT / TSQRT have no Householder fall-back any more:
    a column block whose Gram matrix is not positive definite in f32
    takes the shifted Cholesky-QR branch, and R still satisfies
    R^T R = A^T A — whether the reflector is one group or two, and
    TSMQR's group loop still turns the panel into [R'; 0]."""
    import jax.numpy as jnp
    from parsec_tpu.apps.qr import _mk_geqrt, _mk_tsqrt
    mb, ib = 32, 8
    rng = np.random.default_rng(int(cond))
    u, _ = np.linalg.qr(rng.standard_normal((2 * mb, 2 * mb)))
    v, _ = np.linalg.qr(rng.standard_normal((mb, mb)))
    s = np.logspace(0, -np.log10(cond), mb)
    panel = ((u[:, :mb] * s) @ v.T).astype(np.float32)   # 2mb x mb
    top, bot = panel[:mb], panel[mb:]
    g = _mk_geqrt(ib)(jnp.asarray(top), jnp.zeros((mb, mb), jnp.float32))
    R, Q = np.asarray(g["T"], np.float64), np.asarray(g["Q"], np.float64)
    assert np.isfinite(R).all() and np.isfinite(Q).all()
    tol = 1e-4 if cond <= 1e3 else 2e-2
    tt = top.astype(np.float64).T @ top
    assert np.abs(R.T @ R - tt).max() / np.abs(tt).max() < tol
    t = _mk_tsqrt(ib)(g["T"], jnp.asarray(bot),
                      jnp.zeros((mb + W, mb), jnp.float32))
    Rp = np.asarray(t["T"], np.float64)
    assert np.isfinite(Rp).all() and np.isfinite(np.asarray(t["Q"])).all()
    ata = panel.astype(np.float64).T @ panel
    assert np.abs(Rp.T @ Rp - ata).max() / np.abs(ata).max() < tol
    assert _tsmqr_gap(t["Q"], g["T"], bot, Rp) < tol


def test_chain_programs_is_scraped_with_the_device_counters():
    from parsec_tpu.apps import qr
    from parsec_tpu.apps.qr import qr_taskpool
    qr._kernels.clear()
    a = _operand(32, 7)
    params.set("device_max", 1)
    try:
        with Context(nb_cores=2) as ctx:
            A = TwoDimBlockCyclic(mb=8, nb=8, lm=32, ln=32).from_array(a)
            ctx.add_taskpool(qr_taskpool(A, device="tpu"))
            ctx.wait(timeout=120)
            (dev,) = ctx.device_registry.accelerators
            samples = {s["n"]: s["v"]
                       for s in ctx.metrics._collect_devices()}
            assert dev.stats.as_dict()["chain_programs"] == 2 == \
                samples["parsec_device_chain_programs_total"]
    finally:
        params.unset("device_max")


# ---------------------------------------------------------------------------
# blocks whose Gram matrix is not positive definite in f32 (the check of
# PR 31 met one on the chip: seed 1030282691, factor_resid inf)
# ---------------------------------------------------------------------------

def _with_smallest(n, smin, seed):
    """A Gaussian n x n block with its smallest singular value set to
    ``smin`` (the largest is ~2 sqrt(n)): what the last, square block
    of a GEQRT panel is, one time in a thousand as badly as 1e-6."""
    rng = np.random.default_rng(seed)
    U, s, Vt = np.linalg.svd(rng.standard_normal((n, n)))
    s[-1] = smin
    return ((U * s) @ Vt).astype(np.float32)


def _duplicated(n, seed):
    a = _with_smallest(n, 1.0, seed)
    a[:, n // 3] = a[:, 1]
    return a


BLOCKS = {"cond 1e3": lambda: _with_smallest(64, 1.6e-2, 1),
          "cond 1e5": lambda: _with_smallest(64, 1.6e-4, 2),
          "cond 1e7": lambda: _with_smallest(64, 1.6e-6, 3),
          "cond 1e10": lambda: _with_smallest(64, 1.6e-9, 4),
          "singular": lambda: _with_smallest(64, 0.0, 5),
          "ib 512, cond 1e7": lambda: _with_smallest(512, 4.5e-6, 8),
          "a column twice": lambda: _duplicated(64, 6),
          "rank 1": lambda: np.outer(*_operand(64, 7)[:2]).astype(np.float32)}


def _gram_gap(R, a):
    ata = a.astype(np.float64).T @ a.astype(np.float64)
    return np.linalg.norm(R.astype(np.float64).T @ R - ata) \
        / np.linalg.norm(ata)


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_cholqr_block_of_any_condition_is_finite_and_orthonormal(block):
    """The panel's Cholesky-QR of one block: whatever the block's
    condition, Q R is the block, Q is orthonormal and R is a factor of
    its Gram matrix — never a NaN (two plain passes hold only up to
    cond ~ 1/sqrt(eps); one shifted pass before them up to ~1e6)."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps import qr
    a = BLOCKS[block]()
    hi = jax.lax.Precision.HIGHEST
    Q, R = map(np.asarray, jax.jit(
        lambda X: qr._cholqr2(X, jnp, hi))(jnp.asarray(a)))
    assert np.isfinite(Q).all() and np.isfinite(R).all()
    assert np.abs(np.tril(R, -1)).max() == 0.0
    assert np.abs(Q.astype(np.float64) @ R - a).max() \
        < 1e-4 * np.abs(a).max()
    assert np.abs(Q.astype(np.float64).T @ Q
                  - np.eye(a.shape[1])).max() < 1e-4
    assert _gram_gap(R, a) < 1e-4


@pytest.mark.parametrize("block", ["cond 1e7", "singular", "a column twice"])
def test_gram_factor_of_a_stacked_block_that_is_not_positive_definite(block):
    """TSQRT's factor of [R_jj; B_j]: L L^T is the stacked block's Gram
    matrix, finite, whatever its condition."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps import qr
    bot = np.concatenate([BLOCKS[block](), BLOCKS[block]()[::-1]])
    top = np.triu(BLOCKS[block]())
    hi = jax.lax.Precision.HIGHEST
    L = np.asarray(jax.jit(lambda t, b: qr._gram_factor(t, b, jnp, hi))(
        jnp.asarray(top), jnp.asarray(bot)))
    assert np.isfinite(L).all() and np.abs(np.triu(L, 1)).max() == 0.0
    assert _gram_gap(L.T, np.concatenate([top, bot])) < 1e-4


@pytest.mark.parametrize("W", [64, 32, 16])
@pytest.mark.parametrize("smin", [1.6e-6, 0.0])
def test_blocked_panel_kernels_with_an_ill_conditioned_last_block(smin, W):
    """GEQRT and TSQRT, inner-blocked, on tiles whose LAST ib columns
    are (nearly) dependent on the ones before: the block that is left
    after the earlier blocks are projected out is what fails a plain
    Cholesky.  R is finite and a factor of the tile's (the stacked
    pair's) Gram matrix, and Q R is the tile.  (Q's last column keeps a
    component of eps x cond along the blocks before it: one
    re-projection a block, PERF.md section 7.)"""
    import jax.numpy as jnp
    from parsec_tpu.apps import qr
    mb, ib = 64, 16
    a = _with_smallest(mb, smin, 11)
    b = _with_smallest(mb, 1.0, 12)
    b[:, -1] = b[:, 0] * 2.0 + smin * b[:, -1]
    out = qr._mk_geqrt(ib)(jnp.asarray(a), jnp.asarray(a))
    R, Q = np.asarray(out["T"]), np.asarray(out["Q"])
    assert np.isfinite(R).all() and np.isfinite(Q).all()
    assert _gram_gap(np.triu(R), a) < 1e-4
    assert np.abs(Q.astype(np.float64) @ np.triu(R) - a).max() \
        < 1e-4 * np.abs(a).max()
    assert np.abs((Q.astype(np.float64) ** 2).sum(0) - 1.0).max() < 1e-4
    r0 = np.triu(_with_smallest(mb, 1.0, 13))
    r0[:, -1] = r0[:, 0] * 2.0
    out = qr._mk_tsqrt(ib)(jnp.asarray(r0), jnp.asarray(b),
                           jnp.zeros((mb + W, mb), jnp.float32))
    Rp = np.asarray(out["T"])
    assert np.isfinite(Rp).all() and np.isfinite(np.asarray(out["Q"])).all()
    assert _gram_gap(np.triu(Rp), np.concatenate([r0, b])) < 1e-4
    assert out["Q"].shape == (mb + W, mb)


# ---------------------------------------------------------------------------
# the Q edge between TSQRT and TSMQR: V over the W x mb strip of T^T's
# diagonal blocks (PR 32).  A task of one pool may run either incarnation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mb, ib, W", [(32, 8, 16), (48, 8, 16), (32, 8, 8)])
@pytest.mark.parametrize("tsqrt_on", ["device", "cpu"])
def test_the_two_incarnations_agree_through_the_edge(mb, ib, W, tsqrt_on,
                                                     monkeypatch):
    """Device TSQRT -> ``cpu_tsmqr`` and ``cpu_tsqrt`` -> device TSMQR
    of a device pool give the C1 / C2 that device -> device gives, at a
    group width under mb: the CPU bodies read W off the same edge,
    factor a group at a time in float64 and run the same group loop."""
    import jax.numpy as jnp
    from parsec_tpu.apps import qr
    monkeypatch.setattr(qr, "group_width", lambda mb, ib: W)
    params.set("qr_ib", ib)
    try:
        tp = qr.qr_taskpool(TwoDimBlockCyclic(mb=mb, nb=mb, lm=2 * mb,
                                              ln=2 * mb), device="tpu")
    finally:
        params.unset("qr_ib")
    cpu = {c: dict(tp.task_classes[c].incarnations)["cpu"].__ptg_fn__
           for c in ("TSQRT", "TSMQR")}
    rng = np.random.default_rng(mb + W)
    R = (np.triu(rng.standard_normal((mb, mb)))
         + 3 * np.eye(mb)).astype(np.float32)
    B, C1, C2 = (rng.standard_normal((mb, mb)).astype(np.float32)
                 for _ in range(3))
    edge0 = np.zeros((mb + W, mb), np.float32)
    dev = qr._mk_tsqrt(ib)(jnp.asarray(R), jnp.asarray(B),
                           jnp.asarray(edge0))
    want = qr._mk_tsmqr()(dev["Q"], jnp.asarray(C1), jnp.asarray(C2))
    if tsqrt_on == "device":
        got = cpu["TSMQR"](np.asarray(dev["Q"]), C1, C2)
    else:
        host = cpu["TSQRT"](R, B, edge0)
        assert host["Q"].shape == (mb + W, mb)
        assert np.abs(host["T"] - np.asarray(dev["T"])).max() < 1e-4
        assert np.abs(host["Q"] - np.asarray(dev["Q"])).max() < 1e-4
        got = qr._mk_tsmqr()(jnp.asarray(host["Q"]), jnp.asarray(C1),
                             jnp.asarray(C2))
    for flow in ("C1", "C2"):
        assert np.abs(np.asarray(got[flow])
                      - np.asarray(want[flow])).max() < 1e-4


def test_the_group_width_follows_the_shapes_and_a_cpu_pool_keeps_one_group():
    """The rule reads the shapes alone: the narrowest multiple of ib
    that divides mb and reaches the floor, one group where none does or
    the panel is unblocked; ``device="cpu"`` keeps one group whatever
    the shapes (W = mb, the (2 mb, mb) edge); and the load-balancing
    weights follow what is executed."""
    from parsec_tpu.apps import qr
    assert qr.group_width(6144, 512) == 1024
    assert qr.group_width(6144, 0) == 6144
    assert qr.group_width(1024, 512) == 1024
    assert qr.group_width(2560, 512) == 2560
    assert qr.group_width(6144, 768) == 1536
    params.set("qr_ib", 512)
    try:
        A = TwoDimBlockCyclic(mb=6144, nb=6144, lm=12288, ln=12288)
        pools = {d: qr.qr_taskpool(A, device=d) for d in ("cpu", "tpu")}
    finally:
        params.unset("qr_ib")
    edge = {d: tp.arenas["q2"].shape for d, tp in pools.items()}
    assert edge == {"cpu": (2 * 6144, 6144), "tpu": (6144 + 1024, 6144)}
    flops = {c: tc.properties["flops"] / 6144.0 ** 3
             for c, tc in pools["tpu"].task_classes.items()}
    # 4 mb^3 + 2 W mb^2 in TSMQR; in TSQRT the trailing updates'
    # 2 mb^3 (1 - 1 / 12) and the mb^2 ib terms
    assert flops["TSMQR"] == pytest.approx(4 + 1 / 3)
    assert 2.0 < flops["TSQRT"] < 2.6
