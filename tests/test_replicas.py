"""Replicas of another chip's tile leave at their last consumer, tiles
lie on a P x Q grid of chips, and every chip is warm alike (PR 27): a
distributed potrf and the multi-device GEMM on the virtual CPU mesh,
tiny tiles."""

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.data import Coherency, FLAG_REPLICA
from parsec_tpu.data.matrix import (TwoDimBlockCyclic, VectorTwoDimCyclic,
                                    device_grid)
from parsec_tpu.utils.mca import params

MB = 16


@pytest.fixture
def ctx4():
    params.set("device_max", 4)
    try:
        with Context(nb_cores=4) as c:
            if c.ici is None or c.ici.ndev != 4:
                pytest.skip("needs 4 XLA devices")
            yield c
    finally:
        params.unset("device_max")


def spd(n, seed=0):
    m = np.random.default_rng(seed).standard_normal((n, n)).astype(np.float32)
    return (m + m.T) / 2 + 4 * np.sqrt(n) * np.eye(n, dtype=np.float32)


def stats(ctx):
    return [d.stats.as_dict() for d in ctx.ici.xla_devices]


def replicas_left(ctx, *matrices):
    """(matrix, m, n, space) of every payload still attached on a chip
    that is not the tile's own, and every copy still flagged a replica."""
    left = []
    for M in matrices:
        for (m, n) in M.local_tiles():
            datum = M.data_of(m, n)
            for sp, c in datum.copies().items():
                if c.flags & FLAG_REPLICA or (
                        sp not in (0, datum.preferred_device)
                        and c.payload is not None):
                    left.append((M.name, m, n, sp))
    return left


def run_potrf(ctx, nt, seed=0):
    from parsec_tpu.apps.potrf import potrf_taskpool
    n = nt * MB
    S = spd(n, seed)
    A = TwoDimBlockCyclic(mb=MB, nb=MB, lm=n, ln=n, name="A").from_array(
        S.copy())
    A.distribute_devices(ctx)
    ctx.add_taskpool(potrf_taskpool(A, device="tpu"))
    ctx.wait(timeout=120)
    return A, S


@pytest.mark.parametrize("nt", [6, 9])
def test_distributed_potrf_releases_every_replica(ctx4, nt):
    A, S = run_potrf(ctx4, nt)
    st = stats(ctx4)
    assert sum(s["replicas_adopted"] for s in st) > nt
    for s in st:
        assert s["replicas_released"] == s["replicas_adopted"], st
    assert [d._replica_bytes for d in ctx4.ici.xla_devices] == [0] * 4
    assert replicas_left(ctx4, A) == []
    # three panels' worth, where keeping them all (the logic before)
    # would end a job with every factor tile on every chip that read it
    tile = MB * MB * 4
    assert max(s["replica_bytes_peak"] for s in st) <= 3 * (nt - 1) * tile
    assert max(s["replica_bytes_peak"] for s in st) < \
        (nt * (nt - 1) // 2) * tile / 2
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L, np.linalg.cholesky(S), rtol=2e-3,
                               atol=2e-3)


def test_potrf_tasks_run_where_their_tile_lies(ctx4):
    """Owner computes: the TRSM panel is two chips wide on 2 x 2, and no
    chip runs a task whose written tile is another's."""
    nt = 6
    run_potrf(ctx4, nt)
    done = [s["executed_tasks"] + s["held_tasks"] for s in stats(ctx4)]
    # tasks by the chip of the tile they write: POTRF/SYRK on (k,k) /
    # (m,m), TRSM on (m,k), GEMM on (m,n)
    want = [0] * 4
    chip = lambda m, n: (m % 2) * 2 + n % 2
    for k in range(nt):
        want[chip(k, k)] += 1
        for m in range(k + 1, nt):
            want[chip(m, k)] += 1
            want[chip(m, m)] += 1
            for n in range(k + 1, m):
                want[chip(m, n)] += 1
    assert done == want


def test_multidevice_gemm_releases_every_replica(ctx4):
    """The A/B panel broadcasts of the owner-computes GEMM share the
    path: counted, and gone when the pool ends."""
    from parsec_tpu.apps.gemm import gemm_taskpool
    rng = np.random.default_rng(3)
    nt = 4
    n = MB * nt
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    mk = lambda name, arr: TwoDimBlockCyclic(
        mb=MB, nb=MB, lm=n, ln=n, name=name).from_array(arr)
    A, B, C = mk("A", a), mk("B", b), mk("C", np.zeros((n, n), np.float32))
    C.distribute_devices(ctx4)
    ctx4.add_taskpool(gemm_taskpool(A, B, C, device="tpu", panel_bcast=True))
    ctx4.wait(timeout=120)
    st = stats(ctx4)
    assert ctx4.ici.stats.bcasts > 0
    assert sum(s["replicas_adopted"] for s in st) > 0
    for s in st:
        assert s["replicas_released"] == s["replicas_adopted"], st
    assert not [x for x in replicas_left(ctx4, A, B) if x[3] != 0
                and (A if x[0] == "A" else B).data_of(x[1], x[2])
                .copy_on(x[3]).flags & FLAG_REPLICA]
    np.testing.assert_allclose(C.to_array(), a @ b, rtol=2e-3, atol=2e-3)


def test_late_consumer_after_release_stages_in(ctx4):
    """A reader that comes after the replica has left (a second pool
    over the factor) finds no copy on its chip, stages in lazily and
    computes right."""
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range
    nt = 6
    A, S = run_potrf(ctx4, nt)
    L = np.tril(A.to_array())
    n = nt * MB
    R = TwoDimBlockCyclic(mb=MB, nb=MB, lm=n, ln=n, name="R").from_array(
        np.zeros((n, n), np.float32))
    R.distribute_devices(ctx4)
    # R(m, 0) = A(m, 0) + A(m - 1, 0): each task reads a tile of the
    # chip across the grid's row boundary
    p = PTG("late", NT=nt)
    p.task("ADD", m=Range(1, nt - 1)) \
        .affinity(lambda m, R=R: R(m, 0)) \
        .flow("X", "READ", IN(DATA(lambda m, A=A: A(m, 0)))) \
        .flow("Y", "READ", IN(DATA(lambda m, A=A: A(m - 1, 0)))) \
        .flow("Z", "RW", IN(DATA(lambda m, R=R: R(m, 0))),
              OUT(DATA(lambda m, R=R: R(m, 0)))) \
        .body(lambda X, Y, Z: X + Y, device="tpu") \
        .body(lambda X, Y, Z: X + Y)
    ctx4.add_taskpool(p.build())
    ctx4.wait(timeout=120)
    got = R.to_array()
    for m in range(1, nt):
        want = L[m * MB:(m + 1) * MB, :MB] + L[(m - 1) * MB:m * MB, :MB]
        if m == 1:                 # the diagonal tile's upper triangle
            want = A.to_array()[MB:2 * MB, :MB] + A.to_array()[:MB, :MB]
        np.testing.assert_allclose(got[m * MB:(m + 1) * MB, :MB], want,
                                   rtol=1e-5, atol=1e-5)
    for s in stats(ctx4):
        assert s["replicas_released"] == s["replicas_adopted"]


def test_release_keeps_a_copy_that_was_written_or_is_the_last(ctx4):
    """Never the owner's copy, never a write-back: a flagged copy that
    became the authoritative one only stops being a replica."""
    import jax
    from parsec_tpu.data.data import new_data
    ici = ctx4.ici
    d0, d1 = ici.xla_devices[0], ici.xla_devices[1]
    a = np.arange(64, dtype=np.float32).reshape(8, 8)
    datum = new_data(np.zeros((8, 8), np.float32))
    src = datum.overwrite_on(d0.space, jax.device_put(a, d0.jdev))
    datum.replica_readers = {d1.space: 1}
    assert ici.preplace(src, d1.space, counted=True)
    rep = datum.copy_on(d1.space)
    assert rep.flags & FLAG_REPLICA and d1.stats.replicas_adopted == 1
    rep.coherency = Coherency.EXCLUSIVE          # written there since
    d1.release_replica(datum)
    assert datum.copy_on(d1.space) is rep and rep.payload is not None
    assert not rep.flags & FLAG_REPLICA and d1.stats.replicas_released == 1
    assert d1.stats.bytes_out == 0
    # a counted replica whose readers are gone is dropped, not attached
    datum2 = new_data(np.zeros((8, 8), np.float32))
    src2 = datum2.overwrite_on(d0.space, jax.device_put(a, d0.jdev))
    datum2.replica_readers = {}
    ici.preplace(src2, d1.space, counted=True)
    assert datum2.copy_on(d1.space) is None


@pytest.mark.parametrize("n, grid", [(1, (1, 1)), (2, (1, 2)), (3, (1, 3)),
                                     (4, (2, 2)), (6, (2, 3)), (8, (2, 4))])
def test_device_grid_is_near_square(n, grid):
    assert device_grid(n, rows=5, cols=5) == grid


def test_device_grid_takes_the_callers_and_refuses_what_does_not_cover():
    assert device_grid(8, P=4) == (4, 2)
    assert device_grid(8, Q=8) == (1, 8)
    assert device_grid(4, rows=7, cols=1) == (4, 1)    # a column of tiles
    assert device_grid(4, rows=1, cols=7) == (1, 4)
    with pytest.raises(ValueError):
        device_grid(4, P=3)


@pytest.mark.parametrize("nspaces", [2, 4, 8])
def test_distribute_devices_lays_a_p_by_q_grid(nspaces):
    spaces = list(range(1, nspaces + 1))
    P, Q = {2: (1, 2), 4: (2, 2), 8: (2, 4)}[nspaces]
    A = TwoDimBlockCyclic(mb=4, nb=4, lm=24, ln=24, name="A")
    A.distribute_devices(spaces)
    for m in range(6):
        for n in range(6):
            assert A.data_of(m, n).preferred_device == \
                spaces[(m % P) * Q + n % Q]
    if nspaces == 4:
        assert [A.data_of(m, n).preferred_device - 1
                for m in range(2) for n in range(2)] == [0, 1, 2, 3]
        A.distribute_devices(spaces, P=1, Q=4)           # the old lay-out
        assert A.data_of(3, 2).preferred_device == spaces[2]
    V = VectorTwoDimCyclic(mb=4, lm=4 * nspaces)
    V.distribute_devices(spaces)       # tile k on chip k, as before
    assert [V.data_of(k).preferred_device for k in range(nspaces)] == spaces


def test_every_chip_is_warm_after_the_warm_up(ctx4):
    """Two jobs and ``wait_fuse_warm`` after each, as a bench warms up:
    a further job finds every program it meets already called on its
    chip — 0 more ``compiles`` on every device, whatever widths meet."""
    from parsec_tpu.devices.xla import wait_fuse_warm
    for _ in range(2):
        run_potrf(ctx4, 8)
        assert wait_fuse_warm(timeout=300)
    before = [s["compiles"] for s in stats(ctx4)]
    for seed in (1, 2):
        run_potrf(ctx4, 8, seed)
    assert [s["compiles"] for s in stats(ctx4)] == before
    assert all(not d.fuse_failures for d in ctx4.ici.xla_devices)


def test_ici_spans_are_counted_as_ici_stats(ctx4):
    """One ``ici.put`` / ``ici.bcast`` / ``ici.permute`` span a transfer,
    carrying the bytes ``IciStats`` counts."""
    from parsec_tpu.prof.pins import ICI_SPAN_NAMES
    seen = []

    def begin(es, event, span):
        if span.name in ICI_SPAN_NAMES:
            seen.append((span.name, dict(span.args)))
    ctx4.pins_register("span_begin", begin)
    live = ctx4._span_live
    ctx4._span_live = lambda: True
    try:
        s0 = ctx4.ici.stats.as_dict()
        run_potrf(ctx4, 6)
    finally:
        ctx4._span_live = live
        ctx4.pins_unregister("span_begin", begin)
    s1 = ctx4.ici.stats.as_dict()
    delta = {k: s1[k] - s0[k] for k in s1}
    for kind, count, nbytes in (("put", "puts", "put_bytes"),
                                ("bcast", "bcasts", "bcast_bytes"),
                                ("permute", "permutes", "permute_bytes")):
        mine = [a for n, a in seen if n == "ici." + kind]
        assert len(mine) == delta[count]
        assert sum(a["bytes"] for a in mine) == delta[nbytes]
    assert delta["bcasts"] > 0 and delta["puts"] > 0
    assert all(a["ndst"] >= 1 for _n, a in seen)
