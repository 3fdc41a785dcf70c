"""The tiled Cholesky inserted task by task (``potrf_dtd_taskpool``:
DPLASMA's ``testing_dpotrf_dtd``) on the device path, held to the
benchmark's plain reference (benchmark/reference/, which imports nothing
of the program) and, tile for tile, to the PTG's factor of the same
build; and what the DTD front end gained for it (PR 33): an inserter the
pool runs where it is started, tiles bound where they live, NEW tiles of
an arena, classes that belong to the process, a flush that knows a
tile's home, spans and counters."""

import threading

import numpy as np
import pytest

from benchmark import tiles, work
from benchmark.reference import potrf_dtd as reference
from parsec_tpu.core.context import Context
from parsec_tpu.data.data import Coherency, Data
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.dsl.dtd import (DTDTaskpool, INOUT, INPUT, OUTPUT,
                                create_task_class)
from parsec_tpu.utils.mca import params

SEED = 2 ** 31 + 33


def _builder(front):
    from parsec_tpu.apps import potrf
    return {"ptg": potrf.potrf_taskpool,
            "dtd": potrf.potrf_dtd_taskpool}[front]


def _factor(front, nt, mb, jobs=1, window=None, host_copies=True,
            record=None):
    """``jobs`` factorizations of the seeded operand, its tiles born on
    the device as the benchmark's are, through ``Context.add_taskpool``
    + ``Context.wait`` as the harness calls them.  Returns the factor's
    lower tiles, the device's counters after every job, every pool, the
    matrix and the device's bytes that crossed the host link."""
    n = nt * mb
    stats, pools = [], []
    params.set("device_max", 1)
    for k, v in (window or {}).items():
        params.set(k, v)
    try:
        with Context(nb_cores=4) as ctx:
            if not ctx.device_registry.accelerators:
                pytest.skip("no accelerator attached")
            dev = ctx.device_registry.accelerators[0]
            if record is not None:
                ctx._span_live = lambda: True
                ctx.pins_register("span_end", record)
            A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, name="A",
                                  dtype=np.float32)
            for _ in range(jobs):
                tiles.discard_scratch(ctx)
                tiles.stage(A, ctx, SEED, diag=4.0 * n ** 0.5,
                            keep=lambda m, k: m >= k, symmetric=True)
                if not host_copies:
                    for m, k in A.local_tiles():
                        A.data_of(m, k).detach_copy(0)
                tp = _builder(front)(A, device="tpu")
                ctx.add_taskpool(tp)
                ctx.wait(timeout=120)
                pools.append(tp)
                stats.append(dev.stats.as_dict())
            if record is not None:
                ctx.pins_unregister("span_end", record)
            L = {(m, k): np.asarray(tiles.newest(A, m, k))
                 for m in range(nt) for k in range(m + 1)}
            copies = {t: A.data_of(*t).copies() for t in L}
            tiles.discard_tiles(A)
            tiles.discard_scratch(ctx)
    finally:
        params.unset("device_max")
        for k in (window or {}):
            params.unset(k)
    return L, stats, pools, copies


def _plain(nt, mb):
    import jax.numpy as jnp
    n = nt * mb
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, name="A",
                          dtype=np.float32)
    op = {(i, j): tiles.make_tile(A, SEED, i, j,
                                  4.0 * n ** 0.5 if i == j else 0.0,
                                  symmetric=(i == j))
          for i in range(nt) for j in range(i + 1)}
    return {t: np.asarray(v) for t, v in reference.plain_cholesky(
        op, nt, reference.store_as(jnp.float32)).items()}


def _same(L, M, nt, **tol):
    for (m, k), t in L.items():
        a, b = (np.tril(t), np.tril(M[m, k])) if m == k else (t, M[m, k])
        if tol:
            np.testing.assert_allclose(a, b, err_msg=f"tile {(m, k)}", **tol)
        else:
            assert np.array_equal(a, b), f"tile {(m, k)} differs"


WINDOW = {"dtd_window_size": 8, "dtd_threshold_size": 4}


@pytest.mark.parametrize("nt, mb, window", [
    (4, 32, None), (6, 32, None), (4, 64, None),
    (4, 32, WINDOW), (6, 32, WINDOW), (6, 64, WINDOW)])
def test_dtd_factor_against_the_plain_reference_and_the_ptg(nt, mb, window):
    """The sequentially consistent result: every tile sees its updates
    in insert order, which is the PTG's k order — the same kernels on
    the same values, so the PTG's factor of the same build bit for bit,
    and the plain sequential execution of the stream to rounding.  With
    a window of 8 the inserting thread blocks on it and nothing else
    changes."""
    L, stats, (tp,), _c = _factor("dtd", nt, mb, window=window)
    _same(L, _plain(nt, mb), nt, rtol=2e-4, atol=2e-4)
    P, _s, _p, _c = _factor("ptg", nt, mb)
    _same(L, P, nt)
    n_tasks = work.potrf_tasks(nt)
    assert tp.stats.inserted_tasks == n_tasks == reference.stream_tasks(nt) \
        == sum(1 for _ in reference.insert_stream(nt))
    assert stats[-1]["executed_tasks"] + stats[-1]["held_tasks"] == n_tasks
    assert stats[-1]["faults"] == 0
    assert tp.stats.tracked_tiles == nt * (nt + 1) // 2
    assert tp.stats.new_tiles == nt - 1
    if window:
        most = reference.window_waits(n_tasks, 8, 4)
        assert 0 < tp.stats.window_waits <= most
    else:
        assert tp.stats.window_waits == 0


@pytest.mark.parametrize("inserts, window, threshold, want", [
    (5984, 2048, 1024, 4), (20, 8, 4, 3), (56, 8, 4, 10), (8, 8, 4, 0),
    (9, 8, 4, 1)])
def test_reference_counts_the_most_waits_a_stream_can_meet(
        inserts, window, threshold, want):
    assert reference.window_waits(inserts, window, threshold) == want
    # a drain that keeps up meets none
    assert reference.window_waits(inserts, window, threshold,
                                  lambda i, inflight: inflight) == 0


def test_second_job_builds_no_class_and_no_program():
    """Classes belong to the process: the second factorization on a
    context registers the same five, calls programs the first compiled
    and builds no chain program."""
    from parsec_tpu.apps import potrf
    L, stats, pools, _c = _factor("dtd", 4, 32, jobs=2)
    classes = potrf._dtd_classes[("tpu", None, 32)]
    assert sorted(classes) == ["GEMM", "POTRF", "POTRFL", "SYRK", "TRSM"]
    for tp in pools:
        assert {tc.name for tc in tp.task_classes.values()} == set(classes)
        assert all(tc.properties["flops"] > 0
                   for tc in tp.task_classes.values())
    a, b = (dict(tp._classes) for tp in pools)
    assert set(a) == set(b) and all(a[c] is not b[c] for c in a)
    # one kernel a class, whatever the pool
    assert all(a[c].incarnations[0][1] is b[c].incarnations[0][1] for c in a)
    assert stats[1]["compiles"] == stats[0]["compiles"]
    assert stats[1]["chain_programs"] == stats[0]["chain_programs"]
    assert stats[1]["executed_tasks"] == 2 * stats[0]["executed_tasks"]
    _same(L, _plain(4, 32), 4, rtol=2e-4, atol=2e-4)


def test_device_born_tile_is_bound_where_it_lives_and_never_pulled():
    """Tiles with NO host copy (born on the device, the host copy taken
    away): the tasks bind the device copies, nothing crosses the host
    link in either direction, and no host copy exists after the job —
    the flush that ends the stream leaves a tile born on the device at
    home there."""
    L, stats, (tp,), copies = _factor("dtd", 4, 32, host_copies=False)
    _same(L, _plain(4, 32), 4, rtol=2e-4, atol=2e-4)
    assert stats[-1]["bytes_in"] == 0 and stats[-1]["bytes_out"] == 0
    for t, by_space in copies.items():
        assert 0 not in by_space, f"tile {t} was pulled to the host"
    assert all(t.home_space != 0 for t in tp._tiles.values())


def test_new_tile_allocates_no_host_array():
    """``tile_arena``: the shape of an mb x mb float32 tile over ONE
    element on the host, zeros in device memory at its first writer,
    and nothing of it on the host afterwards."""
    seen = {}

    def inserter(tp):
        W = seen["W"] = tp.tile_arena((256, 256), np.float32)
        host = W.data.copy_on(0).payload
        assert host.shape == (256, 256) and host.nbytes == 256 * 256 * 4
        assert host.strides == (0, 0) and not host.flags.writeable
        assert host.base is not None and host.base.size == 1
        tp.insert_task(lambda W: W + 2.5, (W, OUTPUT), device="tpu")

    params.set("device_max", 1)
    try:
        with Context(nb_cores=2) as ctx:
            dev = ctx.device_registry.accelerators[0]
            tp = DTDTaskpool("new", inserter=inserter)
            ctx.add_taskpool(tp)
            ctx.wait(timeout=60)
            W = seen["W"]
            assert W.data.copy_on(0) is None        # detached at stage-in
            assert W.home_space == -1 and tp.stats.new_tiles == 1
            arena, = tp._arenas.values()
            assert arena.allocated == 0             # no host buffer, ever
            np.testing.assert_array_equal(
                np.asarray(W.data.newest_copy().payload), 2.5)
            assert dev.stats.bytes_in == 0
            dev.discard_scratch()                   # finds it: no home
            assert W.data.copies() == {}
    finally:
        params.unset("device_max")


def test_new_tile_reaching_a_host_body_is_backed_there():
    """No device took the first writer: the host's stage-in gives the
    tile a real buffer, which an in-place body can write."""
    def fill(W):
        W[...] = 7.0

    with Context(nb_cores=2) as ctx:
        tp = DTDTaskpool("new_host")
        ctx.add_taskpool(tp)
        ctx.start()
        W = tp.tile_arena((4, 4), np.float32)
        tp.insert_task(fill, (W, INOUT))
        tp.wait()
        np.testing.assert_array_equal(W.data.copy_on(0).payload, 7.0)


def test_potrf_heads_are_held_and_chained_as_the_ptgs():
    """Every POTRF head is held for its declared successor and traced
    into a TRSM wave's launch, under the PTG's program names; no more
    chain programs than the PTG builds (the process's cache is shared:
    whoever runs second builds none)."""
    programs = {"ptg": [], "dtd": []}

    def rec(front):
        return lambda es, ev, span: (
            programs[front].append(span.args["program"])
            if span.name == "mgr.dispatch" else None)
    nt = 6
    _L, ptg, _p, _c = _factor("ptg", nt, 32, record=rec("ptg"))
    _L, dtd, _p, _c = _factor("dtd", nt, 32, record=rec("dtd"))
    assert dtd[-1]["held_tasks"] == ptg[-1]["held_tasks"] == nt - 1
    assert dtd[-1]["chained_launches"] == nt - 1
    assert dtd[-1]["chain_programs"] <= ptg[-1]["chain_programs"] + 3
    chains = {p for p in programs["dtd"] if "chain" in p}
    assert chains and all(
        p.startswith("jit_parsec_chain_POTRF__TRSM_x") for p in chains)
    assert {p.split("_x")[0] for p in programs["dtd"]} <= \
        {"jit_parsec_chain_POTRF__TRSM", "jit_parsec_POTRF",
         "jit_parsec_POTRFL", "jit_parsec_TRSM", "jit_parsec_SYRK",
         "jit_parsec_GEMM"}
    # same-class waves fuse under discovery as they do under the PTG
    assert dtd[-1]["fused_tasks"] > 0


def test_spans_are_the_counters():
    """One ``dtd.insert`` a run of the inserter (``n`` = what it
    inserted), one ``dtd.window_wait`` a stall inside it, one
    ``dtd.flush`` around the flush."""
    spans = []
    rec = lambda es, ev, span: spans.append(       # noqa: E731
        (span.name, dict(span.args), dict(span.late or {}),
         threading.get_ident())) if span.name.startswith("dtd.") else None
    _L, _s, pools, _c = _factor("dtd", 6, 32, jobs=2, window=WINDOW,
                                record=rec)
    by = {k: [s for s in spans if s[0] == "dtd." + k]
          for k in ("insert", "window_wait", "flush")}
    assert len(by["insert"]) == len(by["flush"]) == 2
    assert [s[2]["n"] for s in by["insert"]] == \
        [tp.stats.inserted_tasks for tp in pools] == [56, 56]
    assert sorted(s[1]["pool"] for s in by["insert"]) == \
        sorted(tp.taskpool_id for tp in pools)
    assert len(by["window_wait"]) == sum(tp.stats.window_waits
                                         for tp in pools) > 0
    assert all(s[1]["inflight"] >= 8 for s in by["window_wait"])
    # all on the thread that started the pools: this one
    assert {s[3] for s in spans} == {threading.get_ident()}


def test_counters_are_summed_on_the_context_and_scraped():
    from parsec_tpu.prof.metrics import install_metrics
    params.set("device_max", 1)
    try:
        with Context(nb_cores=2) as ctx:
            m = install_metrics(ctx)
            A = TwoDimBlockCyclic(mb=8, nb=8, lm=8, ln=8, dtype=np.float32)
            for _ in range(2):
                def inserter(tp):
                    for _i in range(5):
                        tp.insert_task(lambda T: T + 1.0, (A(0, 0), INOUT))
                    tp.tile_new((2,))
                ctx.add_taskpool(DTDTaskpool("count", inserter=inserter))
                ctx.wait(timeout=60)
            assert ctx.dtd_stats.as_dict() == {
                "inserted_tasks": 10, "window_waits": 0,
                "tracked_tiles": 2, "new_tiles": 2}
            got = {s["n"]: s["v"] for s in m.samples()
                   if s["n"].startswith("parsec_dtd_")}
            assert got == {"parsec_dtd_inserted_tasks_total": 10,
                           "parsec_dtd_tracked_tiles_total": 2,
                           "parsec_dtd_new_tiles_total": 2}
    finally:
        params.unset("device_max")
    np.testing.assert_array_equal(
        np.asarray(A.data_of(0, 0).pull_to_host().payload), 10.0)


def test_flush_returns_a_tile_to_where_the_pool_found_it():
    """A tile found on the host is pulled back to its host copy — at the
    pool's termination where the flush is asked for in the stream, as
    DPLASMA's testers do — and a tile found on a device stays there."""
    import jax
    A = TwoDimBlockCyclic(mb=4, nb=4, lm=4, ln=8, dtype=np.float32)
    A.data_of(0, 0).copy_on(0).payload[:] = 1.0
    seen = {}

    def inserter(tp):
        for n in range(2):
            tp.insert_task(lambda T: T + 41.0, (A(0, n), INOUT),
                           device="tpu")
        tp.data_flush_all()
        seen["pending"] = tp._flush_pending
        seen["homes"] = [tp.tile_of(A, 0, n).home_space for n in range(2)]

    params.set("device_max", 1)
    try:
        with Context(nb_cores=2) as ctx:
            dev = ctx.device_registry.accelerators[0]
            A.data_of(0, 1).overwrite_on(
                dev.space, jax.device_put(np.full((4, 4), 2.0, np.float32),
                                          dev.jdev))
            tp = DTDTaskpool("flush", inserter=inserter)
            ctx.add_taskpool(tp)
            ctx.wait(timeout=60)
            assert seen == {"pending": True, "homes": [0, dev.space]}
            assert not tp._flush_pending
            host = A.data_of(0, 0).copy_on(0)
            assert host.coherency != Coherency.INVALID
            np.testing.assert_array_equal(np.asarray(host.payload), 42.0)
            there = A.data_of(0, 1)
            assert there.copy_on(0).coherency == Coherency.INVALID
            np.testing.assert_array_equal(
                np.asarray(there.newest_copy().payload), 43.0)
            tiles.discard_tiles(A)
    finally:
        params.unset("device_max")


def test_inserter_that_raises_is_the_contexts_error_and_hangs_nothing():
    ran = []

    def inserter(tp):
        tp.insert_task(lambda: ran.append(1))
        raise ValueError("the stream broke")

    with pytest.raises(RuntimeError) as err:
        with Context(nb_cores=2) as ctx:
            tp = DTDTaskpool("broken", inserter=inserter)
            ctx.add_taskpool(tp)
            ctx.wait(timeout=30)
    assert "the stream broke" in str(err.value.__cause__)
    assert tp._finished             # the hold was dropped: nothing hangs


def test_a_datum_with_a_device_copy_alone_is_bound():
    """The insert-time binding of the host copy's handle is gone: a raw
    Data whose only copy lives on a device runs through a device task."""
    import jax
    params.set("device_max", 1)
    try:
        with Context(nb_cores=2) as ctx:
            dev = ctx.device_registry.accelerators[0]
            d = Data(nb_elts=64)
            d.overwrite_on(dev.space, jax.device_put(
                np.ones((4, 4), np.float32), dev.jdev))
            tp = DTDTaskpool("raw")
            ctx.add_taskpool(tp)
            ctx.start()
            for _ in range(3):
                tp.insert_task(lambda T: T * 2.0, (d, INOUT), device="tpu")
            tp.wait()
            assert 0 not in d.copies()
            np.testing.assert_array_equal(
                np.asarray(d.newest_copy().payload), 8.0)
            assert dev.stats.bytes_in == 0
            d.detach_copy(dev.space)
    finally:
        params.unset("device_max")


def test_head_is_held_only_for_a_successor_that_can_come():
    """Under discovery a successor exists once the inserter is past it:
    while the stream is open a head may be held for one yet to come;
    once it has ended, only for one that was discovered."""
    cls = create_task_class("HEAD", ("T",), (INOUT,),
                            properties={"fuse_chain": ("T", "NEXT")})
    cls.add_chore("cpu", lambda T: T)
    nxt = create_task_class("NEXT", ("T",), (INOUT,))
    nxt.add_chore("cpu", lambda T: T)
    other = create_task_class("OTHER", ("T",), (INOUT,))
    other.add_chore("cpu", lambda T: T)
    A = TwoDimBlockCyclic(mb=4, nb=4, lm=4, ln=12, dtype=np.float32)
    with Context(nb_cores=2) as ctx:
        tp = DTDTaskpool("expects")
        ctx.add_taskpool(tp)
        ctx.start()
        gate = threading.Event()
        tp.insert_task(lambda T: (gate.wait(30), T)[1], (A(0, 0), INOUT))
        heads = [tp.insert_task(cls, (A(0, n), INOUT)) for n in range(3)]
        tp.insert_task(nxt, (A(0, 0), INOUT))
        tp.insert_task(other, (A(0, 1), INOUT))
        assert all(tp.expects_successor(h, "NEXT") for h in heads)  # open
        tp._end_of_stream()
        assert [tp.expects_successor(h, "NEXT") for h in heads] == \
            [True, False, False]
        assert [tp.expects_successor(h, None) for h in heads] == \
            [True, True, False]
        gate.set()
        tp.wait()
