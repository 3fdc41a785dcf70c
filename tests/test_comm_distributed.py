"""Distributed tests: multiprocess SPMD over the socket comm engine.

Mirrors the reference's multi-process-on-one-node strategy (SURVEY.md §4:
``mpiexec -n N`` on one node; dtd_test_ce.c drives the comm-engine vtable
directly; Ex05_Broadcast exercises the activation fan-out; apps/pingpong
measures the link).  Worker functions are module-level for spawn pickling.
"""

import numpy as np
import pytest

from parsec_tpu.comm.launch import run_distributed

# -- comm engine direct (reference: dtd_test_ce.c) --------------------------

def _ce_echo(ctx, rank, nranks):
    import threading
    from parsec_tpu.comm.engine import TAG_USER
    got = []
    evt = threading.Event()

    def cb(src, payload):
        got.append((src, payload))
        evt.set()

    ce = ctx.comm.ce
    ce.tag_register(TAG_USER, cb)
    ce.barrier()
    ce.send_am(TAG_USER, (rank + 1) % nranks, {"hello": rank})
    if not evt.wait(30):
        raise TimeoutError("no AM received")
    ce.barrier()
    src, payload = got[0]
    assert src == (rank - 1) % nranks
    assert payload == {"hello": src}
    return "ok"


def test_ce_am_ring():
    assert run_distributed(_ce_echo, 3) == ["ok"] * 3


# -- PTG chain across ranks (reference: Ex03 chain over MPI) ----------------

def _chain(ctx, rank, nranks):
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    NT = 8
    V = VectorTwoDimCyclic(mb=4, lm=NT * 4, nodes=nranks, myrank=rank)
    for m, _ in V.local_tiles():
        V.data_of(m).copy_on(0).payload[:] = 0.0

    p = PTG("chain", NT=NT)
    p.task("S", k=Range(0, NT - 1)) \
        .affinity(lambda k, V=V: V(k)) \
        .flow("T", "RW",
              IN(DATA(lambda k, V=V: V(k)), when=lambda k: k == 0),
              IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("S", "T", lambda k, NT=NT: dict(k=k + 1)),
                  when=lambda k, NT=NT: k < NT - 1),
              OUT(DATA(lambda k, V=V: V(k)))) \
        .body(lambda T: T + 1.0)
    ctx.add_taskpool(p.build())
    ctx.wait()
    # tile k ends with value k+1 (chain accumulates one increment per hop)
    out = {}
    for m, _ in V.local_tiles():
        out[m] = float(np.asarray(V.data_of(m).pull_to_host().payload)[0])
    return out


def test_ptg_chain_across_ranks():
    results = run_distributed(_chain, 2)
    merged = {}
    for r in results:
        merged.update(r)
    assert merged == {k: float(k + 1) for k in range(8)}


# -- broadcast fan-out (reference: Ex05_Broadcast + bcast topologies) -------

def _bcast(ctx, rank, nranks, topo):
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    from parsec_tpu.utils.mca import params
    params.set("comm_coll_bcast", topo)
    ctx.comm.bcast = topo
    NT = nranks * 2
    # distinct source and sink collections: a sink must not alias the
    # root's tile through two flows
    V = VectorTwoDimCyclic(mb=4, lm=NT * 4, nodes=nranks, myrank=rank,
                           name="V")
    W = VectorTwoDimCyclic(mb=4, lm=NT * 4, nodes=nranks, myrank=rank,
                           name="W")
    for m, _ in V.local_tiles():
        V.data_of(m).copy_on(0).payload[:] = 0.0
    for m, _ in W.local_tiles():
        W.data_of(m).copy_on(0).payload[:] = 0.0

    p = PTG("bcast", NT=NT)
    p.task("ROOT", z=Range(0, 0)) \
        .affinity(lambda V=V: V(0)) \
        .flow("T", "RW",
              IN(DATA(lambda V=V: V(0))),
              OUT(TASK("SINK", "T",
                       lambda NT=NT: [dict(i=i) for i in range(NT)]))) \
        .body(lambda T: T + 42.0)
    p.task("SINK", i=Range(0, NT - 1)) \
        .affinity(lambda i, W=W: W(i)) \
        .flow("T", "READ", IN(TASK("ROOT", "T", lambda: dict(z=0)))) \
        .flow("O", "RW", IN(DATA(lambda i, W=W: W(i))),
              OUT(DATA(lambda i, W=W: W(i)))) \
        .body(lambda T, O: {"O": np.asarray(O) + np.asarray(T)})
    ctx.add_taskpool(p.build())
    ctx.wait()
    vals = {}
    for m, _ in W.local_tiles():
        vals[m] = float(np.asarray(W.data_of(m).pull_to_host().payload)[0])
    return vals


@pytest.mark.parametrize("topo", ["star", "chain", "binomial"])
def test_broadcast_topologies(topo):
    results = run_distributed(_bcast, 3, args=(topo,))
    merged = {}
    for r in results:
        merged.update(r)
    assert merged == {i: 42.0 for i in range(6)}


# -- rendezvous GET for large payloads --------------------------------------

def _rendezvous(ctx, rank, nranks):
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    ctx.comm.eager = 16   # force the GET path for any real tile
    NT = 4
    V = VectorTwoDimCyclic(mb=256, lm=NT * 256, nodes=nranks, myrank=rank)
    for m, _ in V.local_tiles():
        V.data_of(m).copy_on(0).payload[:] = float(m)

    p = PTG("rdv", NT=NT)
    p.task("S", k=Range(0, NT - 1)) \
        .affinity(lambda k, V=V: V(k)) \
        .flow("T", "RW",
              IN(DATA(lambda k, V=V: V(k)), when=lambda k: k == 0),
              IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("S", "T", lambda k, NT=NT: dict(k=k + 1)),
                  when=lambda k, NT=NT: k < NT - 1),
              OUT(DATA(lambda k, V=V: V(k)))) \
        .body(lambda T: T + 1.0)
    ctx.add_taskpool(p.build())
    ctx.wait()
    out = {}
    for m, _ in V.local_tiles():
        out[m] = float(np.asarray(V.data_of(m).pull_to_host().payload)[0])
    return out


def test_rendezvous_get_path():
    results = run_distributed(_rendezvous, 2)
    merged = {}
    for r in results:
        merged.update(r)
    # chain carries tile 0's value (0.0) forward, +1 per hop
    assert merged == {k: float(k + 1) for k in range(4)}


# -- distributed tiled GEMM (reference: the DPLASMA-style driver) -----------

def _seed(name, m, n):
    # deterministic across processes (str hash() is randomized per run)
    return (ord(name[0]) * 10007 + m * 101 + n) % (2**31)


def _dist_gemm(ctx, rank, nranks):
    from parsec_tpu.apps.gemm import gemm_taskpool
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    mt = nt = kt = 4
    mb = 8
    P = 2
    mk = dict(nodes=nranks, myrank=rank, P=P)

    def fill(M):
        for m, n in M.local_tiles():
            rng = np.random.default_rng(_seed(M.name, m, n))
            M.data_of(m, n).copy_on(0).payload[:] = \
                rng.standard_normal((mb, mb)).astype(np.float32)

    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mt * mb, ln=kt * mb, name="A",
                          **mk)
    B = TwoDimBlockCyclic(mb=mb, nb=mb, lm=kt * mb, ln=nt * mb, name="B",
                          **mk)
    C = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mt * mb, ln=nt * mb, name="C",
                          **mk)
    for M in (A, B, C):
        fill(M)
    ctx.add_taskpool(gemm_taskpool(A, B, C, device="cpu"))
    ctx.wait()

    # every rank can rebuild the GLOBAL inputs deterministically and
    # check its local C tiles against the numpy answer
    def full(name, rows, cols):
        out = np.zeros((rows * mb, cols * mb), np.float32)
        for m in range(rows):
            for n in range(cols):
                rng = np.random.default_rng(_seed(name, m, n))
                out[m * mb:(m + 1) * mb, n * mb:(n + 1) * mb] = \
                    rng.standard_normal((mb, mb)).astype(np.float32)
        return out
    want = full("C", mt, nt) + full("A", mt, kt) @ full("B", kt, nt)
    for m, n in C.local_tiles():
        got = np.asarray(C.data_of(m, n).pull_to_host().payload)
        np.testing.assert_allclose(
            got, want[m * mb:(m + 1) * mb, n * mb:(n + 1) * mb],
            rtol=1e-3, atol=1e-3)
    return len(C.local_tiles())


def test_distributed_gemm_4ranks():
    counts = run_distributed(_dist_gemm, 4, timeout=180)
    assert sum(counts) == 16   # every C tile verified somewhere


# -- funnelled comm thread: many small messages (reference: the comm
# thread + dep_cmd_queue, remote_dep_mpi.c:461-503) ------------------------

def _many_small_msgs(ctx, rank, nranks):
    """A long cross-rank dependency chain of tiny payloads: every edge is
    one small message through the funnelled progress thread, stressing
    enqueue ordering and per-peer send aggregation."""
    import numpy as np
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.dtd import DTDTaskpool, AFFINITY, INOUT

    V = VectorTwoDimCyclic(mb=2, lm=2, nodes=nranks, myrank=rank)
    if rank == 0:
        V.data_of(0).copy_on(0).payload[:] = 0.0
    tp = DTDTaskpool("stress")
    ctx.add_taskpool(tp)
    ctx.start()
    t = tp.tile_of(V, 0)
    steps = 240
    for i in range(steps):
        tp.insert_task(lambda T: T + 1.0, (t, INOUT),
                       (i % nranks, AFFINITY))
    tp.wait(timeout=120)
    ctx.wait(timeout=120)
    if rank == 0:
        val = np.asarray(V.data_of(0).pull_to_host().payload)
        np.testing.assert_allclose(val, float(steps))
    # short-circuit memcpy: a local copy thread-shifted onto the comm
    # progress thread (reference: parsec_remote_dep_memcpy)
    import time
    from parsec_tpu.data.data import new_data
    src = new_data(np.full(4, 7.0, np.float32)).copy_on(0)
    dst = new_data(np.zeros(4, np.float32)).copy_on(0)
    ctx.comm.memcpy_shift(dst, src)
    deadline = time.monotonic() + 10
    while not np.allclose(np.asarray(dst.payload), 7.0):
        if time.monotonic() > deadline:
            raise TimeoutError("memcpy_shift never landed")
        time.sleep(0.01)
    return "ok"


def test_funnelled_many_small_messages():
    assert run_distributed(_many_small_msgs, 3, timeout=240) == ["ok"] * 3


# -- CE one-sided put/get over registered memory (reference:
# dtd_test_ce.c drives the comm-engine vtable directly: AM + put/get;
# mpi_no_thread_put:793 / get:896) -----------------------------------------

def _ce_onesided(ctx, rank, nranks):
    import threading
    import numpy as np
    ce = ctx.comm.ce
    assert ce.CAP_ONESIDED and ce.CAP_MT
    # each rank registers a region; peers write and read it one-sidedly
    mine = np.zeros(8, np.float32)
    rid = ce.mem_register(mine)
    # exchange region ids (they happen to be equal, but don't assume)
    rids = [None] * nranks
    got_rids = threading.Event()
    from parsec_tpu.comm.engine import TAG_USER

    def rid_cb(src, payload):
        rids[src] = payload
        if all(r is not None for r in rids):
            got_rids.set()

    ce.tag_register(TAG_USER, rid_cb)
    ce.barrier()
    for r in range(nranks):
        ce.send_am(TAG_USER, r, rid)
    assert got_rids.wait(30)

    # PUT: write my pattern into my right neighbor's region
    right = (rank + 1) % nranks
    acked = threading.Event()
    errs = []
    ce.put(right, np.full(8, 10.0 + rank, np.float32), rids[right],
           on_complete=lambda err=None: (errs.append(err) if err else None,
                                         acked.set()))
    assert acked.wait(30)
    assert not errs, errs
    ce.barrier()
    np.testing.assert_allclose(mine, 10.0 + (rank - 1) % nranks)

    # GET: read my left neighbor's region back
    left = (rank - 1) % nranks
    box = {}
    fetched = threading.Event()

    def on_data(arr):
        box["arr"] = arr
        fetched.set()

    ce.get(left, rids[left], on_data)
    assert fetched.wait(30)
    np.testing.assert_allclose(box["arr"], 10.0 + (left - 1) % nranks)
    ce.barrier()
    ce.mem_unregister(rid)
    return "ok"


def test_ce_onesided_put_get():
    assert run_distributed(_ce_onesided, 3) == ["ok"] * 3


# -- remote reshape: the pre-send conversion path (reference:
# parsec_reshape.c remote paths; tests/collections/reshape/) ---------------

def _remote_reshape(ctx, rank, nranks):
    import ml_dtypes
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.data.reshape import Dtt
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK

    NT = 4
    V = VectorTwoDimCyclic(mb=8, lm=8 * NT, nodes=nranks, myrank=rank)
    W = VectorTwoDimCyclic(mb=8, lm=8 * NT, nodes=nranks, myrank=rank,
                           name="W")
    for m, _ in V.local_tiles():
        V.data_of(m).copy_on(0).payload[:] = 1.5 + m
    for m, _ in W.local_tiles():
        W.data_of(m).copy_on(0).payload[:] = 0.0
    seen = {}

    bf16 = Dtt(dtype=ml_dtypes.bfloat16, name="bf16")
    p = PTG("rrs", NT=NT)
    # P(k) on V(k)'s rank ships its tile to C(k) on W(k+1 mod NT)'s rank
    # with a bf16 edge dtt: the CONVERTED payload travels (half the
    # bytes), and the consumer observes bf16
    p.task("P", k=Range(0, NT - 1)) \
        .affinity(lambda k, V=V: V(k)) \
        .flow("T", "READ",
              IN(DATA(lambda k, V=V: V(k))),
              OUT(TASK("C", "T", lambda k: dict(k=k)), dtt=bf16)) \
        .body(lambda: None)
    p.task("C", k=Range(0, NT - 1)) \
        .affinity(lambda k, W=W, NT=NT: W((k + 1) % NT)) \
        .flow("T", "READ", IN(TASK("P", "T", lambda k: dict(k=k)))) \
        .flow("O", "RW",
              IN(DATA(lambda k, W=W, NT=NT: W((k + 1) % NT))),
              OUT(DATA(lambda k, W=W, NT=NT: W((k + 1) % NT)))) \
        .body(lambda T, O, k, seen=seen: (
            seen.__setitem__(k, str(np.asarray(T).dtype)),
            np.asarray(T).astype(np.float32) * 2.0)[1])
    ctx.add_taskpool(p.build())
    ctx.wait(timeout=120)
    for m, _ in W.local_tiles():
        k = (m - 1) % NT
        got = np.asarray(W.data_of(m).pull_to_host().payload)
        expect = 2.0 * np.asarray(
            np.full(8, 1.5 + k, np.float32).astype(ml_dtypes.bfloat16),
            dtype=np.float32)
        np.testing.assert_allclose(got, expect)
    # every consumer this rank ran saw a bf16 payload
    assert all(dt == "bfloat16" for dt in seen.values()), seen
    return "ok"


def test_remote_presend_reshape():
    assert run_distributed(_remote_reshape, 2) == ["ok"] * 2


# -- 8-rank scale (the north-star scaling axis, SURVEY §6: 8 -> 256
# chips; here 8 processes on one node per the reference's test strategy) ----

def _scale8(ctx, rank, nranks):
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    NT = nranks * 3
    V = VectorTwoDimCyclic(mb=4, lm=NT * 4, nodes=nranks, myrank=rank)
    for m, _ in V.local_tiles():
        V.data_of(m).copy_on(0).payload[:] = 0.0
    p = PTG("scale", NT=NT)
    p.task("S", k=Range(0, NT - 1)) \
        .affinity(lambda k, V=V: V(k)) \
        .flow("T", "RW",
              IN(DATA(lambda k, V=V: V(k)), when=lambda k: k == 0),
              IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("S", "T", lambda k: dict(k=k + 1)),
                  when=lambda k, NT=NT: k < NT - 1),
              OUT(DATA(lambda k, V=V: V(k)))) \
        .body(lambda T: T + 1.0)
    ctx.add_taskpool(p.build())
    ctx.wait(timeout=180)
    out = {}
    for m, _ in V.local_tiles():
        out[m] = float(np.asarray(V.data_of(m).pull_to_host().payload)[0])
    return out


def test_chain_8_ranks():
    results = run_distributed(_scale8, 8, timeout=300)
    merged = {}
    for r in results:
        merged.update(r)
    assert merged == {k: float(k + 1) for k in range(24)}


# -- failure detection: a dying peer fails waiters fast ---------------------

def _survivor_proc(rank, nranks, port_base, outq):
    """Standalone 2-rank harness (not run_distributed: its epilogue
    barrier would entangle the failure we are injecting)."""
    import os
    import time
    os.environ["JAX_PLATFORMS"] = "cpu"
    from parsec_tpu.comm.engine import SocketCE
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.core.context import Context
    ce = SocketCE(rank, nranks, port_base)
    ctx = Context(nb_cores=1, rank=rank, nranks=nranks)
    rde = RemoteDepEngine(ce, ctx)
    ce.barrier()
    if rank == 1:
        os._exit(17)              # crash without goodbye
    # rank 0: the loss must surface as a recorded ConnectionError AND
    # fail a barrier fast (well under its 60s timeout)
    t0 = time.monotonic()
    deadline = t0 + 60            # generous under 1-core suite load
    while not ctx._errors:
        if time.monotonic() > deadline:
            outq.put(("timeout", None, -1.0))
            return
        time.sleep(0.02)
    kind = type(ctx._errors[0][0]).__name__
    try:
        ce.barrier(timeout=60)
        bar = "no-error"
    except ConnectionError:
        bar = "connection-error"
    except TimeoutError:
        bar = "timeout"
    outq.put((kind, bar, time.monotonic() - t0))


def test_peer_death_detection():
    """_peer_lost records a ConnectionError on the survivor and wakes
    barrier waiters with a cause — removing the detection makes this
    time out, not pass vacuously."""
    import multiprocessing as mp
    from parsec_tpu.comm.launch import _probe_port_base
    mpctx = mp.get_context("spawn")
    outq = mpctx.Queue()
    base = _probe_port_base(2)
    procs = [mpctx.Process(target=_survivor_proc, args=(r, 2, base, outq),
                           daemon=True)
             for r in range(2)]
    for p in procs:
        p.start()
    kind, bar, dt = outq.get(timeout=120)
    for p in procs:
        p.join(timeout=10)
        if p.is_alive():
            p.terminate()
    # PR 5: peer loss surfaces as the structured PeerFailedError (a
    # ConnectionError subclass carrying the dead rank)
    assert kind in ("ConnectionError", "PeerFailedError"), kind
    assert bar == "connection-error", bar
    # the point is beating the 60s barrier timeout, with headroom for
    # a loaded 1-core host (the old 30s bound flaked under full-suite
    # contention — and its timeout branch put a 2-tuple the unpack
    # above crashed on)
    assert dt < 45, f"loss surfaced too slowly ({dt:.1f}s)"


# -- multi-host address book (the DCN deployment path) ----------------------

def _hosts_chain(ctx, rank, nranks):
    # same chain as _ce_echo but through the comm_hosts address book
    import threading
    from parsec_tpu.comm.engine import TAG_USER
    assert ctx.comm.ce._hosts == ["127.0.0.1"] * nranks
    got = threading.Event()
    ce = ctx.comm.ce
    ce.tag_register(TAG_USER, lambda src, p: got.set())
    ce.barrier()
    ce.send_am(TAG_USER, (rank + 1) % nranks, "hi")
    assert got.wait(30)
    ce.barrier()
    return "ok"


def test_multihost_address_book():
    import os
    os.environ["PARSEC_COMM_HOSTS"] = "127.0.0.1,127.0.0.1,127.0.0.1"
    try:
        assert run_distributed(_hosts_chain, 3) == ["ok"] * 3
    finally:
        del os.environ["PARSEC_COMM_HOSTS"]
    from parsec_tpu.comm.engine import SocketCE
    with pytest.raises(ValueError, match="2 hosts for 3"):
        os.environ["PARSEC_COMM_HOSTS"] = "a,b"
        try:
            SocketCE(0, 3, port_base=29123)
        finally:
            del os.environ["PARSEC_COMM_HOSTS"]


def _dist_qr(ctx, rank, nranks):
    # tiled QR across ranks: validates the compact-WY TSQRT/TSMQR
    # kernels' edge payloads (V/T^T pairs) riding the remote-dep
    # protocol (VERDICT r2 #4: QR at POTRF parity)
    from parsec_tpu.apps.qr import qr_taskpool
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    nt, mb, P = 4, 8, 2
    n = nt * mb
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, name="Q",
                          nodes=nranks, myrank=rank, P=P)
    for m, nn in A.local_tiles():
        rng = np.random.default_rng(_seed("Q", m, nn))
        A.data_of(m, nn).copy_on(0).payload[:] = \
            rng.standard_normal((mb, mb)).astype(np.float32)
    ctx.add_taskpool(qr_taskpool(A, device="cpu"))
    ctx.wait()
    # rebuild the global input; R must be upper-triangular with
    # |R| matching the true QR's |R| (signs are convention-dependent)
    full = np.zeros((n, n), np.float32)
    for m in range(nt):
        for nn in range(nt):
            rng = np.random.default_rng(_seed("Q", m, nn))
            full[m * mb:(m + 1) * mb, nn * mb:(nn + 1) * mb] = \
                rng.standard_normal((mb, mb)).astype(np.float32)
    want = np.abs(np.linalg.qr(full, mode="r"))
    checked = 0
    for m, nn in A.local_tiles():
        got = np.asarray(A.data_of(m, nn).pull_to_host().payload)
        blk = slice(m * mb, (m + 1) * mb), slice(nn * mb, (nn + 1) * mb)
        if m > nn:
            np.testing.assert_allclose(got, 0.0, atol=1e-3)
        elif m == nn:
            np.testing.assert_allclose(np.abs(np.triu(got)),
                                       want[blk], rtol=2e-2, atol=2e-2)
            np.testing.assert_allclose(np.tril(got, -1), 0.0, atol=1e-3)
        else:
            # above-diagonal R block: |R| matches up to per-row signs
            np.testing.assert_allclose(np.abs(got), want[blk],
                                       rtol=2e-2, atol=2e-2)
        checked += 1
    return checked


def test_distributed_qr_4ranks():
    counts = run_distributed(_dist_qr, 4, timeout=180)
    assert sum(counts) == 16   # every tile verified somewhere


def test_chain_16_ranks():
    """16-rank smoke: the address book, handshake, and chain dataflow
    hold at 2x the prior scale (VERDICT r2 #9 scale-axis hardening)."""
    results = run_distributed(_scale8, 16, timeout=420, nb_cores=1)
    merged = {}
    for r in results:
        merged.update(r)
    assert merged == {k: float(k + 1) for k in range(48)}


# -- wire-format guard (VERDICT r2 #9): a bad peer fails its connection,
# not the recv thread ------------------------------------------------------

def _wire_guard_victim(outq, port_base):
    import os
    import socket
    import struct
    import time
    os.environ["JAX_PLATFORMS"] = "cpu"
    from parsec_tpu.comm.engine import (SocketCE, TAG_USER, _HANDSHAKE,
                                        _LEN, _WIRE_MAGIC, _WIRE_VERSION)
    from parsec_tpu.utils.mca import params
    params.set("comm_max_frame_mb", 1)
    errors = []
    got = []
    ce = SocketCE(0, 3, port_base=port_base)
    ce.on_error = errors.append
    ce.tag_register(TAG_USER, lambda src, p: got.append((src, p)))

    def dial(rank, magic=_WIRE_MAGIC, version=_WIRE_VERSION):
        s = socket.create_connection(("127.0.0.1", port_base), timeout=10)
        s.sendall(_HANDSHAKE.pack(magic, version, rank))
        return s

    # 1) cross-version peer: rejected at handshake, no peer registered
    bad = dial(1, version=99)
    time.sleep(0.3)
    handshake_rejected = 1 not in ce._peers

    # 2) well-behaved peer 1 sends a valid frame...
    good = dial(1)
    import pickle
    body = pickle.dumps("hello")
    good.sendall(_LEN.pack(TAG_USER, len(body), 0) + body)
    # 3) ...peer 2 handshakes fine, then sends an absurd length field
    evil = dial(2)
    evil.sendall(_LEN.pack(TAG_USER, 1 << 40, 0))
    time.sleep(0.5)
    # 4) and peer 1 can STILL talk (its recv loop was untouched)
    body2 = pickle.dumps("again")
    good.sendall(_LEN.pack(TAG_USER, len(body2), 0) + body2)
    deadline = time.monotonic() + 10
    while len(got) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    outq.put({
        "handshake_rejected": handshake_rejected,
        "got": list(got),
        "dead": sorted(ce.dead_peers),
        "errors": [type(e).__name__ for e in errors],
    })
    # 5) a corrupt (unpicklable) frame from ANOTHER peer also severs
    # only its sender, and the surviving peer still delivers afterwards
    evil2 = dial(3)
    garbage = b"\x00\xde\xad\xbe\xef not a pickle"
    evil2.sendall(_LEN.pack(TAG_USER, len(garbage), 0) + garbage)
    body3 = pickle.dumps("still-here")
    good.sendall(_LEN.pack(TAG_USER, len(body3), 0) + body3)
    deadline = time.monotonic() + 10
    while (len(got) < 3 or 3 not in ce.dead_peers) \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    outq.put({
        "got": list(got),
        "dead": sorted(ce.dead_peers),
    })
    for s in (bad, good, evil, evil2):
        try:
            s.close()
        except OSError:
            pass
    ce.fini()


def test_wire_format_guard():
    import multiprocessing as mp
    from parsec_tpu.comm.launch import _probe_port_base
    mpctx = mp.get_context("spawn")
    outq = mpctx.Queue()
    base = _probe_port_base(1)
    p = mpctx.Process(target=_wire_guard_victim, args=(outq, base),
                      daemon=True)
    p.start()
    res = outq.get(timeout=120)
    res2 = outq.get(timeout=120)
    p.join(timeout=15)
    if p.is_alive():
        p.terminate()
    assert res["handshake_rejected"], "cross-version peer was accepted"
    # the oversized frame severed ONLY rank 2's connection, with a cause
    assert 2 in res["dead"], res
    assert any(e in ("ConnectionError", "PeerFailedError")
               for e in res["errors"]), res
    # the well-behaved peer's messages all arrived, before AND after
    assert [m for _s, m in res["got"]] == ["hello", "again"], res
    # the unpicklable frame severed rank 3; the good peer kept talking
    assert 3 in res2["dead"], res2
    assert [m for _s, m in res2["got"]][-1] == "still-here", res2


# -- reshape-corpus remote cases (VERDICT r4 missing #3; reference:
# tests/collections/reshape/remote_read_reshape.jdf + remote_no_re_reshape
# + the NEW-typed remote case) ---------------------------------------------

def _remote_consumer_reshape(ctx, rank, nranks):
    """Receiver-side IN dtt on a remote edge: the payload crosses the
    wire in the producer's type; the CONSUMER's datatype lookup converts
    on arrival (reference: remote_dep_get_datatypes)."""
    import ml_dtypes
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.data.reshape import Dtt
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, TASK
    bf = np.dtype(ml_dtypes.bfloat16)
    V = VectorTwoDimCyclic(mb=4, lm=4 * nranks, nodes=nranks, myrank=rank)
    if rank == 0:
        V.data_of(0).copy_on(0).payload[:] = 3.0
    seen = {}
    p = PTG("rcr")
    p.task("P") \
        .affinity(lambda V=V: V(0)) \
        .flow("X", "READ",
              IN(DATA(lambda V=V: V(0))),
              OUT(TASK("C", "X", lambda: dict()))) \
        .body(lambda: None)
    p.task("C") \
        .affinity(lambda V=V: V(1)) \
        .flow("X", "READ",
              IN(TASK("P", "X", lambda: dict()), dtt=Dtt(dtype=bf))) \
        .body(lambda X: seen.update(dtype=str(np.asarray(X).dtype),
                                    val=float(np.asarray(X)[0])))
    ctx.add_taskpool(p.build())
    ctx.wait(timeout=120)
    return seen


def test_remote_consumer_side_reshape():
    res = run_distributed(_remote_consumer_reshape, 2)
    assert res[1] == {"dtype": "bfloat16", "val": 3.0}


def _remote_no_re_reshape(ctx, rank, nranks):
    """OUT dtt and IN dtt name the SAME type on a remote edge: the
    presend conversion must satisfy the receiver without a second
    conversion (reference: remote_no_re_reshape.jdf)."""
    import ml_dtypes
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.data.reshape import Dtt
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, TASK
    bf = np.dtype(ml_dtypes.bfloat16)
    V = VectorTwoDimCyclic(mb=4, lm=4 * nranks, nodes=nranks, myrank=rank)
    if rank == 0:
        V.data_of(0).copy_on(0).payload[:] = 5.0
    seen = {}
    p = PTG("rnr")
    p.task("P") \
        .affinity(lambda V=V: V(0)) \
        .flow("X", "READ",
              IN(DATA(lambda V=V: V(0))),
              OUT(TASK("C", "X", lambda: dict()), dtt=Dtt(dtype=bf))) \
        .body(lambda: None)
    p.task("C") \
        .affinity(lambda V=V: V(1)) \
        .flow("X", "READ",
              IN(TASK("P", "X", lambda: dict()), dtt=Dtt(dtype=bf))) \
        .body(lambda X: seen.update(dtype=str(np.asarray(X).dtype),
                                    val=float(np.asarray(X)[0])))
    tp = p.build()
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    # receiver-side: the arrived payload is ALREADY bf16, so the IN dtt
    # must not convert again
    return {"seen": seen, "conv": tp.reshape.conversions}


def test_remote_no_re_reshape():
    res = run_distributed(_remote_no_re_reshape, 2)
    assert res[1]["seen"] == {"dtype": "bfloat16", "val": 5.0}
    assert res[1]["conv"] == 0      # consumer rank: no re-reshape


def _remote_new_flow_reshape(ctx, rank, nranks):
    """A NEW-flow arena temporary crossing ranks with a consumer-side
    dtt: the reference's remote reshape-into-NEW case."""
    import ml_dtypes
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.data.reshape import Dtt
    from parsec_tpu.dsl.ptg.api import IN, NEW, OUT, PTG, TASK
    bf = np.dtype(ml_dtypes.bfloat16)
    V = VectorTwoDimCyclic(mb=4, lm=4 * nranks, nodes=nranks, myrank=rank)
    seen = {}
    p = PTG("rnew")
    p.arena("scratch", (4,), np.float32)

    def produce(X):
        X[:] = np.arange(4, dtype=np.float32) + 1.0
    p.task("P") \
        .affinity(lambda V=V: V(0)) \
        .flow("X", "RW",
              IN(NEW("scratch")),
              OUT(TASK("C", "X", lambda: dict()))) \
        .body(produce)
    p.task("C") \
        .affinity(lambda V=V: V(1)) \
        .flow("X", "READ",
              IN(TASK("P", "X", lambda: dict()), dtt=Dtt(dtype=bf))) \
        .body(lambda X: seen.update(
            dtype=str(np.asarray(X).dtype),
            vals=[float(v) for v in np.asarray(X).astype(np.float32)]))
    ctx.add_taskpool(p.build())
    ctx.wait(timeout=120)
    return seen


def test_remote_new_flow_reshape():
    res = run_distributed(_remote_new_flow_reshape, 2)
    assert res[1] == {"dtype": "bfloat16", "vals": [1.0, 2.0, 3.0, 4.0]}


def _remote_multi_outs_worker(ctx, rank, nranks):
    """Reference corpus: remote_multiple_outs_same_pred_flow.jdf — ONE
    predecessor flow with SEVERAL differently-typed outputs shipped
    remotely: each remote consumer declares its own edge dtt, so the
    same produced payload travels twice in two different wire types."""
    import ml_dtypes
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.data.reshape import Dtt
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, TASK
    bf = np.dtype(ml_dtypes.bfloat16)
    half = Dtt(transform=lambda a: a * 0.5, inverse=lambda a: a * 2.0,
               name="half")
    V = VectorTwoDimCyclic(mb=4, lm=4 * nranks, nodes=nranks, myrank=rank)
    if rank == 0:
        V.data_of(0).copy_on(0).payload[:] = 6.0
    seen = {}
    p = PTG("rmo")
    p.task("P") \
        .affinity(lambda V=V: V(0)) \
        .flow("X", "READ",
              IN(DATA(lambda V=V: V(0))),
              OUT(TASK("CB", "X", lambda: dict()), dtt=Dtt(dtype=bf)),
              OUT(TASK("CH", "X", lambda: dict()), dtt=half)) \
        .body(lambda: None)
    # consumers take each edge's wire type as shipped (the corpus case
    # declares the types on the PRODUCER's outputs; an IN re-declaring
    # the transform would mean "convert again")
    p.task("CB") \
        .affinity(lambda V=V: V(1)) \
        .flow("X", "READ",
              IN(TASK("P", "X", lambda: dict()))) \
        .body(lambda X: seen.update(b_dtype=str(np.asarray(X).dtype),
                                    b_val=float(np.asarray(X)[0])))
    p.task("CH") \
        .affinity(lambda V=V: V(1)) \
        .flow("X", "READ",
              IN(TASK("P", "X", lambda: dict()))) \
        .body(lambda X: seen.update(h_dtype=str(np.asarray(X).dtype),
                                    h_val=float(np.asarray(X)[0])))
    ctx.add_taskpool(p.build())
    ctx.wait(timeout=120)
    return seen


def test_remote_multiple_outs_same_pred_flow():
    res = run_distributed(_remote_multi_outs_worker, 2)
    assert res[1] == {"b_dtype": "bfloat16", "b_val": 6.0,
                      "h_dtype": "float32", "h_val": 3.0}


def _remote_multi_outs_multi_deps_worker(ctx, rank, nranks):
    """Reference corpus: remote_multiple_outs_same_pred_flow_multiple_
    deps.jdf — the SAME predecessor flow additionally fans a RANGE dep
    over several instances of one remote consumer class (its own dtt)
    next to the differently-typed single deps, all shipped remotely."""
    import ml_dtypes
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.data.reshape import Dtt
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    bf = np.dtype(ml_dtypes.bfloat16)
    V = VectorTwoDimCyclic(mb=4, lm=4 * nranks, nodes=nranks, myrank=rank)
    if rank == 0:
        V.data_of(0).copy_on(0).payload[:] = 8.0
    seen = {}
    p = PTG("rmomd", N=2)
    p.task("P") \
        .affinity(lambda V=V: V(0)) \
        .flow("X", "READ",
              IN(DATA(lambda V=V: V(0))),
              OUT(TASK("CB", "X", lambda: dict()), dtt=Dtt(dtype=bf)),
              OUT(TASK("CR", "X",
                       lambda: [dict(i=i) for i in range(2)]),
                  dtt=Dtt(transform=lambda a: a + 1.0,
                          inverse=lambda a: a - 1.0, name="p1"))) \
        .body(lambda: None)
    p.task("CB") \
        .affinity(lambda V=V: V(1)) \
        .flow("X", "READ",
              IN(TASK("P", "X", lambda: dict()))) \
        .body(lambda X: seen.update(b_dtype=str(np.asarray(X).dtype),
                                    b_val=float(np.asarray(X)[0])))

    def cr_body(X, i):
        seen[f"r{i}"] = float(np.asarray(X)[0])
    p.task("CR", i=Range(0, 1)) \
        .affinity(lambda i, V=V: V(1)) \
        .flow("X", "READ",
              IN(TASK("P", "X", lambda i: dict()))) \
        .body(cr_body)
    ctx.add_taskpool(p.build())
    ctx.wait(timeout=120)
    return seen


def test_remote_multiple_outs_same_pred_flow_multiple_deps():
    res = run_distributed(_remote_multi_outs_multi_deps_worker, 2)
    assert res[1] == {"b_dtype": "bfloat16", "b_val": 8.0,
                      "r0": 9.0, "r1": 9.0}
