"""Event-loop transport edge cases (comm/engine.py EventLoopCE).

The tentpole contract tests: partial-write resume under a starved
SO_SNDBUF, interleaved out-of-band payloads from two peers on one loop,
peer death mid-frame failing the connection WITH a cause (engine.py's
documented contract), the eager-race rendezvous-handle purge path, the
adaptive eager threshold's feedback rules, activation coalescing, and a
tier-1-safe loopback stress over mixed eager+rendezvous traffic.
In-process cases run several EventLoopCEs in one process (each owns its
own loop thread + listener), so they cost no spawn overhead.
"""

import socket
import threading
import time

import numpy as np
import pytest

from parsec_tpu.comm.engine import (_HANDSHAKE, _LEN, _WIRE_MAGIC,
                                    _WIRE_VERSION, EventLoopCE, SocketCE,
                                    TAG_USER, make_ce)
from parsec_tpu.comm.launch import _probe_port_base, run_distributed
from parsec_tpu.utils.mca import params


def _mk_pair(n=2, **kw):
    base = _probe_port_base(n)
    ces = [EventLoopCE(r, n, base) for r in range(n)]
    return base, ces


def _fini(ces):
    for ce in ces:
        ce.fini()


# -- partial-write resume under a full send buffer --------------------------

def test_partial_write_resume_tiny_sndbuf():
    """A send buffer far smaller than the frame forces the loop through
    EPOLLOUT partial-write resume; every byte must still land, in
    order."""
    params.set("comm_sockbuf_bytes", 8192)
    try:
        _, (ce0, ce1) = _mk_pair(2)
    finally:
        params.unset("comm_sockbuf_bytes")
    try:
        got = []
        evt = threading.Event()

        def cb(src, msg):
            got.append(msg)
            if len(got) == 4:
                evt.set()

        ce0.tag_register(TAG_USER, cb)
        arrays = [np.arange(256 * 1024, dtype=np.float32) + i
                  for i in range(4)]
        for i, a in enumerate(arrays):
            ce1.send_am(TAG_USER, 0, {"i": i, **ce1.pack(a)})
        assert evt.wait(30), f"only {len(got)}/4 frames arrived"
        # in-order arrival with intact payloads
        assert [m["i"] for m in got] == [0, 1, 2, 3]
        for i, m in enumerate(got):
            np.testing.assert_array_equal(ce0.unpack(m), arrays[i])
        # the tiny SNDBUF actually exercised the resume path
        assert ce1.stats.partial_writes > 0
        assert not ce0.dead_peers and not ce1.dead_peers
    finally:
        _fini([ce0, ce1])


# -- interleaved out-of-band payloads from two peers ------------------------

def test_interleaved_oob_payloads_two_peers():
    """Two peers stream large out-of-band frames at one receiver loop
    concurrently; the per-peer incremental parsers must not cross."""
    _, ces = _mk_pair(3)
    ce0, ce1, ce2 = ces
    try:
        got = {1: [], 2: []}
        lock = threading.Lock()
        evt = threading.Event()

        def cb(src, msg):
            with lock:
                got[src].append(msg)
                if sum(len(v) for v in got.values()) == 12:
                    evt.set()

        ce0.tag_register(TAG_USER, cb)

        def blast(ce, tag_base):
            for i in range(6):
                a = np.full(128 * 1024, tag_base * 100 + i, np.float32)
                ce.send_am(TAG_USER, 0, {"seq": i, "from": tag_base,
                                         **ce.pack(a)})

        t1 = threading.Thread(target=blast, args=(ce1, 1))
        t2 = threading.Thread(target=blast, args=(ce2, 2))
        t1.start(); t2.start(); t1.join(); t2.join()
        assert evt.wait(30), f"got {[len(v) for v in got.values()]}"
        for src in (1, 2):
            assert [m["seq"] for m in got[src]] == list(range(6))
            for m in got[src]:
                arr = ce0.unpack(m)
                assert arr.shape == (128 * 1024,)
                np.testing.assert_array_equal(
                    arr, np.full(128 * 1024, src * 100 + m["seq"],
                                 np.float32))
    finally:
        _fini(ces)


# -- peer death mid-frame: the connection fails WITH a cause ----------------

def test_peer_death_mid_frame_cause():
    base = _probe_port_base(1)
    ce = EventLoopCE(0, 2, base)
    errors = []
    ce.on_error = errors.append
    try:
        s = socket.create_connection(("127.0.0.1", base), timeout=10)
        s.sendall(_HANDSHAKE.pack(_WIRE_MAGIC, _WIRE_VERSION, 1))
        # a frame header promising 4096 body bytes, then death after 100
        s.sendall(_LEN.pack(TAG_USER, 4096, 0) + b"x" * 100)
        time.sleep(0.3)
        s.close()
        deadline = time.monotonic() + 10
        while 1 not in ce.dead_peers and time.monotonic() < deadline:
            time.sleep(0.02)
        assert 1 in ce.dead_peers
        assert errors and isinstance(errors[0], ConnectionError)
        assert "mid-frame" in str(errors[0]), errors[0]
    finally:
        ce.fini()


def test_clean_close_between_frames_no_midframe_cause():
    """A peer closing at a frame boundary is a plain disconnect — the
    mid-frame cause must not fire spuriously."""
    base = _probe_port_base(1)
    ce = EventLoopCE(0, 2, base)
    errors = []
    ce.on_error = errors.append
    try:
        import pickle
        s = socket.create_connection(("127.0.0.1", base), timeout=10)
        s.sendall(_HANDSHAKE.pack(_WIRE_MAGIC, _WIRE_VERSION, 1))
        body = pickle.dumps("bye")
        s.sendall(_LEN.pack(TAG_USER, len(body), 0) + body)
        time.sleep(0.3)
        s.close()
        deadline = time.monotonic() + 10
        while 1 not in ce.dead_peers and time.monotonic() < deadline:
            time.sleep(0.02)
        assert 1 in ce.dead_peers
        assert errors and "mid-frame" not in str(errors[0])
    finally:
        ce.fini()


# -- eager-race rendezvous-handle purge path --------------------------------

def _purged_handle_worker(ctx, rank, nranks):
    """A GET arriving after the serving rank purged (or never had) the
    handle must fail the RECEIVER with a clear miss, not the server."""
    import time
    from parsec_tpu.comm.engine import TAG_GET_REQ
    rde = ctx.comm
    rde.ce.barrier()
    if rank == 1:
        # fake a pending rendezvous pull whose handle rank 0 never
        # serves (the eager race: sender purged it before our GET)
        rde._pending_gets[(0, 987654)] = {"tp": None, "deliveries": []}
        rde._send_app(TAG_GET_REQ, 0, {"handle": 987654, "from": 1})
        deadline = time.monotonic() + 30
        while not ctx._errors:
            if time.monotonic() > deadline:
                return "no-error"
            time.sleep(0.02)
        msg = str(ctx._errors[0][0])
        assert "expired before our GET" in msg, msg
        assert (0, 987654) not in rde._pending_gets
        rde.ce.barrier()
        return "receiver-missed"
    rde.ce.barrier()        # rank 0 must survive the bogus GET
    return "server-alive"


def test_eager_race_rendezvous_purge():
    res = run_distributed(_purged_handle_worker, 2, timeout=120)
    assert res == ["server-alive", "receiver-missed"]


# -- adaptive eager threshold: feedback rules -------------------------------

class _FakeFeedbackCE:
    def __init__(self):
        self.fb = {"out_bytes": 0, "delay_ewma": None, "rate_ewma": None}

    def peer_feedback(self, dst):
        return self.fb


def _bare_rde(eager=65536):
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    rde = RemoteDepEngine.__new__(RemoteDepEngine)
    rde.eager = eager
    rde._proto_peer = {}
    rde._proto_lock = threading.Lock()
    rde.proto = {"eager_downshift": 0, "eager_upshift": 0}
    rde._bp_budget = float(params.get("comm_backpressure_ms", 2.0)) * 1e-3
    rde._eager_floor_cfg = int(params.get("comm_eager_min", 4096))
    rde._eager_cap_mult = max(1, int(params.get("comm_eager_cap_mult", 4)))
    rde.ce = _FakeFeedbackCE()
    return rde


def test_adaptive_eager_downshift_and_recovery():
    rde = _bare_rde(eager=65536)
    # healthy pipe: threshold never drops below base
    rde.ce.fb = {"out_bytes": 0, "delay_ewma": 1e-4, "rate_ewma": 1e9}
    t0 = rde._peer_eager(1)
    assert t0 >= 65536
    def expire_window():
        # adjustments are rate-limited to one per feedback window: step
        # past it instead of sleeping real time
        rde._proto_peer[1]["adj_at"] -= 1.0

    # congested: 100MB queued at 10MB/s -> projected 10s >> budget
    rde.ce.fb = {"out_bytes": 100 << 20, "delay_ewma": 0.5,
                 "rate_ewma": 10e6}
    expire_window()
    t1 = rde._peer_eager(1)
    assert t1 < t0

    for _ in range(20):            # sustained congestion -> the floor
        expire_window()
        rde._peer_eager(1)
    floor = min(int(params.get("comm_eager_min", 4096)), 65536)
    assert rde._proto_peer[1]["eager"] == floor
    assert rde.proto["eager_downshift"] > 0
    # a burst of queries WITHIN one window must shift at most once
    before = rde.proto["eager_downshift"]
    rde._proto_peer[1]["eager"] = 65536
    expire_window()
    for _ in range(10):
        rde._peer_eager(1)
    assert rde.proto["eager_downshift"] == before + 1
    rde._proto_peer[1]["eager"] = floor
    # drained pipe: threshold recovers (and may exceed base, to cap)
    rde.ce.fb = {"out_bytes": 0, "delay_ewma": 1e-5, "rate_ewma": 5e9}
    for _ in range(30):
        expire_window()
        rde._peer_eager(1)
    cap = 65536 * int(params.get("comm_eager_cap_mult", 4))
    assert rde._proto_peer[1]["eager"] == cap
    assert rde.proto["eager_upshift"] > 0


def test_adaptive_eager_disabled_keeps_base():
    rde = _bare_rde(eager=1234)
    rde.ce.fb = {"out_bytes": 100 << 20, "delay_ewma": 9.9,
                 "rate_ewma": 1.0}
    params.set("comm_adaptive_eager", False)
    try:
        assert rde._peer_eager(1) == 1234
    finally:
        params.unset("comm_adaptive_eager")


# -- activation coalescing: one frame per destination per task --------------

def _coalesce_worker(ctx, rank, nranks):
    """One producer task with TWO flows feeding rank 1: both activations
    must pack into ONE wire frame (TAG_BATCH)."""
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, TASK
    V = VectorTwoDimCyclic(mb=4, lm=4 * nranks, nodes=nranks, myrank=rank)
    if rank == 0:
        V.data_of(0).copy_on(0).payload[:] = 2.0
    seen = {}
    p = PTG("coal")
    p.task("P") \
        .affinity(lambda V=V: V(0)) \
        .flow("X", "READ",
              IN(DATA(lambda V=V: V(0))),
              OUT(TASK("C", "X", lambda: dict()))) \
        .flow("Y", "READ",
              IN(DATA(lambda V=V: V(0))),
              OUT(TASK("C", "Y", lambda: dict()))) \
        .body(lambda: None)
    p.task("C") \
        .affinity(lambda V=V: V(1)) \
        .flow("X", "READ", IN(TASK("P", "X", lambda: dict()))) \
        .flow("Y", "READ", IN(TASK("P", "Y", lambda: dict()))) \
        .body(lambda X, Y: seen.update(
            x=float(np.asarray(X)[0]), y=float(np.asarray(Y)[0])))
    ctx.add_taskpool(p.build())
    ctx.wait(timeout=120)
    return {"seen": seen, "stats": ctx.comm.stats()}


def test_activation_coalescing_one_frame_per_dst():
    res = run_distributed(_coalesce_worker, 2, timeout=120)
    assert res[1]["seen"] == {"x": 2.0, "y": 2.0}
    st = res[0]["stats"]
    assert st["coalesced_batches"] >= 1, st
    assert st["coalesced_msgs"] >= 2, st


# -- transport A/B knob ------------------------------------------------------

def test_make_ce_transport_knob():
    base = _probe_port_base(1)
    params.set("comm_transport", "threads")
    try:
        ce = make_ce(0, 1, base)
        assert isinstance(ce, SocketCE)
        ce.fini()
        params.set("comm_transport", "evloop")
        ce = make_ce(0, 1, base)
        assert isinstance(ce, EventLoopCE)
        ce.fini()
    finally:
        params.unset("comm_transport")


def _ab_chain(ctx, rank, nranks):
    assert type(ctx.comm.ce).__name__ == "SocketCE"
    assert ctx.comm.stats()["transport"] == "threads"
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    NT = 6
    V = VectorTwoDimCyclic(mb=4, lm=NT * 4, nodes=nranks, myrank=rank)
    for m, _ in V.local_tiles():
        V.data_of(m).copy_on(0).payload[:] = 0.0
    p = PTG("ab", NT=NT)
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


def test_threads_transport_ab_reproduces_old_path(monkeypatch):
    monkeypatch.setenv("PARSEC_MCA_COMM_TRANSPORT", "threads")
    results = run_distributed(_ab_chain, 2)
    merged = {}
    for r in results:
        merged.update(r)
    assert merged == {k: float(k + 1) for k in range(6)}


# -- tier-1-safe loopback stress: mixed eager + rendezvous, N seeds ---------

def _stress_worker(ctx, rank, nranks, seeds):
    """Chains over tiles around the eager threshold: every hop is a
    remote edge, randomly eager (small tile) or rendezvous (big tile)
    per seed; payload integrity is the assertion."""
    import numpy as np
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    ctx.comm.eager = 2048          # base threshold in bytes
    out = {}
    for i, seed in enumerate(seeds):
        NT = 8
        # tile sizes straddle the threshold: 64B/1KB ride eager, 32KB
        # exceeds even the adaptive cap (base * comm_eager_cap_mult)
        # -> rendezvous
        mb = [16, 256, 8192][i % 3]
        V = VectorTwoDimCyclic(mb=mb, lm=NT * mb, nodes=nranks,
                               myrank=rank, name=f"S{seed}")
        for m, _ in V.local_tiles():
            V.data_of(m).copy_on(0).payload[:] = 0.0
        p = PTG(f"stress{seed}", NT=NT)
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
        ctx.wait(timeout=120)
        for m, _ in V.local_tiles():
            out[(seed, m)] = float(
                np.asarray(V.data_of(m).pull_to_host().payload)[0])
    st = ctx.comm.stats()
    return {"vals": out, "eager": st["act_eager"], "rdv": st["act_rdv"]}


def test_loopback_stress_mixed_eager_rdv():
    seeds = [11, 23, 47]
    res = run_distributed(_stress_worker, 2, args=(seeds,), timeout=240)
    merged = {}
    eager = rdv = 0
    for r in res:
        merged.update(r["vals"])
        eager += r["eager"]
        rdv += r["rdv"]
    for seed in seeds:
        for k in range(8):
            assert merged[(seed, k)] == float(k + 1), (seed, k)
    # the traffic really mixed both protocols
    assert eager > 0 and rdv > 0, (eager, rdv)


# -- cross-task flush window -------------------------------------------------

def _window_worker(ctx, rank, nranks):
    """Independent producers completing within the flush window: their
    same-destination activations may coalesce; correctness must hold."""
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    N = 6
    V = VectorTwoDimCyclic(mb=4, lm=4 * nranks, nodes=nranks, myrank=rank)
    W = VectorTwoDimCyclic(mb=4, lm=4 * N * nranks, nodes=nranks,
                           myrank=rank, name="W")
    if rank == 0:
        V.data_of(0).copy_on(0).payload[:] = 1.0
    for m, _ in W.local_tiles():
        W.data_of(m).copy_on(0).payload[:] = 0.0
    p = PTG("win", N=N)
    p.task("P", i=Range(0, N - 1)) \
        .affinity(lambda i, V=V: V(0)) \
        .flow("X", "READ",
              IN(DATA(lambda i, V=V: V(0))),
              OUT(TASK("C", "X", lambda i: dict(i=i)))) \
        .body(lambda: None)
    p.task("C", i=Range(0, N - 1)) \
        .affinity(lambda i, W=W: W(2 * i + 1)) \
        .flow("X", "READ", IN(TASK("P", "X", lambda i: dict(i=i)))) \
        .flow("O", "RW",
              IN(DATA(lambda i, W=W: W(2 * i + 1))),
              OUT(DATA(lambda i, W=W: W(2 * i + 1)))) \
        .body(lambda X, O: np.asarray(O) + np.asarray(X) + 1.0)
    ctx.add_taskpool(p.build())
    ctx.wait(timeout=120)
    out = {}
    for m, _ in W.local_tiles():
        out[m] = float(np.asarray(W.data_of(m).pull_to_host().payload)[0])
    return out


def test_cross_task_flush_window(monkeypatch):
    monkeypatch.setenv("PARSEC_MCA_COMM_FLUSH_WINDOW_MS", "2")
    res = run_distributed(_window_worker, 2, timeout=120)
    merged = {}
    for r in res:
        merged.update(r)
    for i in range(6):
        assert merged[2 * i + 1] == 2.0, (i, merged)


# -- mid-run sibling death: rank 0 aborts the round for survivors -----------

def test_barrier_abort_fails_survivors_fast():
    """A sibling dying BEFORE arriving makes rank 0 abort the round:
    surviving non-root ranks fail promptly with the cause instead of
    riding out the full barrier timeout."""
    _, ces = _mk_pair(3)
    ce0, ce1, ce2 = ces
    try:
        ce2.fini()                 # rank 2 dies without arriving
        deadline = time.monotonic() + 10
        while (2 not in ce0.dead_peers or 2 not in ce1.dead_peers) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert 2 in ce0.dead_peers and 2 in ce1.dead_peers
        errs = {}

        def run(name, ce):
            t0 = time.monotonic()
            try:
                ce.barrier(timeout=30)
                errs[name] = ("none", time.monotonic() - t0)
            except Exception as exc:
                errs[name] = (exc, time.monotonic() - t0)

        t1 = threading.Thread(target=run, args=("r1", ce1))
        t1.start()
        run("r0", ce0)
        t1.join(timeout=30)
        exc0, _ = errs["r0"]
        exc1, dt1 = errs["r1"]
        assert isinstance(exc0, ConnectionError), exc0
        assert isinstance(exc1, ConnectionError), exc1
        assert dt1 < 10, f"survivor waited {dt1:.1f}s (timeout-class)"
    finally:
        _fini([ce0, ce1])


# -- undelivered-before-register replay holds on the loop thread ------------

def test_undelivered_backlog_replayed_on_register():
    _, (ce0, ce1) = _mk_pair(2)
    try:
        ce1.send_am(TAG_USER, 0, {"early": True})
        time.sleep(0.3)            # lands before anyone registered
        got = []
        evt = threading.Event()
        ce0.tag_register(TAG_USER, lambda s, p: (got.append((s, p)),
                                                 evt.set()))
        assert evt.wait(10)
        assert got == [(1, {"early": True})]
    finally:
        _fini([ce0, ce1])


# -- multi-core-host validation of the evloop freed-core claim ---------------
# (the r6 threads-vs-evloop parity was measured on a 1-core host, where
# the freed progress-thread core cannot show up.)

def _mc_pingpong_worker(ctx, rank, nranks, nbytes, hops):
    from parsec_tpu.apps.pingpong import run_pingpong
    run_pingpong(ctx, nbytes, 4)            # warm the link
    per_hop, mbps = run_pingpong(ctx, nbytes, hops)
    return per_hop, mbps, ctx.comm.stats()["transport"]


@pytest.mark.slow
def test_evloop_threads_parity_multicore():
    """Paired A/B on a host with >= 2 cores: the evloop transport must
    hold parity with the threads transport (generous band — CI hosts
    are noisy), and the datapoint is archived to a JSON file + the
    test log so the r6 freed-core claim accumulates real
    multi-core evidence (bw/rtt bench lines now record the host core
    inventory for the same reason)."""
    import json
    import os
    import tempfile
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count() or 1
    if cores < 2:
        pytest.skip("multi-core validation needs >= 2 available cores "
                    f"(have {cores}); the 1-core parity leg is README's "
                    "transport table")
    results = {}
    for transport in ("threads", "evloop"):
        prior = os.environ.get("PARSEC_MCA_COMM_TRANSPORT")
        os.environ["PARSEC_MCA_COMM_TRANSPORT"] = transport
        try:
            res = run_distributed(_mc_pingpong_worker, 2,
                                  args=(1 << 20, 24), timeout=240)
        finally:
            if prior is None:
                os.environ.pop("PARSEC_MCA_COMM_TRANSPORT", None)
            else:
                os.environ["PARSEC_MCA_COMM_TRANSPORT"] = prior
        assert all(r[2] == transport for r in res), res
        results[transport] = round(max(r[1] for r in res), 1)  # MB/s
    ratio = results["evloop"] / results["threads"]
    datapoint = {"cpu_count": os.cpu_count(), "cores_available": cores,
                 "bw_mbps": results, "evloop_over_threads": round(ratio, 3)}
    out = os.path.join(tempfile.gettempdir(),
                       "parsec_evloop_multicore.json")
    with open(out, "w") as fh:
        json.dump(datapoint, fh)
    print(f"multicore evloop datapoint (archived {out}): {datapoint}")
    # parity band: evloop must not collapse where cores stop being
    # shared; the freed-core UPSIDE is informational (the datapoint)
    assert ratio >= 0.5, datapoint
