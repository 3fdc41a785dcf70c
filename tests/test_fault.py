"""Fault-injection + failure-lifecycle tests (ISSUE 5: inject -> detect
-> contain -> diagnose).

In-process tests drive the transport/detector machinery directly (two
EventLoopCEs on loopback); end-to-end cases spawn 2-rank workloads under
seeded fault plans through the chaos harness's environment contract
(``PARSEC_MCA_FAULT_PLAN`` is inherited by spawned ranks and armed at
import, utils/faultinject.py)."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from parsec_tpu.core.errors import (FaultInjected, PeerFailedError,
                                    TaskRetryExhausted)
from parsec_tpu.utils import faultinject
from parsec_tpu.utils.mca import params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# plan grammar
# ---------------------------------------------------------------------------

def test_fault_plan_parsing():
    plan = faultinject.FaultPlan(
        "seed=9;drop_frame=tag:ACT,p=0.25,n=3;"
        "delay_frame=tag:DTD,pm='ver': 0,ms=250;"
        "kill_rank=1@t+2.5s,mode=hang;fail_task=key~POTRF(k=0),n=2;"
        "delay_dispatch=ms=5,p=0.1")
    assert plan.seed == 9
    kinds = [d.kind for d in plan.directives]
    assert kinds == ["drop_frame", "delay_frame", "kill_rank",
                     "fail_task", "delay_dispatch"]
    drop, delay, kill, ftask, disp = plan.directives
    assert drop.tag == 1 and drop.p == 0.25 and drop.n == 3
    assert delay.pm == "'ver': 0" and delay.ms == 250.0
    assert kill.rank == 1 and kill.at_s == 2.5 and kill.mode == "hang"
    assert ftask.key == "POTRF(k=0)" and ftask.n == 2
    assert disp.ms == 5.0 and disp.p == 0.1


def test_fault_plan_take_counts_and_determinism():
    faultinject.arm("seed=3;drop_frame=tag:ACT,n=2")
    try:
        cf = faultinject.comm_faults(0)
        hits = [cf.frame_action(1, 1, None) for _ in range(5)]
        assert [h is not None for h in hits] == [True, True, False,
                                                False, False]
        # seeded determinism: the same plan + rank replays the stream
        faultinject.arm("seed=3;drop_frame=tag:ACT,p=0.5")
        a = [faultinject.comm_faults(1).frame_action(1, 0, None)
             is not None for _ in range(1)]
        b = [faultinject.comm_faults(1).frame_action(1, 0, None)
             is not None for _ in range(1)]
        assert a == b
    finally:
        faultinject.disarm()
    assert not faultinject.ARMED


def test_delay_recv_parsing_and_matching():
    """ISSUE 7 satellite: recv-side per-frame delay (reorder coverage
    on the RECEIVE path — send-side delays cannot reorder what TCP
    delivers in stream order)."""
    plan = faultinject.FaultPlan(
        "seed=4;delay_recv=tag:DTD,p=0.5,ms=120,rank=1")
    (d,) = plan.directives
    assert d.kind == "delay_recv" and d.tag == 6 and d.ms == 120.0 \
        and d.rank == 1 and d.p == 0.5
    faultinject.arm("seed=4;delay_recv=tag:DTD,n=1,ms=50,rank=1")
    try:
        cf = faultinject.comm_faults(0)
        assert cf is not None and len(cf.recv_dirs) == 1
        # rank= scopes by SOURCE rank on the receive side
        assert cf.recv_delay_ms(6, 2, None) is None
        assert cf.recv_delay_ms(6, 1, None) == 50.0
        assert cf.recv_delay_ms(6, 1, None) is None   # n=1 consumed
        # outbound frame directives unaffected by a recv-only plan
        assert cf.frame_action(6, 1, None) is None
    finally:
        faultinject.disarm()


def test_delay_recv_reorders_dispatch_on_receive_path():
    """Two frames sent in order on one TCP stream dispatch REVERSED at
    the receiver when a delay_recv holds the first — the hook must not
    stall the loop (later frames flow during the hold), and the held
    frame's handler still runs (on the loop thread: the funnelled
    redelivery re-posts instead of dispatching off-thread)."""
    from parsec_tpu.comm.launch import _probe_port_base

    # WIDE margin (hold 1.2s vs 0.1s send gap): strict-order asserts
    # with tight margins are exactly the load-sensitive flake class
    # this repo keeps retiring — the second frame has >1s of slack to
    # dispatch before the held frame's redelivery timer fires
    faultinject.arm("seed=1;delay_recv=tag:16,n=1,ms=1200")
    try:
        ce0, ce1 = _pair_of_engines(_probe_port_base(2))
        try:
            got = []
            ce1.tag_register(16, lambda src, msg: got.append(msg["i"]))
            time.sleep(0.3)   # both lanes dialed in
            ce0.send_am(16, 1, {"i": 1})   # held 1.2s at the receiver
            time.sleep(0.1)
            ce0.send_am(16, 1, {"i": 2})   # flows past the held frame
            deadline = time.monotonic() + 6.0
            while len(got) < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert got == [2, 1], got
        finally:
            ce0.fini()
            ce1.fini()
    finally:
        faultinject.disarm()


def test_unarmed_hooks_are_inert():
    assert faultinject.comm_faults(0) is None
    assert faultinject.runtime() is None


# ---------------------------------------------------------------------------
# detect: hard close vs silent hang (the two detector paths)
# ---------------------------------------------------------------------------

def _pair_of_engines(port_base):
    from parsec_tpu.comm.engine import EventLoopCE
    ce0 = EventLoopCE(0, 2, port_base)
    ce1 = EventLoopCE(1, 2, port_base)
    return ce0, ce1


def test_hard_close_vs_silent_hang_detection_latency():
    """EOF detection is immediate; a HUNG peer (sockets open, nothing
    flowing) is only caught by the heartbeat timeout — within 2x
    comm_peer_timeout_s (the ISSUE acceptance bound)."""
    from parsec_tpu.comm.launch import _probe_port_base

    params.set("comm_peer_timeout_s", 1.0)
    try:
        # --- silent hang ---------------------------------------------
        ce0, ce1 = _pair_of_engines(_probe_port_base(2))
        errors = []
        ce0.on_error = errors.append
        try:
            for ce in (ce0, ce1):
                ce.add_periodic(ce.heartbeat_tick, 0.25)
                ce.add_periodic(ce.check_peer_timeouts, 0.25)
            time.sleep(0.8)          # a few heartbeat rounds flow
            assert not ce0.dead_peers
            t0 = time.monotonic()
            ce1.fault_kill("hang")   # mute: sockets stay OPEN
            deadline = t0 + 4.0
            while 1 not in ce0.dead_peers and time.monotonic() < deadline:
                time.sleep(0.02)
            dt = time.monotonic() - t0
            assert 1 in ce0.dead_peers, "hung peer never declared dead"
            assert dt <= 2.0 * 1.0 + 0.6, f"detection took {dt:.2f}s"
            assert errors and isinstance(errors[0], PeerFailedError)
            assert errors[0].rank == 1
            assert errors[0].detector == "heartbeat"
        finally:
            ce0.fini()
            ce1.fini()
        # --- hard close ----------------------------------------------
        ce0, ce1 = _pair_of_engines(_probe_port_base(2))
        errors = []
        ce0.on_error = errors.append
        try:
            time.sleep(0.3)
            t0 = time.monotonic()
            ce1.fault_kill("close")  # abrupt EOF on every socket
            deadline = t0 + 3.0
            while 1 not in ce0.dead_peers and time.monotonic() < deadline:
                time.sleep(0.02)
            dt = time.monotonic() - t0
            assert 1 in ce0.dead_peers, "closed peer never declared dead"
            assert dt <= 1.0, f"EOF detection took {dt:.2f}s"
            assert errors and isinstance(errors[0], PeerFailedError)
        finally:
            ce0.fini()
            ce1.fini()
    finally:
        params.unset("comm_peer_timeout_s")


def test_silent_hang_detection_threads_transport():
    """The legacy threads transport detects a silent hang too, and its
    heartbeat discipline is NONBLOCKING — a hung peer's full send buffer
    or an undialed rank must not wedge the thread that runs the
    detector (SocketCE._hb_send: established + try-lock + writability
    gates)."""
    from parsec_tpu.comm.engine import SocketCE
    from parsec_tpu.comm.launch import _probe_port_base

    params.set("comm_peer_timeout_s", 1.0)
    try:
        base = _probe_port_base(2)
        ce0, ce1 = SocketCE(0, 2, base), SocketCE(1, 2, base)
        errors = []
        ce0.on_error = errors.append
        try:
            for _ in range(8):       # connect + a few beats each way
                ce0.heartbeat_tick()
                ce1.heartbeat_tick()
                time.sleep(0.05)
            ce0.check_peer_timeouts()
            assert not ce0.dead_peers
            t0 = time.monotonic()
            ce1.fault_kill("hang")   # mute: sockets stay OPEN
            deadline = t0 + 4.0
            while 1 not in ce0.dead_peers and time.monotonic() < deadline:
                ce0.heartbeat_tick()     # must never block
                ce0.check_peer_timeouts()
                time.sleep(0.05)
            dt = time.monotonic() - t0
            assert 1 in ce0.dead_peers, "hung peer never declared dead"
            assert dt <= 2.0 * 1.0 + 0.6, f"detection took {dt:.2f}s"
            assert errors and isinstance(errors[0], PeerFailedError)
            assert errors[0].detector == "heartbeat"
        finally:
            ce0.fini()
            ce1.fini()
    finally:
        params.unset("comm_peer_timeout_s")


def test_starved_checker_rebases_instead_of_declaring():
    """A checker that itself was starved past the timeout (GIL storm)
    must NOT declare peers dead from its own silence."""
    from parsec_tpu.comm.engine import CommEngine

    params.set("comm_peer_timeout_s", 0.5)
    try:
        ce = CommEngine(0, 2)
        ce._last_heard[1] = time.monotonic() - 10.0
        ce._hb_check_at = time.monotonic() - 10.0   # WE were frozen
        ce.check_peer_timeouts()
        assert 1 not in ce.dead_peers
        # the rebase reset the peer's clock; sustained silence past a
        # HEALTHY check interval still declares
        ce._last_heard[1] = time.monotonic() - 10.0
        ce.check_peer_timeouts()
        assert 1 in ce.dead_peers
    finally:
        params.unset("comm_peer_timeout_s")


# ---------------------------------------------------------------------------
# end-to-end: frame faults + kills through the chaos harness contract
# ---------------------------------------------------------------------------

def _chaos(only, seeds=1, timeout=300):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos.py"),
         "--seeds", str(seeds), "--only", only],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=timeout)


def test_chaos_frame_drop_dup_recovery():
    """Dropped GET_REP frames recover through rendezvous retry; dup'd
    activation/DTD frames are deduplicated — both complete CORRECTLY
    (the workloads validate their numbers internally)."""
    proc = _chaos("drop-getrep,dup-frames,dup-potrf", seeds=3)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_chaos_kill_mid_run_fails_cleanly():
    """2-rank kill mid-workload: structured PeerFailedError, no hang,
    well inside the harness deadline."""
    proc = _chaos("kill-close,trunc-act", seeds=2)
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.slow
def test_chaos_full_catalog():
    """The ISSUE acceptance run: 12 seeded plans, zero hangs, zero
    silent wrong answers (incl. the silent-hang kill detected by
    heartbeat within 2x comm_peer_timeout_s)."""
    proc = _chaos("", seeds=12, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# contain: rendezvous terminal timeout, retry, service degraded mode
# ---------------------------------------------------------------------------

def _run_distributed_with_env(fn, nranks, env, timeout=120):
    from parsec_tpu.comm.launch import run_distributed
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        return run_distributed(fn, nranks, timeout=timeout)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_rendezvous_terminal_timeout():
    """Every GET_REP dropped: bounded retries, then the pull fails its
    pool with a structured rendezvous PeerFailedError — no infinite
    wait."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos
    with pytest.raises(RuntimeError) as ei:
        _run_distributed_with_env(
            chaos.potrf_workload, 2,
            {"PARSEC_MCA_FAULT_PLAN": "seed=5;drop_frame=tag:GET_REP,p=1",
             "PARSEC_MCA_COMM_EAGER_LIMIT": "512",
             "PARSEC_MCA_COMM_ADAPTIVE_EAGER": "0",
             "PARSEC_MCA_COMM_RDV_RETRY_S": "0.3",
             "PARSEC_MCA_COMM_RDV_TIMEOUT_S": "3",
             "PARSEC_CHAOS_WAIT_S": "30"})
    text = str(ei.value)
    assert "PeerFailedError" in text and "rendezvous" in text, text


def test_task_retry_transient_then_success():
    """A transiently-failing idempotent body retries against PRISTINE
    inputs (write-flow snapshot) and the pool completes correctly."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.dsl.dtd import INOUT, DTDTaskpool

    params.set("task_retry_max", 2)
    try:
        with Context(nb_cores=2) as ctx:
            assert ctx._retry_max == 2
            tp = DTDTaskpool("retry")
            ctx.add_taskpool(tp)
            ctx.start()
            from parsec_tpu.data.data import new_data
            datum = new_data(np.full(4, 7.0, np.float32))
            attempts = []

            def flaky(T):
                arr = np.asarray(T)
                attempts.append(arr.copy())
                if len(attempts) == 1:
                    arr[:] = -1.0          # corrupt in place...
                    raise RuntimeError("transient glitch")
                return arr * 2.0
            tp.insert_task(flaky, (datum, INOUT))
            tp.wait(timeout=30)
            ctx.wait(timeout=30)
            assert len(attempts) == 2
            # the retry saw the ORIGINAL value, not the corruption
            np.testing.assert_allclose(attempts[1], 7.0)
            np.testing.assert_allclose(
                np.asarray(datum.pull_to_host().payload), 14.0)
    finally:
        params.unset("task_retry_max")


def test_task_retry_exhausted_is_structured():
    from parsec_tpu.core.context import Context
    from parsec_tpu.dsl.dtd import INOUT, DTDTaskpool

    params.set("task_retry_max", 1)
    try:
        with Context(nb_cores=2) as ctx:
            tp = DTDTaskpool("exhaust")
            ctx.add_taskpool(tp)
            ctx.start()
            from parsec_tpu.data.data import new_data
            datum = new_data(np.zeros(4, np.float32))
            calls = []

            def always_fails(T):
                calls.append(1)
                raise FaultInjected("injected, forever")
            tp.insert_task(always_fails, (datum, INOUT))
            with pytest.raises(RuntimeError):
                tp.wait(timeout=30)
            assert len(calls) == 2       # first try + one retry
            exc = ctx._errors[0][0]
            assert isinstance(exc, TaskRetryExhausted)
            assert exc.attempts == 2
            assert isinstance(exc.__cause__, FaultInjected)
    finally:
        params.unset("task_retry_max")


def _slow_chain_factory(name, nt=30, delay=0.02):
    """PTG increment chain over a private tile (the test_service idiom):
    slow enough that peer death can be injected mid-run."""
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK

    def factory():
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=4, ln=4)
        A.data_of(0, 0).copy_on(0).payload[:] = 0.0

        def body(T, k):
            time.sleep(delay)
            return T + 1.0

        p = PTG(name, NT=nt)
        p.task("S", k=Range(0, nt - 1)) \
            .affinity(lambda k, A=A: A(0, 0)) \
            .flow("T", "RW",
                  IN(DATA(lambda A=A: A(0, 0)), when=lambda k: k == 0),
                  IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                     when=lambda k: k > 0),
                  OUT(TASK("S", "T", lambda k, NT=nt: dict(k=k + 1)),
                      when=lambda k, NT=nt: k < NT - 1),
                  OUT(DATA(lambda A=A: A(0, 0)),
                      when=lambda k, NT=nt: k == NT - 1)) \
            .body(body)

        def result():
            return float(np.asarray(
                A.data_of(0, 0).copy_on(0).payload)[0, 0])
        return p.build(), result
    return factory


def test_service_degraded_mode_keeps_serving():
    """A job killed by a dead peer flips the service into degraded mode
    (rank recorded on service + handle); unaffected jobs keep running
    and new submissions are still admitted."""
    from parsec_tpu.service.service import JobService
    from parsec_tpu.service.job import JobError, JobStatus

    with JobService(nb_cores=2) as svc:
        victim = svc.submit(_slow_chain_factory("victim"), name="victim")
        bystander = svc.submit(_slow_chain_factory("bystander"),
                               name="bystander")
        # wait until the victim's pool is attached, then inject the
        # peer death through the containment route
        deadline = time.monotonic() + 10
        while victim.taskpool is None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert victim.taskpool is not None
        svc.context.record_pool_error(
            victim.taskpool,
            PeerFailedError(3, "rank 0: peer rank 3 disconnected",
                            detector="heartbeat"))
        victim.wait(timeout=10)
        assert victim.status() == JobStatus.FAILED
        assert victim.failed_rank == 3
        with pytest.raises(JobError):
            victim.result(timeout=5)
        # the service is degraded but SERVING: the bystander finishes,
        # and a fresh submission is admitted and runs
        assert svc.degraded and svc.degraded_ranks() == [3]
        assert svc.stats()["degraded_ranks"] == [3]
        assert bystander.result(timeout=30) == 30.0
        late = svc.submit(_slow_chain_factory("late", nt=3, delay=0.0),
                          name="late")
        assert late.result(timeout=30) == 3.0
        assert late.status() == JobStatus.DONE
        assert victim.info()["failed_rank"] == 3


# ---------------------------------------------------------------------------
# diagnose: the hang autopsy
# ---------------------------------------------------------------------------

def test_hang_autopsy_emitted_on_soft_deadline(capfd):
    from parsec_tpu.core.context import Context
    from parsec_tpu.dsl.dtd import DTDTaskpool

    params.set("runtime_autopsy_s", 0.4)
    try:
        with Context(nb_cores=1) as ctx:
            tp = DTDTaskpool("stuck")
            ctx.add_taskpool(tp)       # insertion hold: never completes
            ctx.start()
            with pytest.raises(TimeoutError):
                ctx.wait(timeout=1.2)
            report = ctx.hang_autopsy()
            assert "hang autopsy" in report
            assert "stuck" in report and "pending_actions=1" in report
            tp.wait(timeout=10)        # release the hold for teardown
            ctx.wait(timeout=10)
        err = capfd.readouterr().err
        assert "hang autopsy" in err   # the one-shot in-wait emission
    finally:
        params.unset("runtime_autopsy_s")


def test_autopsy_includes_comm_state():
    """debug_state feeds the autopsy: termdet balance, parked work,
    per-peer liveness ages."""
    from parsec_tpu.comm.launch import _probe_port_base

    ce0, ce1 = _pair_of_engines(_probe_port_base(2))
    try:
        time.sleep(0.2)
        dbg = ce0.peer_debug()
        assert 1 in dbg and "last_heard_age_s" in dbg[1]
        assert dbg[1]["dead"] is False
    finally:
        ce0.fini()
        ce1.fini()


# ---------------------------------------------------------------------------
# the r6 DTD region-lane stale read, now a replayable fault plan
# ---------------------------------------------------------------------------

def _region_plan_env(seed):
    return {"PARSEC_MCA_FAULT_PLAN":
            f"seed={seed};delay_frame=tag:DTD,pm='ver': 0,ms=600"}


def test_dtd_region_ordering_under_delay_plan():
    """The ~1/12 load-sensitive stale-chain read, forced DETERMINISTICALLY:
    delaying the version-0 pristine-pull payload past the chain's final
    write used to clobber the tile (whole-covering applies on disjoint
    lanes take no mutual edges and extent-less lanes have no slices to
    preserve).  The applied_ver landing-order guard in dsl/dtd/insert.py
    keeps the late v0 payload from regressing the tile."""
    from tests.test_dtd_distributed import _region_ordering_only
    res = _run_distributed_with_env(_region_ordering_only, 2,
                                    _region_plan_env(1), timeout=120)
    assert res == ["ok"] * 2


@pytest.mark.slow
def test_geqrf_chain_under_dispatch_delay():
    """The r7 geqrf wrong-R flake's replay conditions: chained panel
    dispatch with seeded delay_dispatch perturbation must keep R
    correct."""
    from parsec_tpu.apps.qr import qr_taskpool
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    faultinject.arm("seed=31;delay_dispatch=ms=4,p=0.3")
    try:
        for i in range(3):
            rng = np.random.default_rng(2)
            mb, nt = 32, 6
            n = mb * nt
            a = rng.standard_normal((n, n)).astype(np.float32)
            Q = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n,
                                  name=f"Aqr_f{i}").from_array(a.copy())
            with Context(nb_cores=4) as ctx:
                Q.distribute_devices(ctx)
                ctx.add_taskpool(qr_taskpool(Q, device="tpu"))
                ctx.wait(timeout=300)
            out = Q.to_array()
            ata = a.T @ a
            R = np.triu(out)
            qerr = np.abs(R.T @ R - ata).max() / np.abs(ata).max()
            assert qerr < 1e-4, f"iter {i}: wrong R (qerr={qerr:.3e})"
    finally:
        faultinject.disarm()


@pytest.mark.slow
def test_dtd_region_ordering_under_delay_plan_20x():
    """The ISSUE satellite's acceptance loop: 20 seeded runs under the
    plan, all green."""
    from tests.test_dtd_distributed import _region_ordering_only
    for seed in range(1, 21):
        env = {"PARSEC_MCA_FAULT_PLAN":
               f"seed={seed};delay_frame=tag:DTD,p=0.5,ms=120"
               if seed % 2 else
               f"seed={seed};delay_frame=tag:DTD,pm='ver': 0,ms=600"}
        res = _run_distributed_with_env(_region_ordering_only, 2, env,
                                        timeout=120)
        assert res == ["ok"] * 2, f"seed {seed}"


# ---------------------------------------------------------------------------
# the flight recorder's incident path (ISSUE 8): chaos kill under an
# armed ring must yield a merged, clock-aligned incident bundle
# ---------------------------------------------------------------------------

def test_flightrec_kill_rank_yields_merged_bundle(tmp_path):
    """chaos ``kill_rank`` with the flight recorder armed: both ranks'
    rings land in ONE bundle directory, the merged trace is
    clock-aligned with matched comm_send/dep_deliver pairs covering
    the kill window, and ``tools/trace2chrome.py --merge`` opens it
    unchanged."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos
    bundle = str(tmp_path / "bundle")
    with pytest.raises(RuntimeError) as ei:
        # the potrf workload rides the PTG activation path, so the ring
        # holds dep_deliver points (the DTD path's deliveries are lane
        # applies); frame delays stretch the run past the kill instant
        # (kill at 1.2s with 250ms/frame delays on BOTH activation
        # tags: the threads transport's progress loop aggregates
        # same-destination activations into TAG_BATCH frames, which an
        # ACT-only plan misses — it then outran the old 0.8s/150ms
        # window and completed before the kill; 0.5s was conversely
        # too early for evloop's first delayed wave to have recorded
        # any flow.  This pairing holds the kill mid-run on all three
        # transports.)
        _run_distributed_with_env(
            chaos.potrf_workload, 2,
            {"PARSEC_MCA_FAULT_PLAN":
                 "seed=7;kill_rank=1@t+1.2s,mode=close;"
                 "delay_frame=tag:ACT,p=1,ms=250;"
                 "delay_frame=tag:BATCH,p=1,ms=250",
             "PARSEC_MCA_FLIGHTREC_ENABLED": "1",
             "PARSEC_MCA_FLIGHTREC_DIR": bundle,
             "PARSEC_CHAOS_WAIT_S": "30"})
    assert "PeerFailedError" in str(ei.value)
    # the survivor's containment dumped its ring; the killed rank's own
    # failing sends dumped the other side of every flow edge
    import glob
    traces = sorted(glob.glob(os.path.join(bundle, "rank*.ptt")))
    assert len(traces) == 2, traces
    from parsec_tpu.prof.flightrec import summarize_bundle
    s = summarize_bundle(bundle)
    assert s["ranks"] == [0, 1]
    assert s["events"] > 0
    assert s["flows"]["matched"] >= 1, s
    assert s["incidents"] and any("PeerFailedError" in i["reason"]
                                  or "error" in i["reason"]
                                  for i in s["incidents"])
    # the merged trace pairs sends with deliveries on the consumer oid
    from parsec_tpu.prof.critpath import merge_traces
    df, _metas = merge_traces(traces)
    sends = {tuple(r.info["corr"]) for r in
             df[df["name"] == "comm_send"].itertuples()
             if r.info and r.info.get("corr")}
    delivers = {tuple(r.info["corr"]) for r in
                df[df["name"] == "dep_deliver"].itertuples()
                if r.info and r.info.get("corr")}
    assert sends & delivers, (len(sends), len(delivers))
    # trace2chrome --merge opens the bundle unchanged
    out = str(tmp_path / "incident.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace2chrome.py"),
         "--merge", *traces, "-o", out],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json as json_mod
    with open(out) as fh:
        chrome = json_mod.load(fh)
    assert chrome["traceEvents"], "empty merged timeline"
    pids = {e.get("pid") for e in chrome["traceEvents"]}
    assert {0, 1} <= pids, pids


def test_flightrec_autopsy_names_bundle(tmp_path):
    """The hang autopsy's text points the reader at the incident
    bundle when the recorder is armed (and dumps it)."""
    from parsec_tpu.core.context import Context
    params.set("flightrec_enabled", 1)
    params.set("flightrec_dir", str(tmp_path))
    try:
        with Context(nb_cores=1) as ctx:
            report = ctx.hang_autopsy()
            assert "flight recorder incident bundle" in report
            assert str(tmp_path) in report
            assert "trace2chrome.py --merge" in report
            # the dump runs on its own thread (containment must not
            # stall the comm loop): wait for it to land
            deadline = time.monotonic() + 10
            while ctx._flightrec.incidents < 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert ctx._flightrec.incidents == 1
            assert (tmp_path / "rank0.ptt").exists()
            # the dump is rate-limited: a second autopsy re-reports the
            # SAME bundle instead of thrashing the disk
            ctx.hang_autopsy()
            time.sleep(0.1)
            assert ctx._flightrec.incidents == 1
    finally:
        params.unset("flightrec_enabled")
        params.unset("flightrec_dir")


# ---------------------------------------------------------------------------
# the donation soak: chained launches donate the leaves they read once
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fused_chain_donation_soak():
    """The soak that guards donation in chained launches: 50+
    fused-chain geqrf/potrf iterations under seeded delay_dispatch
    load, asserting ZERO wrong results.  (The r8 wrong-R reproduced at
    ~2/22 under this load before the device_put_private fix.)"""
    from parsec_tpu.apps.potrf import potrf_taskpool
    from parsec_tpu.apps.qr import qr_taskpool
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    faultinject.arm("seed=53;delay_dispatch=ms=3,p=0.3")
    try:
        mb, nt = 16, 5
        n = mb * nt
        rng = np.random.default_rng(8)
        with Context(nb_cores=4) as ctx:
            chained0 = sum(d.stats.chained_launches
                           for d in ctx.device_registry.accelerators)
            for i in range(52):
                if i % 2 == 0:
                    a = rng.standard_normal((n, n)).astype(np.float32)
                    Q = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n,
                                          name=f"soakQ{i}").from_array(
                        a.copy())
                    Q.distribute_devices(ctx)
                    ctx.add_taskpool(qr_taskpool(Q, device="tpu"))
                    ctx.wait(timeout=120)
                    R = np.triu(Q.to_array())
                    ata = (a.T @ a).astype(np.float64)
                    qerr = np.abs(R.astype(np.float64).T @ R - ata).max() \
                        / np.abs(ata).max()
                    assert qerr < 1e-4, f"iter {i}: wrong R ({qerr:.3e})"
                else:
                    b = rng.standard_normal((n, n)).astype(np.float32)
                    spd = (b @ b.T + n * np.eye(n)).astype(np.float32)
                    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n,
                                          name=f"soakA{i}").from_array(
                        spd.copy())
                    A.distribute_devices(ctx)
                    ctx.add_taskpool(potrf_taskpool(A, device="tpu"))
                    ctx.wait(timeout=120)
                    L = np.tril(A.to_array()).astype(np.float64)
                    perr = np.abs(L @ L.T - spd).max() / np.abs(spd).max()
                    assert perr < 1e-4, f"iter {i}: wrong L ({perr:.3e})"
            chained = sum(d.stats.chained_launches
                          for d in ctx.device_registry.accelerators)
        # the soak must actually have exercised chained (donating)
        # launches, not the plain path
        assert chained > chained0, "no fused chains ran — soak is void"
    finally:
        faultinject.disarm()


# ---------------------------------------------------------------------------
# shm transport (r11): the ring transport must produce the SAME
# structured detectors and containment as TCP
# ---------------------------------------------------------------------------

def test_shm_hard_close_vs_silent_hang_detection():
    """Over shm rings: a hard kill surfaces as a closed-ring EOF
    immediately; a silent hang (rings open, nothing flowing) is caught
    by the heartbeat timeout within 2x comm_peer_timeout_s — the same
    detector latencies the TCP transports guarantee."""
    from parsec_tpu.comm.launch import _probe_port_base
    from parsec_tpu.comm.shm import ShmCE

    params.set("comm_peer_timeout_s", 1.0)
    try:
        # --- silent hang ---------------------------------------------
        base = _probe_port_base(2)
        ce0, ce1 = ShmCE(0, 2, base), ShmCE(1, 2, base)
        errors = []
        ce0.on_error = errors.append
        try:
            for ce in (ce0, ce1):
                ce.add_periodic(ce.heartbeat_tick, 0.25)
                ce.add_periodic(ce.check_peer_timeouts, 0.25)
            # attach both directions (heartbeats only beat attached
            # rings; a real run attaches at the first activation)
            ce0.send_am(13, 1, None)
            ce1.send_am(13, 0, None)
            time.sleep(0.8)          # a few heartbeat rounds flow
            assert not ce0.dead_peers
            t0 = time.monotonic()
            ce1.fault_kill("hang")   # mute: rings stay OPEN
            deadline = t0 + 4.0
            while 1 not in ce0.dead_peers and time.monotonic() < deadline:
                time.sleep(0.02)
            dt = time.monotonic() - t0
            assert 1 in ce0.dead_peers, "hung shm peer never declared"
            assert dt <= 2.0 * 1.0 + 0.6, f"detection took {dt:.2f}s"
            assert errors and isinstance(errors[0], PeerFailedError)
            assert errors[0].rank == 1
            assert errors[0].detector == "heartbeat"
        finally:
            ce0.fini()
            ce1.fini()
        # --- hard close ----------------------------------------------
        base = _probe_port_base(2)
        ce0, ce1 = ShmCE(0, 2, base), ShmCE(1, 2, base)
        errors = []
        ce0.on_error = errors.append
        try:
            ce0.send_am(13, 1, None)
            ce1.send_am(13, 0, None)
            time.sleep(0.3)
            t0 = time.monotonic()
            ce1.fault_kill("close")  # closed flag on every ring
            deadline = t0 + 3.0
            while 1 not in ce0.dead_peers and time.monotonic() < deadline:
                time.sleep(0.02)
            dt = time.monotonic() - t0
            assert 1 in ce0.dead_peers, "closed shm peer never declared"
            assert dt <= 1.0, f"closed-ring detection took {dt:.2f}s"
            assert errors and isinstance(errors[0], PeerFailedError)
        finally:
            ce0.fini()
            ce1.fini()
    finally:
        params.unset("comm_peer_timeout_s")


def test_shm_frame_directives_hook_send_path():
    """drop/delay fault-plan frame directives apply to shm sends: a
    dropped frame never dispatches, a delayed one arrives late (the
    directives hook ShmCE.send_am through the shared _fault_frame)."""
    from parsec_tpu.comm.launch import _probe_port_base
    from parsec_tpu.comm.shm import ShmCE

    faultinject.arm("seed=5;drop_frame=tag:ACT,n=1;"
                    "delay_frame=tag:DTD,n=1,ms=300")
    try:
        base = _probe_port_base(2)
        ce0, ce1 = ShmCE(0, 2, base), ShmCE(1, 2, base)
        got = []
        dropped = []
        ce0.on_frame_fault = lambda kind, tag, p, dst=-1: dropped.append(
            (kind, tag))
        ce1.tag_register(1, lambda src, p: got.append(("act", p)))
        ce1.tag_register(6, lambda src, p: got.append(("dtd", p)))
        try:
            t0 = time.monotonic()
            ce0.send_am(1, 1, {"n": 1})     # dropped (n=1 directive)
            ce0.send_am(6, 1, {"n": 2})     # delayed 300ms
            while len(got) < 1 and time.monotonic() - t0 < 5:
                time.sleep(0.02)
            dt = time.monotonic() - t0
            assert got and got[0][0] == "dtd"
            assert dt >= 0.25, f"delayed frame arrived after {dt:.3f}s"
            assert ("drop", 1) in dropped    # Safra reconcile fired
            time.sleep(0.2)
            assert all(k != "act" for k, _ in got), "dropped frame arrived"
        finally:
            ce0.fini()
            ce1.fini()
    finally:
        faultinject.disarm()


def test_chaos_kill_shm():
    """2-rank shm kills end-to-end (hard + silent) through the chaos
    contract: structured PeerFailedError containment, no hang."""
    proc = _chaos("kill-close-shm,kill-hang-shm", seeds=2)
    assert proc.returncode == 0, proc.stdout + proc.stderr
