"""r14 C task-object core (schedext TaskCore/TaskVT/run_quantum):
native-vs-Python parity properties, batched-termdet semantics, the
coalesced worker doorbell, and the chaos kill with the C core active.

The parity property is the gate that matters: identical DAG results,
termdet final counts, PINS event counts, and lineage-ring contents
under both ``PARSEC_MCA_SCHED_NATIVE`` settings — a fast path that
drops an event or a count is a regression no throughput number can
excuse."""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.data.matrix import VectorTwoDimCyclic
from parsec_tpu.dsl.ptg import DATA, IN, NEW, OUT, PTG, Range, TASK
from parsec_tpu.native import load_schedext
from parsec_tpu.utils.mca import params

se = load_schedext()

pytestmark = pytest.mark.skipif(se is None,
                                reason="schedext did not build")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_EVENTS = ("select", "exec_begin", "exec_end", "complete_exec",
           "task_discard")


def _bail_delta(before):
    after = se.bailout_stats()
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] - before.get(k, 0)}


def _mixed_run(native: int):
    """One mixed DAG — a trivial CTL class (the C chain's r14 fast
    path) plus an RW data chain (the r17 EXTENDED chain: FromDesc
    binding, FromTask inputs, local ToTask delivery walks all C-side)
    — returning every observable the parity property compares.

    ICI is disabled for BOTH legs: the conftest's virtual 8-device
    mesh attaches an IciEngine, whose deferred-placement walk rides
    release_deps and (correctly) gates the extended chain off — this
    property is about the chain, so make it eligible."""
    params.set("sched_native", native)
    params.set("comm_ici_enabled", 0)
    try:
        order = []
        events = []       # list.append is GIL-atomic across workers
        A = VectorTwoDimCyclic(1, 1).from_array(
            np.zeros(1, np.float32))
        NE, NB = 40, 6

        def chain_body(T, k):
            order.append(k)
            T += 1.0

        g = PTG("parity", NE=NE, NB=NB)
        g.task("E", i=Range(0, NE - 1)).flow("x", "CTL") \
            .body(lambda: None)
        g.task("S", k=Range(0, NB - 1)) \
            .affinity(lambda k: A(0)) \
            .flow("T", "RW",
                  IN(DATA(lambda k: A(0)), when=lambda k: k == 0),
                  IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                     when=lambda k: k > 0),
                  OUT(TASK("S", "T", lambda k: dict(k=k + 1)),
                      when=lambda k, NB=NB: k < NB - 1)) \
            .body(chain_body)
        tp = g.build()
        bail0 = se.bailout_stats()
        with Context(nb_cores=2) as ctx:
            assert (ctx.scheduler.name == "native") == bool(native)
            for ev in _EVENTS:
                ctx.pins_register(
                    ev, lambda es, e, t: events.append(e))
            ctx.add_taskpool(tp)
            ctx.wait(timeout=30)
        counts = {ev: events.count(ev) for ev in _EVENTS}
        val = float(np.asarray(A(0).resolve().copy_on(0).payload)[0])
        return {"order": order, "value": val, "counts": counts,
                "nb_tasks": tp.nb_tasks,
                "pending": tp.nb_pending_actions,
                "total": NE + NB, "bailouts": _bail_delta(bail0)}
    finally:
        params.unset("comm_ici_enabled")
        params.unset("sched_native")


def test_native_vs_python_parity_property():
    nat = _mixed_run(1)
    py = _mixed_run(0)
    # identical DAG results and execution order on the serialized chain
    assert nat["value"] == py["value"] == 6.0
    assert nat["order"] == py["order"] == list(range(6))
    # termdet final counts drained to zero on both paths
    assert nat["nb_tasks"] == py["nb_tasks"] == 0
    assert nat["pending"] == py["pending"] == 0
    # PINS event counts: every event fires exactly once per task on
    # BOTH paths (the C quantum dispatches the same five hooks)
    assert nat["counts"] == py["counts"]
    assert nat["counts"]["select"] == nat["total"]
    assert nat["counts"]["complete_exec"] == nat["total"]
    assert nat["counts"]["exec_begin"] == nat["total"]
    assert nat["counts"]["exec_end"] == nat["total"]
    assert nat["counts"]["task_discard"] == 0
    # r17: the RW chain is C-chain-covered end to end — not one task
    # fell back to Python (the coverage property the bailout counters
    # exist to gate)
    assert nat["bailouts"] == {}


def _lineage_run(native: int):
    """Recovery-armed single-rank chain: the lineage ring must record
    the same completions (keys, read/write versions) under both knob
    settings — with lineage installed the C chain defers to the Python
    completion path, and THAT is the property (recorded lineage can
    never silently thin out because the fast path got faster)."""
    params.set("sched_native", native)
    params.set("recovery_enable", 1)
    try:
        A = VectorTwoDimCyclic(1, 1).from_array(
            np.zeros(1, np.float32))
        NB = 5
        g = PTG("lin", NB=NB)
        g.task("S", k=Range(0, NB - 1)) \
            .affinity(lambda k: A(0)) \
            .flow("T", "RW",
                  IN(DATA(lambda k: A(0)), when=lambda k: k == 0),
                  IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                     when=lambda k: k > 0),
                  OUT(TASK("S", "T", lambda k: dict(k=k + 1)),
                      when=lambda k, NB=NB: k < NB - 1),
                  OUT(DATA(lambda k: A(0)))) \
            .body(lambda T, k: T.__iadd__(1.0) and None)
        tp = g.build()
        tp.recovery_collections = [A]
        with Context(nb_cores=2) as ctx:
            ctx.add_taskpool(tp)
            ctx.wait(timeout=30)
        lin = tp._lineage
        assert lin is not None, "lineage plane not installed"
        recs = sorted(
            (r.key, tuple(sorted((f, v) for f, (_, v) in r.rmap.items())),
             tuple(sorted((f, v) for f, (_, v) in r.wmap.items())))
            for r in lin.records)
        return recs
    finally:
        params.unset("recovery_enable")
        params.unset("sched_native")


def test_lineage_ring_parity():
    assert _lineage_run(1) == _lineage_run(0)


def _new_binding_run(native: int):
    """NEW-arena scratch binding through the extended chain: MAKE binds
    a fresh arena block (CK_NEW), fills it, and hands it to USE over a
    ToTask edge; USE folds it into a FromDesc-bound RW tile in place.
    Every binding kind the r17 prepare covers, in one DAG.  ICI off as
    in ``_mixed_run`` (the virtual test mesh would gate the chain)."""
    params.set("sched_native", native)
    params.set("comm_ici_enabled", 0)
    try:
        NI = 4
        A = VectorTwoDimCyclic(1, NI).from_array(
            np.zeros(NI, np.float32))
        g = PTG("newbind", NI=NI)
        g.arena("tmp", (2,))
        g.task("MAKE", i=Range(0, NI - 1)) \
            .affinity(lambda i: A(i)) \
            .flow("W", "WRITE",
                  IN(NEW("tmp")),
                  OUT(TASK("USE", "W", lambda i: dict(i=i)))) \
            .body(lambda W: np.full_like(W, 3.0))
        g.task("USE", i=Range(0, NI - 1)) \
            .affinity(lambda i: A(i)) \
            .flow("W", "READ",
                  IN(TASK("MAKE", "W", lambda i: dict(i=i)))) \
            .flow("T", "RW", IN(DATA(lambda i: A(i)))) \
            .body(lambda W, T: T.__iadd__(float(np.sum(W))) and None)
        tp = g.build()
        bail0 = se.bailout_stats()
        with Context(nb_cores=2) as ctx:
            ctx.add_taskpool(tp)
            ctx.wait(timeout=30)
        vals = [float(np.asarray(A(i).resolve().copy_on(0).payload)[0])
                for i in range(NI)]
        return {"vals": vals, "nb_tasks": tp.nb_tasks,
                "pending": tp.nb_pending_actions,
                "bailouts": _bail_delta(bail0)}
    finally:
        params.unset("comm_ici_enabled")
        params.unset("sched_native")


def test_new_arena_binding_parity():
    nat = _new_binding_run(1)
    py = _new_binding_run(0)
    assert nat["vals"] == py["vals"] == [6.0] * 4
    assert nat["nb_tasks"] == py["nb_tasks"] == 0
    assert nat["pending"] == py["pending"] == 0
    assert nat["bailouts"] == {}


def _shm_mix_worker(ctx, rank, nranks):
    """Per-rank body of the 2-rank interleave property: a cross-rank
    RW chain (remote activations, Python path by design), a rank-LOCAL
    RW chain and trivial CTL tasks (both C-chain-eligible even with
    the RemoteDepEngine attached — r17 comm-attached fast-complete),
    all in one taskpool."""
    import numpy as np
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    from parsec_tpu.native import load_schedext
    se_ = load_schedext()
    NT, NB, NE = 8, 6, 24
    V = VectorTwoDimCyclic(mb=4, lm=NT * 4, nodes=nranks, myrank=rank)
    for m, _ in V.local_tiles():
        V.data_of(m).copy_on(0).payload[:] = 0.0
    L = VectorTwoDimCyclic(mb=1, lm=nranks, nodes=nranks, myrank=rank,
                           name="L")
    for m, _ in L.local_tiles():
        L.data_of(m).copy_on(0).payload[:] = 0.0
    events = []
    g = PTG("mix", NT=NT, NB=NB, NE=NE)
    g.task("E", i=Range(0, NE - 1)) \
        .affinity(lambda i, L=L, nr=nranks: L(i % nr)) \
        .flow("x", "CTL").body(lambda: None)
    g.task("S", c=Range(0, nranks - 1), k=Range(0, NB - 1)) \
        .affinity(lambda c, k, L=L: L(c)) \
        .flow("T", "RW",
              IN(DATA(lambda c, k, L=L: L(c)), when=lambda c, k: k == 0),
              IN(TASK("S", "T", lambda c, k: dict(c=c, k=k - 1)),
                 when=lambda c, k: k > 0),
              OUT(TASK("S", "T", lambda c, k: dict(c=c, k=k + 1)),
                  when=lambda c, k, NB=NB: k < NB - 1)) \
        .body(lambda T: T + 1.0)
    g.task("R", k=Range(0, NT - 1)) \
        .affinity(lambda k, V=V: V(k)) \
        .flow("T", "RW",
              IN(DATA(lambda k, V=V: V(k)), when=lambda k: k == 0),
              IN(TASK("R", "T", lambda k: dict(k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("R", "T", lambda k: dict(k=k + 1)),
                  when=lambda k, NT=NT: k < NT - 1),
              OUT(DATA(lambda k, V=V: V(k)))) \
        .body(lambda T: T + 1.0)
    tp = g.build()
    for ev in ("select", "exec_begin", "exec_end", "complete_exec",
               "task_discard"):
        ctx.pins_register(ev, lambda es, e, t: events.append(e))
    bail0 = dict(se_.bailout_stats()) if se_ else {}
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    bail = {}
    if se_ is not None:
        after = se_.bailout_stats()
        bail = {k: after[k] - bail0.get(k, 0) for k in after
                if after[k] - bail0.get(k, 0)}
    cross = {m: float(np.asarray(
        V.data_of(m).pull_to_host().payload)[0])
        for m, _ in V.local_tiles()}
    local = {m: float(np.asarray(L.data_of(m).copy_on(0).payload)[0])
             for m, _ in L.local_tiles()}
    counts = {ev: events.count(ev) for ev in set(events)} \
        if events else {}
    return {"cross": cross, "local": local, "counts": counts,
            "nb_tasks": tp.nb_tasks, "pending": tp.nb_pending_actions,
            "bailouts": bail,
            "native": 1 if ctx.scheduler.name == "native" else 0}


def _shm_mix(native: int):
    from parsec_tpu.comm.launch import run_distributed
    env = {"PARSEC_MCA_SCHED_NATIVE": str(native),
           "PARSEC_MCA_COMM_TRANSPORT": "shm",
           # the conftest's 8-device virtual mesh would attach an
           # IciEngine in the children and gate the extended chain
           "PARSEC_MCA_COMM_ICI_ENABLED": "0"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        return run_distributed(_shm_mix_worker, 2, timeout=120)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_two_rank_shm_fast_complete_interleave():
    """Comm-attached fast-complete under real remote traffic: local
    trivial + local data-chain tasks ride the C chain while the
    cross-rank chain's activations interleave through the shm
    transport — identical results, per-rank PINS counts, and termdet
    finals vs the Python path, and the ONLY bailouts on the native
    legs are the cross-rank chain's own (plan-time comm_buffered /
    writeback), never the local classes'."""
    nat = _shm_mix(1)
    py = _shm_mix(0)
    for r in range(2):
        assert nat[r]["native"] == 1 and py[r]["native"] == 0
        # identical DAG results per rank
        assert nat[r]["cross"] == py[r]["cross"]
        assert nat[r]["local"] == py[r]["local"]
        # local chains accumulated NB increments in place
        assert list(nat[r]["local"].values()) == [6.0]
        # per-rank PINS parity and drained termdet on both paths
        assert nat[r]["counts"] == py[r]["counts"]
        assert nat[r]["nb_tasks"] == py[r]["nb_tasks"] == 0
        assert nat[r]["pending"] == py[r]["pending"] == 0
        # the ONLY tasks that may leave the C chain are the 4
        # cross-rank R tasks this rank owns: a remote ToTask successor
        # bails at plan time (comm_buffered), the final writeback task
        # bails statically — the 24/2 E and 6 S tasks contributed ZERO,
        # which is the comm-attached fast-complete property (a
        # regression would add this rank's 18 local tasks).  AT MOST 4,
        # not exactly 4: an R task whose activation lands while the
        # worker idles is picked up by the idle probe (spin poll, or
        # the doorbell's pre-wait probe) and goes straight to the
        # Python path without entering the C chain, so it counts no
        # bailout — how many of the 4 are counted is timing, and on a
        # loaded host fewer are (3 under the six-worker run).  The
        # reasons are not timing: comm_buffered for an R task with a
        # remote successor, non_trivial for the last R task's writeback
        bail = nat[r]["bailouts"]
        assert sum(bail.values()) <= 4, bail
        assert set(bail) <= {"comm_buffered", "non_trivial"}, bail
        assert bail.get("non_trivial", 0) <= 1, bail
    # cross-rank chain value: tile k ends at k+1, merged across ranks
    merged = {}
    for r in nat:
        merged.update(r["cross"])
    assert merged == {k: float(k + 1) for k in range(8)}


def test_taskcore_object_contract():
    """vt.build_one's TaskCore matches Task field-for-field for the
    attributes every runtime layer reads, shares the process-global
    seq counter, and reprs identically."""
    from parsec_tpu.core.task import Task, TaskClass
    from parsec_tpu.core.taskpool import ParameterizedTaskpool
    params.set("sched_native", 1)
    try:
        tp = ParameterizedTaskpool("tc-contract")
        tp.priority = 7
        tc = tp.add_task_class(TaskClass(
            "C", params=[("i", lambda g, l: range(4))],
            priority=lambda loc: loc["i"] * 10,
            body=lambda es, task: None))
        vt = tc.native_vt()
        assert vt is not None and vt.trivial
        ct = vt.build_one({"i": 3})
        pt = Task(tc, tp, {"i": 3})
        assert type(ct) is se.TaskCore
        assert ct.key == pt.key == ("C", 3)
        assert ct.priority == pt.priority == 37   # class prio + pool bias
        assert ct.locals == pt.locals
        assert ct.status == 0 and ct.chore_mask == 0xFFFF
        assert ct.data == {} and ct.input_sources == {}
        assert ct.pinned_flows == set()
        assert ct.ready_at is None and ct.mtr_t0 is None
        assert ct.pool_epoch == 0 and ct.retries == 0
        assert repr(ct) == repr(pt) == "C(i=3)"
        # one process-global sequence: C- and Python-constructed tasks
        # interleave monotonically (lineage orders by seq)
        assert pt.seq == ct.seq + 1
        b = vt.build_range("i", 0, 4, 1)
        assert [t.key for t in b] == [("C", i) for i in range(4)]
        assert [t.priority for t in b] == [7, 17, 27, 37]
    finally:
        params.unset("sched_native")


def test_nontrivial_class_has_no_trivial_vtable():
    """Data flows keep a class off the TRIVIAL chain, but a single-cpu
    class with binding-table-coverable flows is extended-chain
    (cchain) eligible since r17; multiple incarnations keep a class
    off both chains (construction stays)."""
    from parsec_tpu.core.task import (Dep, FromDesc, RW, TaskClass)
    from parsec_tpu.core.taskpool import ParameterizedTaskpool
    params.set("sched_native", 1)
    try:
        tp = ParameterizedTaskpool("vt-gate")
        tc = tp.add_task_class(TaskClass(
            "D", params=[("i", lambda g, l: range(2))],
            flows=[RW("T", inputs=[Dep(FromDesc(lambda loc: None))])],
            body=lambda es, task: None))
        vt = tc.native_vt()
        assert vt is not None and not vt.trivial
        assert vt.cchain == 1
        tc2 = tp.add_task_class(TaskClass(
            "D2", params=[("i", lambda g, l: range(2))],
            incarnations=[("cpu", lambda es, task: None),
                          ("tpu", lambda es, task: None)]))
        vt2 = tc2.native_vt()
        assert vt2 is None or (not vt2.trivial and vt2.cchain == 0)
    finally:
        params.unset("sched_native")


def test_invalid_hook_return_is_contained_on_native_path():
    """A trivial body returning an int that is no HookReturn code must
    become a CONTAINED task failure on the C chain, exactly like the
    Python chain — not a ValueError escaping run_quantum that kills
    the worker thread and hangs the run with zero recorded errors
    (the review-round repro)."""
    import re
    from parsec_tpu.core.task import TaskClass
    from parsec_tpu.core.taskpool import ParameterizedTaskpool
    for native in (1, 0):
        params.set("sched_native", native)
        try:
            # raw incarnation hook (no PTG value-normalizing wrapper):
            # its return IS treated as a lifecycle code
            tp = ParameterizedTaskpool("badret")
            tp.add_task_class(TaskClass(
                "B", params=[("i", lambda g_, l: range(4))],
                properties={"idempotent": False},
                incarnations=[("cpu", lambda es, task: 7)]))
            with Context(nb_cores=2) as ctx:
                ctx.add_taskpool(tp)
                with pytest.raises(RuntimeError,
                                   match=re.escape("task B(")):
                    ctx.wait(timeout=15)
        finally:
            params.unset("sched_native")


def test_batched_termdet_epoch_fence():
    """A torn-generation batch flush drops under the termdet lock
    instead of corrupting the re-counted pool (the recovery rewind
    contract for accumulated decrements)."""
    from parsec_tpu.core.taskpool import Taskpool
    from parsec_tpu.core.termdet import LocalTermdet, TermdetState
    tp = Taskpool("fence")
    td = LocalTermdet()
    fired = []
    td.monitor(tp, lambda: fired.append(1))
    td.taskpool_addto_nb_tasks(tp, 5)
    # matching epoch applies
    assert td.taskpool_addto_nb_tasks(tp, -2, epoch=tp.run_epoch) == 3
    # a restart bumped the generation: the stale batch drops whole
    tp.run_epoch += 1
    assert td.taskpool_addto_nb_tasks(tp, -3, epoch=0) == 3
    assert tp.nb_tasks == 3
    # current-generation flushes keep applying
    assert td.taskpool_addto_nb_tasks(tp, -3, epoch=1) == 0
    assert not fired   # NOT_READY: no termination fired


def test_doorbell_suppression_no_lost_wakeup():
    """ring_doorbell skips the condvar entirely while no worker has
    raised its waiting flag, and the probe-under-lock discipline means
    a push racing the flag is never lost: N sequential waves complete
    with the coalesced doorbell counted."""
    done = []
    g = PTG("db", N=64)
    g.task("E", i=Range(0, 63)).flow("x", "CTL") \
        .body(lambda: done.append(1))
    with Context(nb_cores=2) as ctx:
        for _ in range(5):
            p = PTG("dbw", N=64)
            p.task("E", i=Range(0, 63)).flow("x", "CTL") \
                .body(lambda: done.append(1))
            ctx.add_taskpool(p.build())
            ctx.wait(timeout=20)
        # idle workers park with their waiting flag raised; the flag
        # count can never exceed the worker population
        assert 0 <= ctx._db_waiters <= ctx.nb_cores
    assert len(done) == 5 * 64


@pytest.mark.slow
def test_chaos_kill_with_c_task_core_active():
    """A mid-run rank kill with the C task core explicitly active —
    including the r17 extended chain, which sched_native=1 arms: the
    recover catalog's minimal-replay case must still pass (lineage
    recorded from completions while sched_native=1 — BOTH C chains'
    lineage gate defers those pools to the recording path)."""
    env = dict(os.environ)
    env["PARSEC_MCA_SCHED_NATIVE"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos.py"),
         "--only", "kill-minimal-recover", "--seeds", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASS" in r.stdout
