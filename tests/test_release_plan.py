"""The release plan (PR 34): ``engine.release_deps`` is one walk over
``TaskClass.release_plan()``.  What it delivers is held to what the INPUT
deps declare — every ``FromTask`` input of every instance of the space,
enumerated here without the walk — for potrf, geqrf, gemm, a JDF text
and a fan-out onto a writer; the counters say which arm ran; arena
buffers come home; the affinity is not asked on a context of one rank."""

import threading
import time

import numpy as np
import pytest

from parsec_tpu.core import engine
from parsec_tpu.core.context import Context
from parsec_tpu.core.task import FromTask
from parsec_tpu.data.data import ACCESS_WRITE, FLAG_COW
from parsec_tpu.data.matrix import TwoDimBlockCyclic, VectorTwoDimCyclic
from parsec_tpu.dsl.ptg.api import (DATA, IN, NEW, OUT, PTG, Range, TASK,
                                    _named, _positional)
from parsec_tpu.dsl.ptg.jdf import jdf_taskpool
from parsec_tpu.prof.grapher import DotGrapher

MB = 8


def _spd(n, seed=0):
    m = np.random.default_rng(seed).standard_normal((n, n)).astype(np.float32)
    return m @ m.T + n * np.eye(n, dtype=np.float32)


def _square(nt, arr=None, seed=1):
    n = nt * MB
    if arr is None:
        arr = np.random.default_rng(seed).standard_normal(
            (n, n)).astype(np.float32)
    return TwoDimBlockCyclic(mb=MB, nb=MB, lm=n, ln=n).from_array(arr)


def _potrf(nt=6, device="cpu"):
    from parsec_tpu.apps.potrf import potrf_taskpool
    return potrf_taskpool(_square(nt, _spd(nt * MB)), device=device)


def _geqrf(nt=4, device="cpu"):
    from parsec_tpu.apps.qr import qr_taskpool
    return qr_taskpool(_square(nt), device=device)


def _gemm(panel_bcast=None):
    from parsec_tpu.apps.gemm import gemm_taskpool
    return gemm_taskpool(_square(3, seed=2), _square(3, seed=3),
                         _square(3, seed=4), device="cpu",
                         panel_bcast=panel_bcast)


#: a broadcast read by NB / 2 + 1 readers and, through a copy of its own,
#: by a writer that a CTL gather orders after them (the reference's
#: Ex07_RAW_CTL shape): range outputs, a range input, a WRITE consumer of
#: a fan-out, a write-back
_JDF = """
NB      [type = int]
mydata  [type = "parsec_data_collection_t*"]

TaskBcast(k)

k = 0 .. 1

: mydata( k )

RW A <- mydata( k )
     -> A TaskRecv( k, 0 .. NB .. 2 )
     -> A TaskUpdate( k )

BODY
END

TaskRecv(k, n)

k = 0 .. 1
n = 0 .. NB .. 2

: mydata( k )

READ A <- A TaskBcast( k )
CTL ctl -> ctl TaskUpdate( k )

BODY
END

TaskUpdate(k)

k = 0 .. 1

: mydata( k )

RW A <- A TaskBcast( k )
     -> mydata( k )
CTL ctl <- ctl TaskRecv( k, 0 .. NB .. 2 )

BODY
END
"""


def _jdf():
    V = VectorTwoDimCyclic(mb=1, lm=2, dtype=np.int32)
    return jdf_taskpool(
        _JDF, globals={"NB": 6}, data={"mydata": V}, name="bcast",
        bodies={"TaskBcast": lambda A, k: A.__setitem__(0, k + 1),
                "TaskRecv": lambda A: None,
                "TaskUpdate": lambda A, k: A.__setitem__(0, -k - 1)})


def _fanout(seen=None):
    """P's tile goes to two readers and two writers: each writer takes a
    copy-on-write alias, each reader the tile itself."""
    V = VectorTwoDimCyclic(mb=4, lm=4)
    seen = {} if seen is None else seen

    def bump(X):
        X += 1.0

    def read(X, i):
        seen["R", i] = float(X[0])

    def write(X, i):
        X += i + 1.0
        seen["W", i] = float(X[0])

    g = PTG("fanout")
    g.task("P").affinity(lambda: V(0)) \
        .flow("X", "RW", IN(DATA(lambda: V(0))),
              OUT(TASK("R", "X", lambda: [dict(i=0), dict(i=1)])),
              OUT(TASK("W", "X", lambda: [dict(i=0), dict(i=1)]))) \
        .body(bump)
    g.task("R", i=Range(0, 1)).affinity(lambda i: V(0)) \
        .flow("X", "READ", IN(TASK("P", "X", lambda i: dict()))) \
        .body(read)
    g.task("W", i=Range(0, 1)).affinity(lambda i: V(0)) \
        .flow("X", "RW", IN(TASK("P", "X", lambda i: dict()))) \
        .body(write)
    return g.build()


CASES = {"potrf": _potrf, "geqrf": _geqrf, "gemm": _gemm,
         "gemm_bcast": lambda: _gemm(panel_bcast=True), "jdf": _jdf,
         "fanout": _fanout}


def _declared(tp):
    """(producer key, producer flow, consumer key, consumer flow) of
    every task-fed input of every instance, as the INPUT side says it."""
    edges = []
    for tc in tp.task_classes.values():
        for loc in tc.iter_space(tp.globals):
            for flow in tc.flows:
                for dep in flow.inputs:
                    if not isinstance(dep.end, FromTask) \
                            or not dep.applies(loc):
                        continue
                    ptc = tp.task_classes[dep.end.task_class]
                    for pl in dep.end.instances(loc):
                        pk = ptc.make_key(ptc.complete_locals(dict(pl)))
                        edges.append((pk, dep.end.flow, tc.make_key(loc),
                                      flow.name))
    return sorted(edges)


def _run(tp, grapher=False, nb_cores=2):
    """Run ``tp``; what the walk delivered, seen from outside it: the
    ``deliver_dep`` PINS payloads, the grapher's edges where asked, and
    every ``engine.deliver_dep`` call's copy beside the producer's."""
    pins, calls = [], []
    lock = threading.Lock()
    real = engine.deliver_dep

    def spy(taskpool, succ_tc, succ_locals, flow_name, copy, source,
            key=None):
        with lock:    # the alias flag as handed over: a stage-in clears it
            calls.append((key, flow_name, copy, source,
                          bool(copy is not None and copy.flags & FLAG_COW)))
        return real(taskpool, succ_tc, succ_locals, flow_name, copy, source,
                    key)

    engine.deliver_dep = spy
    try:
        with Context(nb_cores=nb_cores) as ctx:
            g = DotGrapher()
            if grapher:
                g.install(ctx)
            ctx.pins_register(
                "deliver_dep", lambda es, ev, p: pins.append(
                    (p[0].key, p[0], p[1].make_key(p[2]), p[3])))
            ctx.add_taskpool(tp)
            ctx.wait(timeout=120)
            stats = ctx.release_stats.as_dict()
    finally:
        engine.deliver_dep = real
    return pins, calls, g._edges, stats


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_delivers_the_edges_the_inputs_declare(case):
    tp = CASES[case]()
    declared = _declared(tp)
    assert declared, "the case has no task-fed input"
    pins, calls, _, stats = _run(tp)
    # producer, consumer and the consumer's flow name, edge for edge
    assert sorted((pk, ck, cf) for pk, _, ck, cf in pins) == \
        sorted((pk, ck, cf) for pk, _, ck, cf in declared)
    # each delivery reached deliver_dep once, under the key the walk made
    assert sorted((k, f) for k, f, _, _, _ in calls) == \
        sorted((ck, cf) for _, _, ck, cf in declared)
    assert stats["deliveries"] == len(declared)
    assert stats["general_deliveries"] == 0


@pytest.mark.parametrize("case", sorted(CASES))
def test_under_a_grapher_every_delivery_is_general_and_named(case):
    tp = CASES[case]()
    declared = _declared(tp)
    _, _, edges, stats = _run(tp, grapher=True)
    # the grapher hears the PRODUCER's flow name of every edge
    assert sorted(edges) == sorted((pk, ck, pf) for pk, pf, ck, _ in declared)
    assert stats["deliveries"] == stats["general_deliveries"] \
        == len(declared)


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_writer_of_a_fan_out_takes_a_copy_on_write_alias(case):
    tp = CASES[case]()
    by_producer = {}
    for pk, pf, ck, cf in _declared(tp):
        by_producer.setdefault((pk, pf), []).append((ck, cf))
    classes = tp.task_classes
    want = sorted(
        (ck, cf) for cons in by_producer.values() if len(cons) > 1
        for ck, cf in cons
        if classes[ck[0]].flow(cf).access & ACCESS_WRITE)
    if case in ("fanout", "jdf"):
        assert want, "the case was built to have one"
    pins, calls, _, _ = _run(tp)
    produced = {(ck, cf): task.data.get(pf) for (pk, pf), cons
                in by_producer.items() for ck, cf in cons
                for k, task, c, f in pins if k == pk and (c, f) == (ck, cf)}
    aliased = []
    for key, flow_name, copy, _, cow in calls:
        if cow:
            aliased.append((key, flow_name))
            assert copy is not produced[key, flow_name]
        elif (key, flow_name) in produced:
            assert copy is produced[key, flow_name], (key, flow_name)
    assert sorted(aliased) == want


def test_fan_out_results():
    """The aliases are private: each writer adds to what P left, no
    reader or writer sees another's update."""
    seen = {}
    with Context(nb_cores=2) as ctx:
        ctx.add_taskpool(_fanout(seen))
        ctx.wait(timeout=60)
    assert seen == {("R", 0): 1.0, ("R", 1): 1.0, ("W", 0): 2.0,
                    ("W", 1): 3.0}


# -- the plan ---------------------------------------------------------------

def test_the_plan_is_made_once_and_is_the_c_twins_table():
    tp = _potrf(nt=3)
    tc = tp.task_classes["TRSM"]
    plan = tc.release_plan()
    assert tc.release_plan() is plan
    (name, index, access, deps, flow), = plan
    assert (name, index, flow) == ("C", tc.flow("C").flow_index,
                                   tc.flow("C"))
    kinds = [kind for _, kind, _ in deps]
    assert kinds == [tc._CK_TOTASK] * 3 + [tc._CK_TODESC]
    end, succ_tc, dflow, write, dep, edge_dtt = deps[0][2]
    assert (succ_tc, dflow, write, edge_dtt) == \
        (tp.task_classes["SYRK"], "R", 0, False)
    assert dep.end is end
    # GEMM's C goes on to a writer
    assert [p[3] for _, k, p in tp.task_classes["GEMM"].release_plan()[0][3]
            ] == [1, 1]


def test_the_first_arrivals_count_is_evaluated_an_instance():
    """The countdown's goal is what the task-fed inputs say for THIS
    instance: a guard that applies, a range's length (a lambda may
    return a list whatever it looks like, so nothing is a constant of
    the class but "no task-fed input at all")."""
    classes = _jdf().task_classes
    assert classes["TaskBcast"].nb_task_inputs({"k": 0}) == 0
    assert classes["TaskRecv"].nb_task_inputs({"k": 0, "n": 2}) == 1
    assert classes["TaskUpdate"].nb_task_inputs({"k": 0}) == 1 + 4
    trsm = _potrf(nt=3).task_classes["TRSM"]
    assert trsm.nb_task_inputs({"k": 0, "m": 1}) == 1       # W alone
    assert trsm.nb_task_inputs({"k": 1, "m": 2}) == 2       # W and C
    V = VectorTwoDimCyclic(mb=1, lm=4)
    g = PTG("gather")
    g.task("A", k=Range(0, 3)).affinity(lambda k: V(k)) \
        .flow("X", "CTL", OUT(TASK("B", "X", lambda k: dict(k=0)))) \
        .body(lambda: None)
    g.task("B", k=Range(0, 0)).affinity(lambda k: V(k)) \
        .flow("X", "CTL",
              IN(TASK("A", "X", lambda k: [dict(k=i) for i in range(4)]))) \
        .body(lambda: None)
    tp = g.build()
    assert tp.task_classes["B"].nb_task_inputs({"k": 0}) == 4
    _, _, _, stats = _run(tp)
    assert stats["deliveries"] == 4


def test_a_class_added_later_is_in_the_plan():
    """The plan is kept until the pool's set of classes changes: a class
    added after a producer's plan was asked is delivered to, and a class
    no pool holds keeps no plan."""
    V = VectorTwoDimCyclic(mb=1, lm=2)
    seen = []
    g = PTG("late")
    g.task("A", k=Range(0, 1)).affinity(lambda k: V(k)) \
        .flow("X", "RW", IN(DATA(lambda k: V(k))),
              OUT(TASK("B", "X", lambda k: dict(k=k)))) \
        .body(lambda X: X + 1.0)
    tp = g.build()
    a = tp.task_classes["A"]
    (_, kind, _), = a.release_plan()[0][3]
    assert kind == a._CK_NOCLASS
    h = PTG("late-b")
    h.task("B", k=Range(0, 1)).affinity(lambda k: V(k)) \
        .flow("X", "READ", IN(TASK("A", "X", lambda k: dict(k=k)))) \
        .body(lambda X, k: seen.append(k))
    b = h.build().task_classes["B"]
    assert b.release_plan() == () and b._release_plan == ()
    tp.add_task_class(b)
    (_, kind, payload), = a.release_plan()[0][3]
    assert kind == a._CK_TOTASK and payload[1] is b
    _, _, _, stats = _run(tp)
    assert sorted(seen) == [0, 1] and stats["deliveries"] == 2
    from parsec_tpu.core.task import TaskClass
    loose = TaskClass("L", params=[("k", None)], flows=a.flows)
    assert loose.release_plan()[0][3][0][1] == a._CK_NOCLASS
    assert loose._release_plan is None      # no pool: nothing kept


def test_a_dep_the_pool_cannot_resolve_is_an_error_where_it_applies():
    V = VectorTwoDimCyclic(mb=1, lm=2)
    g = PTG("lost")
    g.task("T", k=Range(0, 1)).affinity(lambda k: V(k)) \
        .flow("X", "RW", IN(DATA(lambda k: V(k))),
              OUT(TASK("NOBODY", "X", lambda k: dict(k=k)),
                  when=lambda k: k == 1)) \
        .body(lambda X: None)
    tp = g.build()
    (_, kind, end), = tp.task_classes["T"].release_plan()[0][3]
    assert kind == tp.task_classes["T"]._CK_NOCLASS \
        and end.task_class == "NOBODY"
    with Context(nb_cores=1) as ctx:
        ctx.add_taskpool(tp)
        with pytest.raises(RuntimeError) as exc:
            ctx.wait(timeout=60)
    assert "NOBODY" in str(exc.value.__cause__)


# -- the counters -----------------------------------------------------------

@pytest.mark.parametrize("nt", [3, 6])
def test_potrf_holds_one_repo_entry_a_panel(nt):
    """A host pool hands on nt - 1 arena copies (POTRF's W): those
    producers alone take a repo entry, and their consumers alone record
    a source."""
    tp = _potrf(nt=nt)
    pins, calls, _, stats = _run(tp)
    assert stats["repo_holds"] == nt - 1
    assert tp.release_stats.as_dict() == stats
    sourced = [(k, f) for k, f, _, src, _ in calls if src is not None]
    assert sorted(sourced) == sorted(
        (("TRSM", k, m), "W") for k in range(nt - 1)
        for m in range(k + 1, nt))
    assert all(len(tc.repo) == 0 for tc in tp.task_classes.values())


def test_the_device_path_hands_on_copies_without_an_arena():
    """On a device the copy a W producer hands on is the device's (the
    arena's host buffer is the device module's to return): no entry."""
    tp = _potrf(nt=4, device="tpu")
    with Context(nb_cores=2) as ctx:
        if not ctx.device_registry.accelerators:
            pytest.skip("no accelerator attached")
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
        stats = ctx.release_stats.as_dict()
    arena = tp.arenas["w"]
    assert stats["repo_holds"] == 0 and stats["general_deliveries"] == 0
    assert stats["deliveries"] == len(_declared(tp))
    assert len(arena._free) == arena.allocated


def test_a_dtt_edge_is_a_general_delivery():
    from parsec_tpu.data.reshape import Dtt
    V = VectorTwoDimCyclic(mb=4, lm=8)
    seen = []
    g = PTG("dtt")
    g.task("A", k=Range(0, 1)).affinity(lambda k: V(k)) \
        .flow("X", "RW", IN(DATA(lambda k: V(k))),
              OUT(TASK("B", "X", lambda k: dict(k=k)))) \
        .body(lambda X: X + 1.5)
    g.task("B", k=Range(0, 1)).affinity(lambda k: V(k)) \
        .flow("X", "READ", IN(TASK("A", "X", lambda k: dict(k=k)),
                              dtt=Dtt(dtype=np.float64))) \
        .body(lambda X: seen.append(X.dtype))
    tp = g.build()
    (_, kind, payload), = tp.task_classes["A"].release_plan()[0][3]
    assert kind == tp.task_classes["A"]._CK_OBAIL and payload[5] is True
    with Context(nb_cores=1) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
        stats = ctx.release_stats.as_dict()
    assert seen == [np.dtype(np.float64)] * 2
    assert stats == {"deliveries": 2, "general_deliveries": 2,
                     "repo_holds": 0}


def test_the_counters_are_scraped():
    tp = _potrf(nt=3)
    with Context(nb_cores=2) as ctx:
        from parsec_tpu.prof.metrics import RuntimeMetrics
        rm = ctx.metrics or RuntimeMetrics(rank=0)
        if ctx.metrics is None:
            rm.install(ctx)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
        got = {s["n"]: s["v"] for s in rm._collect_summed(
            "release_stats", "parsec_release_")}
    assert got == {"parsec_release_deliveries_total": len(_declared(tp)),
                   "parsec_release_repo_holds_total": 2}


# -- arena buffers come home ------------------------------------------------

@pytest.mark.parametrize("device", ["cpu", "tpu"])
def test_a_qr_job_returns_every_panel_to_its_arena(device):
    """Every Q panel a task read is back on its arena's freelist when
    the job ends, each returned once.  (A host pool keeps ONE q1 buffer
    out: GEQRT(nt - 1)'s panel, which no task reads and no entry ever
    held — the parent's accounting too.)"""
    nt = 4
    tp = _geqrf(nt=nt, device=device)
    with Context(nb_cores=4) as ctx:
        if device == "tpu" and not ctx.device_registry.accelerators:
            pytest.skip("no accelerator attached")
        before = {n: (len(a._free), a.allocated)
                  for n, a in tp.arenas.items()}
        ctx.add_taskpool(tp)
        ctx.wait(timeout=180)
        stats = ctx.release_stats.as_dict()
    assert before == {n: (0, 0) for n in tp.arenas}
    out = {n: a.allocated - len(a._free) for n, a in tp.arenas.items()}
    read = {"q1": nt - 1, "q2": nt * (nt - 1) // 2}   # panels with a reader
    if device == "cpu":
        assert out == {"q1": 1, "q2": 0}
        assert {n: a.released for n, a in tp.arenas.items()} == read
        assert stats["repo_holds"] == sum(read.values())
    else:
        assert out == {"q1": 0, "q2": 0}
        assert stats["repo_holds"] == 0
    assert all(len(tc.repo) == 0 for tc in tp.task_classes.values())


def test_a_cancelled_pool_returns_its_frontiers_arena_copies():
    """MAKE(0) cancels its pool and hands its arena tile on: USE(0) is
    discarded at selection, and the discard releases the producer's
    entry (scheduling.task_progress), so the buffer is home again."""
    V = VectorTwoDimCyclic(mb=1, lm=4)
    holder = {}

    def make(W, i):
        if i == 0:
            holder["tp"].cancel()
        W[:] = 3.0

    g = PTG("cancelled")
    g.arena("tmp", (2,))
    g.task("MAKE", i=Range(0, 3)).affinity(lambda i: V(i)) \
        .flow("W", "WRITE", IN(NEW("tmp")),
              OUT(TASK("USE", "W", lambda i: dict(i=i)))) \
        .body(make)
    g.task("USE", i=Range(0, 3)).affinity(lambda i: V(i)) \
        .flow("W", "READ", IN(TASK("MAKE", "W", lambda i: dict(i=i)))) \
        .body(lambda W: None)
    tp = holder["tp"] = g.build()
    arena = tp.arenas["tmp"]
    with Context(nb_cores=1) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)    # returns at the cancel, inside MAKE(0)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not (
                arena.allocated and len(arena._free) == arena.allocated):
            time.sleep(0.01)    # the worker discards what is still queued
    # (the context's sum was taken when the cancel terminated the pool)
    assert tp.release_stats.repo_holds >= 1
    assert arena.allocated >= 1 and len(arena._free) == arena.allocated
    assert all(len(tc.repo) == 0 for tc in tp.task_classes.values())


# -- what a one-rank context never asks ----------------------------------------

@pytest.mark.parametrize("native", [0, 1])
def test_a_release_on_one_rank_never_evaluates_the_affinity(native):
    """Owner computes needs the affinity at startup (once an instance);
    no delivery asks again where no successor can be remote — in the
    Python walk and, for a class of one cpu incarnation, in its C twin
    (whose releases the counters do not see)."""
    from parsec_tpu.utils.mca import params
    V = VectorTwoDimCyclic(mb=1, lm=8)
    asked = []
    lock = threading.Lock()
    gate = {"startup": True}

    def aff(k):
        with lock:
            asked.append((gate["startup"], k))
        return V(k)

    def body(T):
        gate["startup"] = False
        return T + 1.0

    NB = 8
    g = PTG("chain", NB=NB)
    g.task("S", k=Range(0, NB - 1)).affinity(aff) \
        .flow("T", "RW",
              IN(DATA(lambda k: V(0)), when=lambda k: k == 0),
              IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("S", "T", lambda k: dict(k=k + 1)),
                  when=lambda k, NB=NB: k < NB - 1)) \
        .body(body)
    tp = g.build()
    params.set("sched_native", native)
    params.set("comm_ici_enabled", 0)    # the ICI engine asks where a
    try:                                 # successor will run; not the walk
        with Context(nb_cores=2) as ctx:
            assert ctx.nranks == 1 and ctx.comm is None
            ctx.add_taskpool(tp)
            ctx.wait(timeout=60)
            stats = ctx.release_stats.as_dict()
    finally:
        params.unset("comm_ici_enabled")
        params.unset("sched_native")
    assert [k for startup, k in asked if not startup] == []
    assert sorted(k for startup, k in asked if startup) == list(range(NB))
    if not native:
        assert stats == {"deliveries": NB - 1, "general_deliveries": 0,
                         "repo_holds": 0}


# -- expressions called positionally ----------------------------------------

def _count_kwargs_calls(fn):
    """``fn`` wrapped so that a call with keyword arguments is seen."""
    seen = []

    def probe(*a, **kw):
        seen.append((a, kw))
        return fn(*a, **kw)
    probe.__signature__ = __import__("inspect").signature(fn)
    return probe, seen


@pytest.mark.parametrize("fn, params, locals_, want, positional", [
    (lambda: 7, ("k",), {"k": 1}, 7, True),
    (lambda k: k + 1, ("k",), {"k": 1}, 2, True),
    (lambda m, k: (m, k), ("k", "m"), {"k": 1, "m": 2}, (2, 1), True),
    (lambda m, n, k: (m, n, k), ("n", "m", "k"),
     {"k": 1, "m": 2, "n": 3}, (2, 3, 1), True),
    (lambda k, NT=9: k + NT, ("k",), {"k": 1}, 10, True),
    # a task parameter that also has a default: the task's value wins
    (lambda k, m=5: k + m, ("k", "m"), {"k": 1, "m": 2}, 3, True),
    # a default AHEAD of a task parameter, a keyword-only one: by name
    (lambda NT=9, k=0: k + NT, ("k",), {"k": 1}, 10, False),
    (lambda *, k: k, ("k",), {"k": 4}, 4, False),
], ids=["none", "one", "two", "three", "default", "param-with-default",
        "default-first", "keyword-only"])
def test_expressions_are_called_positionally(fn, params, locals_, want,
                                             positional):
    probe, seen = _count_kwargs_calls(fn)
    call = _positional(_named(probe), frozenset(params))
    assert call(dict(locals_)) == want
    assert bool(seen[-1][1]) == (not positional and bool(locals_))
    # any callable(locals_) that is no _named wrapper stays what it is
    plain = lambda loc: loc["k"]
    assert _positional(plain, frozenset(params)) is plain
    assert _positional(None, frozenset(params)) is None


def test_locals_that_lack_a_name_take_the_by_name_diagnosis():
    call = _positional(_named(lambda k, m: k + m), frozenset(("k", "m")))
    assert call({"k": 1, "m": 2}) == 3
    with pytest.raises(KeyError, match="needs 'm'"):
        call({"k": 1})
    # a default stands in, as by name
    call = _positional(_named(lambda k, m=5: k + m), frozenset(("k", "m")))
    assert call({"k": 1}) == 6
    # a name that is no task parameter and has no default: the old error
    call = _positional(_named(lambda k, NT: k + NT), frozenset(("k",)))
    with pytest.raises(KeyError, match="needs 'NT'"):
        call({"k": 1})


def test_built_classes_call_their_expressions_positionally():
    tp = _potrf(nt=3)
    for tc in tp.task_classes.values():
        fns = [tc.affinity, tc.priority]
        for flow in tc.flows:
            for dep in flow.inputs + flow.outputs:
                fns += [dep.guard, getattr(dep.end, "params_fn", None),
                        getattr(dep.end, "ref_fn", None)]
        for fn in fns:
            if fn is not None:
                assert fn.__name__ == "call", (tc.name, fn)


def test_locate_completes_and_keys_once():
    tp = _jdf()
    tc = tp.task_classes["TaskRecv"]
    loc, key = tc.locate({"k": 1, "n": 4})
    assert key == ("TaskRecv", 1, 4) and loc == {"k": 1, "n": 4}
    g = PTG("derived", NT=4)
    g.task("T", k=Range(0, 3), d=lambda g_, loc: [loc["k"] * 2]) \
        .flow("x", "CTL").body(lambda: None)
    tc = g.build().task_classes["T"]
    loc, key = tc.locate({"k": 3})
    assert loc == {"k": 3, "d": 6} and key == ("T", 3, 6)
    assert tc.complete_locals(loc) is loc
    with pytest.raises(KeyError):
        tc.locate({"d": 1})
