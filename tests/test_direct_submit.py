"""The direct hand-in (core/scheduling.schedule, ``_hand_in``): on a
context that drives ONE accelerator, a ready task whose first incarnation
is that accelerator goes from the thread that released it — a device's
completer inside its pass, a DTD pool's inserter — straight to the
device's queue, progressed by the same ``task_progress`` a worker runs.
No scheduler push, no doorbell, no worker.  Counted by
``DeviceStats.direct_submits``; everything else keeps the worker path:
the tasks that start a PTG pool, host incarnations, retries, and every
task of a context with several devices."""

import threading

import numpy as np
import pytest

import bench
from parsec_tpu.apps import potrf
from parsec_tpu.core import scheduling
from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.devices.xla import XlaDevice
from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
from parsec_tpu.utils import faultinject
from parsec_tpu.utils.mca import params

NT, MB = 8, 16
TASKS = NT * (NT + 1) * (NT + 2) // 6          # 120
#: the host-paced cells' MCA (benchmark/configs/dplasma_potrf_bf16.json)
CELL_MCA = {"device_fuse": 8, "device_runahead": 48,
            "device_inflight_depth": 32}


def _spd(n, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n)).astype(np.float32)
    return m @ m.T + n * np.eye(n, dtype=np.float32)


def _job(front="ptg", device="tpu", ndev=1, mca=None, watch=None):
    """One factorization of the nt = 8 Cholesky on ``ndev`` CPU devices
    with the cell's MCA.  Returns the devices' counters summed, the
    ``XlaDevice.submit`` calls made on the workers, and the context's
    streams; the factor is checked."""
    n = NT * MB
    spd = _spd(n)
    mca = {"device_max": ndev, **CELL_MCA, **(mca or {})}
    for k, v in mca.items():
        params.set(k, v)
    try:
        with Context(nb_cores=4) as ctx:
            devs = ctx.device_registry.accelerators
            assert len(devs) == ndev
            if watch is not None:
                watch(ctx)
            A = TwoDimBlockCyclic(mb=MB, nb=MB, lm=n,
                                  ln=n).from_array(spd.copy())
            build = {"ptg": potrf.potrf_taskpool,
                     "dtd": potrf.potrf_dtd_taskpool}[front]
            tp = build(A, device=device)

            def job():
                ctx.add_taskpool(tp)
                ctx.wait(timeout=120)
            _all, on = bench._call_counts(
                {"submit": XlaDevice.submit.__code__}, job,
                on_threads=("parsec-worker",))
            st = {}
            for d in devs:
                for k, v in d.stats.as_dict().items():
                    st[k] = st.get(k, 0) + v
            streams = list(ctx.streams)
    finally:
        for k in mca:
            params.unset(k)
    L = np.tril(A.to_array())
    err = np.abs(L @ L.T - spd).max() / np.abs(spd).max()
    assert err < 1e-4, err
    return st, on["parsec-worker"]["submit"], streams


@pytest.mark.parametrize("front, roots", [("ptg", 1), ("dtd", 0)])
def test_every_device_task_but_the_roots_is_handed_in(front, roots):
    """PTG: only POTRF(0), which the pool's start-up returns, goes through
    a worker.  DTD: none — the inserter is a releasing thread, so even the
    tasks ready at their insert are handed in by it.  Chain heads are
    held and chained as before: one POTRF -> TRSM program a panel."""
    st, worker_submits, _streams = _job(front)
    assert st["executed_tasks"] + st["held_tasks"] == TASKS
    assert st["direct_submits"] == TASKS - roots
    assert worker_submits == roots
    assert st["held_tasks"] == st["chained_launches"] == NT - 1


def test_two_releasing_threads_under_a_short_switch_interval_lose_nothing():
    """A DTD pool whose window keeps its inserter behind the completer:
    both hand tasks in, trading places every 10 us; every task is handed
    in once, the factor is right and the device gives back all its
    load."""
    import sys
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        st, worker_submits, _streams = _job(
            "dtd", mca={"dtd_window_size": 16, "dtd_threshold_size": 8})
    finally:
        sys.setswitchinterval(interval)
    assert st["direct_submits"] == TASKS and worker_submits == 0
    assert st["executed_tasks"] + st["held_tasks"] == TASKS


def test_the_hand_in_runs_on_its_own_threads_streams():
    """The progress of a handed-in task runs on the stream of the thread
    that released it — the device's stream for its completer, a stream
    of its own for the DTD inserter — never on a worker's."""
    seen = []
    real = scheduling.task_progress

    def progress(es, task, *a, **kw):
        name = threading.current_thread().name
        if not name.startswith("parsec-worker"):
            seen.append((name, es))
        return real(es, task, *a, **kw)

    mp = pytest.MonkeyPatch()
    mp.setattr(scheduling, "task_progress", progress)
    try:
        _st, _w, streams = _job("dtd")
    finally:
        mp.undo()
    threads = {name for name, _es in seen}
    assert any(t.startswith("xla-fin") for t in threads)
    assert "MainThread" in threads                      # the inserter
    for _name, es in seen:
        assert es not in streams and es.releaser


def test_a_pass_queues_what_it_made_ready_once_in_priority_order():
    """SRC lets go of twelve MULs of mixed priority in one release: the
    completer progresses them itself, takes the device's lock for none of
    them in ``submit``, and queues all twelve in ONE ``enqueue`` —
    highest priority first, release order among equals, as the ready
    queue would have popped them."""
    MT = 12
    mb = 8
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=(MT + 1) * mb)
    for _m, n in A.local_tiles():
        A.data_of(0, n).copy_on(0).payload[:] = float(n)
    p = PTG("fan", MT=MT)
    p.task("SRC") \
        .affinity(lambda A=A, MT=MT: A(0, MT)) \
        .flow("T", "RW", IN(DATA(lambda A=A, MT=MT: A(0, MT))),
              OUT(DATA(lambda A=A, MT=MT: A(0, MT)))) \
        .flow("go", "CTL", OUT(TASK(
            "MUL", "go", lambda MT=MT: [dict(n=n) for n in range(MT)]))) \
        .body(lambda T: T + 1.0, device="tpu")
    p.task("MUL", n=Range(0, MT - 1)) \
        .affinity(lambda n, A=A: A(0, n)) \
        .priority(lambda n: n % 3) \
        .flow("T", "RW", IN(DATA(lambda n, A=A: A(0, n))),
              OUT(DATA(lambda n, A=A: A(0, n)))) \
        .flow("go", "CTL", IN(TASK("SRC", "go", lambda n: dict()))) \
        .body(lambda T: T * 2.0, device="tpu")
    progressed, queued, locked = [], [], []
    real_progress, real_enqueue = scheduling.task_progress, \
        XlaDevice.enqueue
    real_submit = XlaDevice.submit
    in_submit = threading.local()

    def progress(es, task, *a, **kw):
        if task.task_class.name == "MUL":
            progressed.append(task)
        return real_progress(es, task, *a, **kw)

    def enqueue(self, es, items):
        real_enqueue(self, es, items)
        queued.append([it[0] for it in items])

    def submit(self, es, task, spec):
        in_submit.on = True
        try:
            return real_submit(self, es, task, spec)
        finally:
            in_submit.on = False

    class Counted:
        """The device's condition, counting the holds taken inside a
        ``submit`` on the completer's thread."""

        def __init__(self, cond):
            self._c = cond

        def __enter__(self):
            if getattr(in_submit, "on", False) and threading.current_thread(
                    ).name.startswith("xla-fin"):
                locked.append(1)
            return self._c.__enter__()

        def __exit__(self, *exc):
            return self._c.__exit__(*exc)

        def __getattr__(self, name):
            return getattr(self._c, name)

    mp = pytest.MonkeyPatch()
    mp.setattr(scheduling, "task_progress", progress)
    mp.setattr(XlaDevice, "enqueue", enqueue)
    mp.setattr(XlaDevice, "submit", submit)
    params.set("device_max", 1)
    try:
        with Context(nb_cores=2) as ctx:
            (dev,) = ctx.device_registry.accelerators
            dev._cond = Counted(dev._cond)
            ctx.add_taskpool(p.build())
            ctx.wait(timeout=120)
            assert dev.stats.direct_submits == MT
    finally:
        params.unset("device_max")
        mp.undo()
    assert locked == []
    assert len(queued) == 1 and len(queued[0]) == MT
    want = sorted(progressed, key=lambda t: t.priority, reverse=True)
    assert queued[0] == want
    assert [t.priority for t in queued[0]] == [2] * 4 + [1] * 4 + [0] * 4
    for n in range(MT):
        np.testing.assert_allclose(
            np.asarray(A.data_of(0, n).pull_to_host().payload), 2.0 * n)


def test_several_devices_keep_the_workers_path():
    """Two devices: placement is owner-computes and idle workers drive the
    ICI engine, so no task is handed in."""
    st, worker_submits, _streams = _job(ndev=2)
    assert st["direct_submits"] == 0
    assert worker_submits == TASKS


def test_a_host_pool_keeps_the_workers_path():
    """Host incarnations are run by the workers: nothing is handed in."""
    st, worker_submits, _streams = _job(device="cpu")
    assert st["direct_submits"] == 0 and worker_submits == 0
    assert st["executed_tasks"] == 0


def test_a_retried_task_goes_back_through_a_worker():
    """POTRF(1) fails once in its progress on the completer: its retry is
    a reschedule (``distance`` 1), so a worker submits it; every other
    task but the root is handed in."""
    faultinject.arm("seed=1;fail_task=key~POTRF(k=1),n=1")
    try:
        st, worker_submits, _streams = _job(mca={"task_retry_max": 1})
    finally:
        faultinject.disarm()
    assert st["executed_tasks"] + st["held_tasks"] == TASKS
    assert st["direct_submits"] == TASKS - 2
    assert worker_submits == 2
