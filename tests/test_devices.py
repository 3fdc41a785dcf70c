"""Device-layer tests: XLA offload path on the virtual CPU mesh.

Mirrors the reference's GPU test strategy (reference: tests/dsl/ptg/cuda/
stress.jdf throughput, get_best_device_check.jdf placement; SURVEY.md §4):
device tasks run through the real stage-in / dispatch / async-complete
pipeline, on jax CPU devices standing in for TPU chips.
"""

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.devices.device import DeviceRegistry, HostDevice
from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
from parsec_tpu.utils.mca import params


def make_ctx(**kw):
    return Context(nb_cores=2, **kw)


def test_registry_attach_and_spaces():
    reg = DeviceRegistry()
    assert reg.host.space == 0
    from parsec_tpu.devices.xla import XlaDevice
    import jax
    d = reg.attach(XlaDevice(jax.devices()[0]))
    assert d.space == 1
    assert reg.get(1) is d
    assert reg.accelerators == [d]
    d.fini()


def test_context_attaches_xla_devices():
    with make_ctx() as ctx:
        assert len(ctx.device_registry.accelerators) >= 1
        for d in ctx.device_registry.accelerators:
            assert d.kind in ("xla", "tpu")


def _chain_ptg(A, nt, device):
    """S(k): T = T@T' chain through a single tile, alternating devices."""
    p = PTG("chain", NT=nt)
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
        .body(lambda T: T + 1.0, device=device)
    return p.build()


@pytest.mark.parametrize("device", ["tpu", "cpu"])
def test_device_chain_matches_cpu(device):
    A = TwoDimBlockCyclic(mb=8, nb=8, lm=8, ln=8)
    tile = A.data_of(0, 0).copy_on(0).payload
    tile[:] = 0.0
    with make_ctx() as ctx:
        ctx.add_taskpool(_chain_ptg(A, 10, device))
        ctx.wait()
    np.testing.assert_allclose(np.asarray(A.data_of(0, 0).pull_to_host().payload),
                               np.full((8, 8), 10.0), rtol=1e-6)


def test_device_gemm_tiles_correct():
    """Tiled C += A@B on devices vs numpy."""
    mt = nt = kt = 2
    mb = 16
    rng = np.random.default_rng(0)
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mt * mb, ln=kt * mb,
                          name="A")
    B = TwoDimBlockCyclic(mb=mb, nb=mb, lm=kt * mb, ln=nt * mb,
                          name="B")
    C = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mt * mb, ln=nt * mb,
                          name="C")
    for M in (A, B, C):
        for m, n in M.local_tiles():
            M.data_of(m, n).copy_on(0).payload[:] = rng.standard_normal((mb, mb),
                                                          ).astype(np.float32)
    refA = A.to_array().copy()
    refB = B.to_array().copy()
    refC = C.to_array() + refA @ refB

    p = PTG("gemm", MT=mt, NT=nt, KT=kt)
    p.task("GEMM", m=Range(0, mt - 1), n=Range(0, nt - 1),
           k=Range(0, kt - 1)) \
        .affinity(lambda m, n, C=C: C(m, n)) \
        .flow("Ai", "READ", IN(DATA(lambda m, k, A=A: A(m, k)))) \
        .flow("Bi", "READ", IN(DATA(lambda k, n, B=B: B(k, n)))) \
        .flow("Ci", "RW",
              IN(DATA(lambda m, n, C=C: C(m, n)), when=lambda k: k == 0),
              IN(TASK("GEMM", "Ci", lambda m, n, k: dict(m=m, n=n, k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("GEMM", "Ci",
                       lambda m, n, k: dict(m=m, n=n, k=k + 1)),
                  when=lambda k, KT=kt: k < KT - 1),
              OUT(DATA(lambda m, n, C=C: C(m, n)),
                  when=lambda k, KT=kt: k == KT - 1)) \
        .body(lambda Ai, Bi, Ci: Ci + Ai @ Bi, device="tpu")
    with make_ctx() as ctx:
        ctx.add_taskpool(p.build())
        ctx.wait()
    np.testing.assert_allclose(C.to_array(), refC, rtol=1e-4, atol=1e-4)


def test_device_fallback_to_cpu_body():
    """tpu incarnation declines when no accelerator: cpu body runs."""
    params.set("device_enabled", 0)
    try:
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=4, ln=4)
        A.data_of(0, 0).copy_on(0).payload[:] = 0.0
        with make_ctx() as ctx:
            assert ctx.device_registry.accelerators == []
            p = PTG("fb", NT=1)
            p.task("S", k=Range(0, 0)) \
                .affinity(lambda k, A=A: A(0, 0)) \
                .flow("T", "RW", IN(DATA(lambda A=A: A(0, 0))),
                      OUT(DATA(lambda A=A: A(0, 0)))) \
                .body(lambda T: T + 7.0, device="tpu") \
                .body(lambda T: T + np.float32(3.0))
            ctx.add_taskpool(p.build())
            ctx.wait()
        assert np.asarray(A.data_of(0, 0).pull_to_host().payload)[0, 0] == 3.0
    finally:
        params.unset("device_enabled")


def test_lru_eviction_under_pressure():
    """Tiny copy-cache capacity forces evictions yet stays correct."""
    params.set("device_mem_mb", 1)     # 1 MiB cap
    params.set("device_max", 1)
    try:
        nt = 24
        mb = 128                        # 64 KiB per f32 tile; 24 > 1 MiB cap
        A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=nt * mb, ln=mb)
        for m, n in A.local_tiles():
            A.data_of(m, n).copy_on(0).payload[:] = float(m)
        with make_ctx() as ctx:
            # three chained sweeps over all tiles: proper dep edges between
            # revisits (racing on a tile without deps is UB, as in JDF)
            p = PTG("sweep", NT=nt)
            p.task("S", rep=Range(0, 2), m=Range(0, nt - 1)) \
                .affinity(lambda m, A=A: A(m, 0)) \
                .flow("T", "RW",
                      IN(DATA(lambda m, A=A: A(m, 0)),
                         when=lambda rep: rep == 0),
                      IN(TASK("S", "T", lambda rep, m: dict(rep=rep - 1,
                                                            m=m)),
                         when=lambda rep: rep > 0),
                      OUT(TASK("S", "T", lambda rep, m: dict(rep=rep + 1,
                                                             m=m)),
                          when=lambda rep: rep < 2),
                      OUT(DATA(lambda m, A=A: A(m, 0)),
                          when=lambda rep: rep == 2)) \
                .body(lambda T: T + 1.0, device="tpu")
            ctx.add_taskpool(p.build())
            ctx.wait()
            dev = ctx.device_registry.accelerators[0]
            stats = dev.stats
        for m, n in A.local_tiles():
            np.testing.assert_allclose(
                np.asarray(A.data_of(m, n).pull_to_host().payload),
                float(m) + 3.0)
        assert stats.evictions > 0
        assert stats.executed_tasks == 3 * nt
    finally:
        params.unset("device_mem_mb")
        params.unset("device_max")


def test_retired_pins_release_while_the_completer_idles(monkeypatch):
    """The same sweep on a device whose outputs take 0.3 s to show as
    ready (a busy host, a slow chip).  Retired tasks keep their pins
    until a readiness probe finds them done; the completer used to
    probe only after the NEXT dispatch, which under a tight
    device_mem_mb waits in _reserve for exactly those pins — the
    manager starved for 30 s and failed the task with device-oom (how
    test_lru_eviction_under_pressure failed under the loaded six-worker
    run).  The completer now re-probes while it idles."""
    import time as _time

    from parsec_tpu.devices.xla import XlaDevice
    real = XlaDevice._outputs_ready
    seen = {}

    def slow_ready(inf):
        t0 = seen.setdefault(id(inf), _time.monotonic())
        return _time.monotonic() - t0 > 0.3 and real(inf)
    monkeypatch.setattr(XlaDevice, "_outputs_ready",
                        staticmethod(slow_ready))
    t0 = _time.monotonic()
    test_lru_eviction_under_pressure()
    assert _time.monotonic() - t0 < 20.0


def test_best_device_load_balance():
    """Without affinity hints, tasks spread across devices by load."""
    with make_ctx() as ctx:
        accs = ctx.device_registry.accelerators
        if len(accs) < 2:
            pytest.skip("needs >=2 jax devices")
        nt = 24
        A = TwoDimBlockCyclic(mb=8, nb=8, lm=nt * 8, ln=8)
        for m, n in A.local_tiles():
            A.data_of(m, n).copy_on(0).payload[:] = 1.0
        p = PTG("spread", NT=nt)
        p.task("S", m=Range(0, nt - 1)) \
            .affinity(lambda m, A=A: A(m, 0)) \
            .flow("T", "RW", IN(DATA(lambda m, A=A: A(m, 0))),
                  OUT(DATA(lambda m, A=A: A(m, 0)))) \
            .body(lambda T: T * 2.0, device="tpu")
        ctx.add_taskpool(p.build())
        ctx.wait()
        used = sum(1 for d in accs if d.stats.executed_tasks > 0)
        assert used >= 2


def test_device_fault_degrades_to_cpu():
    """Degraded mode (reference: device_cuda_module.c:2757-2762 — GPU
    errors disable the device and tasks fall back to the CPU
    incarnation, the reference's only fault tolerance)."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range
    from parsec_tpu.utils.mca import params

    NT = 6
    V = VectorTwoDimCyclic(mb=4, lm=4 * NT)
    for m, _ in V.local_tiles():
        V.data_of(m).copy_on(0).payload[:] = 1.0

    def bad_kernel(X):
        raise RuntimeError("injected device fault")

    params.set("device_max_faults", 2)
    try:
        with Context(nb_cores=2) as ctx:
            if not ctx.device_registry.accelerators:
                pytest.skip("no accelerator attached")
            p = PTG("faulty", NT=NT)
            tb = p.task("T", k=Range(0, NT - 1)) \
                .affinity(lambda k, V=V: V(k)) \
                .flow("X", "RW",
                      IN(DATA(lambda k, V=V: V(k))),
                      OUT(DATA(lambda k, V=V: V(k))))
            tb.body(bad_kernel, device="tpu")
            tb.body(lambda X: X + 1.0)          # the CPU fallback
            ctx.add_taskpool(p.build())
            ctx.wait(timeout=120)
            dev = ctx.device_registry.devices[1]
            assert not dev.enabled
            assert dev.stats.faults >= 2
    finally:
        params.unset("device_max_faults")
    for m in range(NT):
        np.testing.assert_allclose(
            np.asarray(V.data_of(m).pull_to_host().payload), 2.0)


def test_wavefront_fusion_batches_same_class_waves():
    """Wavefront launch fusion: when the device queue holds a wave of
    same-class ready tasks, the manager dispatches them as ONE jitted
    program (reference analog: the GPU manager draining its pending FIFO
    into exec streams, device_cuda_module.c:2697 — here the drain fuses
    the wave, amortizing per-launch latency)."""
    import time as _time

    from parsec_tpu.core.context import Context

    MT = 16
    mb = 8
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=MT * mb)
    rng = np.random.default_rng(3)
    ref = {}
    for _m, n in A.local_tiles():
        t = rng.standard_normal((mb, mb)).astype(np.float32)
        A.data_of(0, n).copy_on(0).payload[:] = t
        ref[n] = t * 2.0

    def mul_kernel(T):
        # trace-time stall (runs ONCE per compile, not per task): the
        # first launch traces while the rest of the wave queues behind
        # it, making the fusion window deterministic for the test
        _time.sleep(0.05)
        return T * 2.0

    params.set("device_fuse", 8)
    params.set("device_max", 1)   # one device => the whole wave queues there
    try:
        with Context(nb_cores=2) as ctx:
            if not ctx.device_registry.accelerators:
                pytest.skip("no accelerator attached")
            p = PTG("wave", MT=MT)
            tb = p.task("MUL", n=Range(0, MT - 1)) \
                .affinity(lambda n, A=A: A(0, n)) \
                .flow("T", "RW",
                      IN(DATA(lambda n, A=A: A(0, n))),
                      OUT(DATA(lambda n, A=A: A(0, n))))
            tb.body(mul_kernel, device="tpu")
            tb.body(lambda T: np.asarray(T) * 2.0)
            ctx.add_taskpool(p.build())
            ctx.wait(timeout=120)
            dev = ctx.device_registry.devices[1]
            assert dev.stats.executed_tasks == MT
            # the wave behind the first (tracing) launch must have fused
            assert dev.stats.fused_launches >= 1
            assert dev.stats.fused_tasks >= 2
    finally:
        params.unset("device_fuse")
        params.unset("device_max")
    for n in range(MT):
        np.testing.assert_allclose(
            np.asarray(A.data_of(0, n).pull_to_host().payload), ref[n],
            rtol=1e-6)


def test_chain_links_go_alone_on_the_per_kernel_panel_path():
    """The same 16-wide wave as above, but of a class that names a
    fuse_chain (POTRF, GEQRT, TSQRT do) whose chain flow has no task
    successor, so no link is ever held.  Every link is dispatched
    alone (PR 31: which links of OTHER panels' chains meet in the queue
    is timing, and a wave of them costs the heaviest kernel's compile
    once more per width — minutes for a two-wide TSQRT wave at mb=6144
    on a v5e)."""
    import time as _time

    from parsec_tpu.core.context import Context

    MT, mb = 16, 8
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=MT * mb)
    for _m, n in A.local_tiles():
        A.data_of(0, n).copy_on(0).payload[:] = float(n)

    def mul_kernel(T):
        _time.sleep(0.05)    # trace-time stall: the wave queues behind it
        return T * 2.0

    params.set("device_fuse", 8)
    params.set("device_max", 1)
    try:
        with Context(nb_cores=2) as ctx:
            p = PTG("links", MT=MT)
            tb = p.task("MUL", n=Range(0, MT - 1)) \
                .affinity(lambda n, A=A: A(0, n)) \
                .flow("T", "RW",
                      IN(DATA(lambda n, A=A: A(0, n))),
                      OUT(DATA(lambda n, A=A: A(0, n)))) \
                .property("fuse_chain", ("T", "MUL"))
            tb.body(mul_kernel, device="tpu")
            ctx.add_taskpool(p.build())
            ctx.wait(timeout=120)
            st = ctx.device_registry.devices[1].stats
            assert st.executed_tasks == MT
            assert st.fused_launches == 0 and st.fused_tasks == 0
    finally:
        params.unset("device_fuse")
        params.unset("device_max")
    for n in range(MT):
        np.testing.assert_allclose(
            np.asarray(A.data_of(0, n).pull_to_host().payload), 2.0 * n)


def test_cross_panel_chain_fusion_potrf():
    """r6 tentpole: cross-panel fused dispatch — POTRF(k) is HELD at
    the device (its deps release eagerly with Deferred payloads) and
    its kernel is traced INTO the TRSM wave's launch, so the panel
    chain rides one dispatch.  The result must match numpy and the
    chained counters must show the fusion actually ran."""
    from parsec_tpu.apps.potrf import potrf_taskpool

    mb, nt = 16, 5
    n = nt * mb
    rng = np.random.default_rng(21)
    B = rng.standard_normal((n, n)).astype(np.float32)
    spd = (B @ B.T + n * np.eye(n)).astype(np.float32)
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n).from_array(spd.copy())
    with Context(nb_cores=4) as ctx:
        if not ctx.device_registry.accelerators:
            pytest.skip("no accelerator attached")
        ctx.add_taskpool(potrf_taskpool(A, device="tpu"))
        ctx.wait(timeout=120)
        st = ctx.device_registry.accelerators[0].stats
        launches, tasks = st.chained_launches, st.chained_tasks
    L = np.tril(A.to_array())
    err = np.abs(L @ L.T - spd).max() / np.abs(spd).max()
    assert err < 1e-4, err
    assert launches > 0 and tasks > launches   # chains really fused


def test_cross_panel_chain_fusion_qr_column():
    """The GEQRT -> TSQRT column chain: successive holds stack their
    placeholders on the SAME RW copy; the TSMQR/UNMQR waves force the
    chain and the factorization stays exact (regression for the
    resolution identity check).  ONE device: on the eight of the
    virtual mesh a head is held only where its whole chain stays on its
    chip and the column's tiles land where the load was least, so how
    many heads met their successor's launch on device 0 was a matter
    of timing — one in a cold process, none in a warm or a loaded one
    (PR 36: the driver's -n 6 run read 0)."""
    from parsec_tpu.apps.qr import qr_taskpool
    mb, nt = 8, 5
    n = nt * mb
    rng = np.random.default_rng(22)
    a = rng.standard_normal((n, n)).astype(np.float32)
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n).from_array(a.copy())
    params.set("device_max", 1)
    try:
        with Context(nb_cores=4) as ctx:
            if not ctx.device_registry.accelerators:
                pytest.skip("no accelerator attached")
            ctx.add_taskpool(qr_taskpool(A, device="tpu"))
            ctx.wait(timeout=120)
            (dev,) = ctx.device_registry.accelerators
            st = dev.stats
            # a held head goes out in its successor's chain program
            # or, forced, alone
            assert 0 < st.chained_launches <= st.held_tasks
    finally:
        params.unset("device_max")
    out = A.to_array()
    R = np.triu(out)
    ata = a.T @ a
    assert np.abs(np.tril(out, -1)).max() < 1e-4
    assert np.abs(R.T @ R - ata).max() / np.abs(ata).max() < 1e-4


def test_chain_hold_resolves_at_sync_without_consumer():
    """A held chain whose consumers run on the CPU incarnation (or
    never arrive) must still dispatch: stage_in_host forces the
    Deferred, and device sync resolves any straggler holds."""
    from parsec_tpu.apps.potrf import potrf_taskpool
    mb, nt = 8, 3
    n = nt * mb
    rng = np.random.default_rng(23)
    B = rng.standard_normal((n, n)).astype(np.float32)
    spd = (B @ B.T + n * np.eye(n)).astype(np.float32)
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n).from_array(spd.copy())
    p = potrf_taskpool(A, device="tpu")
    # force every TRSM to the cpu incarnation: the held POTRF's W
    # output reaches a CPU body as a Deferred payload
    trsm = p.task_classes["TRSM"]
    for idx, (dev_type, _hook) in enumerate(trsm.incarnations):
        if dev_type != "cpu":
            trsm.chore_disabled_mask |= 1 << idx
    with Context(nb_cores=2) as ctx:
        if not ctx.device_registry.accelerators:
            pytest.skip("no accelerator attached")
        ctx.add_taskpool(p)
        ctx.wait(timeout=120)
    L = np.tril(A.to_array())
    err = np.abs(L @ L.T - spd).max() / np.abs(spd).max()
    assert err < 1e-4, err


# ---------------------------------------------------------------------
# the completer's pass (devices/xla.py _completer_loop): everything the
# managers have handed over is taken under one hold of the device's
# lock, released in dispatch order, retired and drained once
# ---------------------------------------------------------------------
PASS_MT = 12


def _until(cond, what, seconds=60.0):
    import time as _time
    deadline = _time.monotonic() + seconds
    while not cond():
        assert _time.monotonic() < deadline, f"never saw: {what}"
        _time.sleep(0.001)


def _fan_pool(A, mt, successors=False, src=True):
    """SRC on tile (0, mt) lets go of MUL(n): T * 2 on tile (0, n), n <
    mt, over a CTL edge each (``src`` False: no SRC, the MULs are ready
    at once); with ``successors`` each MUL feeds ADD(n): T + 1."""
    p = PTG("passes", MT=mt)
    if src:
        p.task("SRC") \
            .affinity(lambda A=A, MT=mt: A(0, MT)) \
            .flow("T", "RW", IN(DATA(lambda A=A, MT=mt: A(0, MT))),
                  OUT(DATA(lambda A=A, MT=mt: A(0, MT)))) \
            .flow("go", "CTL", OUT(TASK(
                "MUL", "go", lambda MT=mt: [dict(n=n) for n in range(MT)]))) \
            .body(lambda T: T + 1.0, device="tpu")
    out = [OUT(TASK("ADD", "T", lambda n: dict(n=n)))] if successors \
        else [OUT(DATA(lambda n, A=A: A(0, n)))]
    mul = p.task("MUL", n=Range(0, mt - 1)) \
        .affinity(lambda n, A=A: A(0, n)) \
        .flow("T", "RW", IN(DATA(lambda n, A=A: A(0, n))), *out)
    if src:
        mul = mul.flow("go", "CTL", IN(TASK("SRC", "go", lambda n: dict())))
    mul.body(lambda T: T * 2.0, device="tpu")
    if successors:
        p.task("ADD", n=Range(0, mt - 1)) \
            .affinity(lambda n, A=A: A(0, n)) \
            .flow("T", "RW", IN(TASK("MUL", "T", lambda n: dict(n=n))),
                  OUT(DATA(lambda n, A=A: A(0, n)))) \
            .body(lambda T: T + 1.0, device="tpu")
    return p.build()


class _Releases:
    """``scheduling.complete_execution`` as the completer calls it, under
    the test's hand.  ``calls`` lists the tasks in the order of their
    release.  The call numbered k of ``holds`` waits for ``open(k)``,
    ``"after"`` the real release (its successors are on their way while
    the completer is still inside its pass) or ``"before"`` it.  The
    call numbered ``fail_at`` raises in the real one's place.  Every
    release takes ``slow_s`` longer."""

    def __init__(self, monkeypatch, holds=(), fail_at=None, slow_s=0.0):
        import threading
        from parsec_tpu.core import scheduling
        self.calls = []
        self.holds, self.fail_at, self.slow_s = dict(holds), fail_at, slow_s
        self.held = {k: threading.Event() for k in self.holds}
        self._gates = {k: threading.Event() for k in self.holds}
        self._real = scheduling.complete_execution
        monkeypatch.setattr(scheduling, "complete_execution", self)

    def _hold(self, number, when):
        if self.holds.get(number) == when:
            self.held[number].set()
            assert self._gates[number].wait(60.0), "the gate never opened"

    def __call__(self, es, task, *a, **kw):
        import threading
        import time as _time
        if not threading.current_thread().name.startswith("xla-fin"):
            return self._real(es, task, *a, **kw)
        number = len(self.calls)
        self.calls.append(task)
        self._hold(number, "before")
        if self.slow_s:
            _time.sleep(self.slow_s)
        if number == self.fail_at:
            raise RuntimeError(f"injected release fault in {task!r}")
        try:
            return self._real(es, task, *a, **kw)
        finally:
            self._hold(number, "after")

    def open(self, number):
        self._gates[number].set()


def _dispatch_log(monkeypatch):
    """The tasks in the order their entries were made for ``_inflight``
    (under the device's lock, in ``_launch``): the dispatch order."""
    from parsec_tpu.devices import xla
    made = []

    class Logged(xla._Inflight):
        __slots__ = ()

        def __init__(self, es, task, *a, **kw):
            made.append(task)
            super().__init__(es, task, *a, **kw)
    monkeypatch.setattr(xla, "_Inflight", Logged)
    return made


def _span_log(ctx, monkeypatch):
    """(name, arguments known at the begin and at the end) of every span
    as it closes, the profiler's gate forced open (no session needed)."""
    spans = []
    monkeypatch.setattr(ctx, "_span_live", lambda: True)
    ctx.pins_register("span_end", lambda es, event, span: spans.append(
        (span.name, {**span.args, **(span.late or {})})))
    return spans


@pytest.fixture
def pass_mca():
    mca = {"device_max": 1, "device_fuse": 8, "device_inflight_depth": 32}
    for k, v in mca.items():
        params.set(k, v)
    yield mca
    for k in mca:
        params.unset(k)


def _tiles(mt, mb=8):
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=mt * mb)
    for _m, n in A.local_tiles():
        A.data_of(0, n).copy_on(0).payload[:] = float(n)
    return A


def _hand_over_behind_src(ctx, dev, rel, A, successors=False):
    """Start the fan with the completer held at the end of SRC's release:
    on return its first pass (SRC alone) is still open and every MUL
    sits in ``_inflight``, handed over by the managers.  The MULs go the
    workers' way: handed in by the completer itself (the one-chip direct
    path, tests/test_direct_submit.py), they would reach the managers
    only when the held pass ends."""
    ctx.direct_device = None
    ctx.add_taskpool(_fan_pool(A, PASS_MT, successors))
    ctx.start()
    _until(rel.held[0].is_set, "the completer at the end of SRC's release")
    _until(lambda: len(dev._inflight) == PASS_MT, "every MUL handed over")
    assert dev._completing == 1 and rel.calls[0].task_class.name == "SRC"


def test_a_pass_takes_what_was_handed_over_and_releases_it_in_dispatch_order(
        monkeypatch, pass_mca):
    """The completer is held inside its first pass while the managers
    hand over a whole fan: its next pass takes all of it under one hold,
    releases it in the order it was dispatched, and finalizes it in one
    drain."""
    made = _dispatch_log(monkeypatch)
    rel = _Releases(monkeypatch, holds={0: "after"})
    A = _tiles(PASS_MT + 1)
    with make_ctx() as ctx:
        spans = _span_log(ctx, monkeypatch)
        (dev,) = ctx.device_registry.accelerators
        _hand_over_behind_src(ctx, dev, rel, A)
        rel.open(0)
        ctx.wait(timeout=120)
        assert dev.stats.faults == 0
        assert dev.stats.executed_tasks == PASS_MT + 1
        assert dev.stats.release_passes == 2
    assert rel.calls == made and len(made) == PASS_MT + 1
    assert [a["n"] for name, a in spans if name == "fin.pass"] == [1, PASS_MT]
    assert sum(1 for name, _a in spans if name == "fin.release") == \
        PASS_MT + 1
    drains = [a["n"] for name, a in spans if name == "fin.drain"]
    assert max(drains) >= PASS_MT and sum(drains) <= PASS_MT + 1
    for n in range(PASS_MT):
        np.testing.assert_allclose(
            np.asarray(A.data_of(0, n).pull_to_host().payload), 2.0 * n)


def test_the_valve_holds_at_runahead_plus_one_whatever_a_pass_finds(
        monkeypatch):
    """Outputs that never read ready and a slow completer: the managers
    keep ``_inflight`` full, yet no pass takes more than the valve
    leaves room for — released and unfinalized entries together never
    pass ``device_runahead`` + 1, and the oldest is waited for there."""
    from parsec_tpu.devices.xla import XlaDevice
    MT, AHEAD = 40, 4
    monkeypatch.setattr(XlaDevice, "_outputs_ready",
                        staticmethod(lambda inf: False))
    drain, seen = XlaDevice._drain_retired, []

    def watched(self, max_unfinalized):
        seen.append(len(self._retire) + self._completing)
        return drain(self, max_unfinalized)
    monkeypatch.setattr(XlaDevice, "_drain_retired", watched)
    _Releases(monkeypatch, slow_s=0.003)
    mca = {"device_max": 1, "device_inflight_depth": AHEAD,
           "device_runahead": AHEAD}
    for k, v in mca.items():
        params.set(k, v)
    try:
        A = _tiles(MT)
        with make_ctx() as ctx:
            spans = _span_log(ctx, monkeypatch)
            (dev,) = ctx.device_registry.accelerators
            assert dev._runahead == AHEAD
            ctx.add_taskpool(_fan_pool(A, MT, src=False))
            ctx.wait(timeout=120)
            assert dev.stats.faults == 0
            assert dev.stats.release_passes < MT       # passes of several
    finally:
        for k in mca:
            params.unset(k)
    assert max(seen) == AHEAD + 1
    blocking = [a for name, a in spans
                if name == "fin.drain" and a["block"] == 1]
    assert blocking and all(a["n"] == 1 for a in blocking)
    for n in range(MT):
        np.testing.assert_allclose(
            np.asarray(A.data_of(0, n).pull_to_host().payload), 2.0 * n)


def test_a_release_that_raises_is_its_tasks_error_and_the_pass_goes_on(
        monkeypatch, pass_mca):
    """``complete_execution`` raises for the second task of a pass: the
    error is recorded against that task, the rest of the pass is
    released and its successors run, and the pool ends with the error
    instead of hanging."""
    rel = _Releases(monkeypatch, holds={0: "after"}, fail_at=2)
    A = _tiles(PASS_MT + 1)
    with make_ctx() as ctx:
        (dev,) = ctx.device_registry.accelerators
        _hand_over_behind_src(ctx, dev, rel, A, successors=True)
        rel.open(0)
        adds = lambda: [t for t in rel.calls  # noqa: E731
                        if t.task_class.name == "ADD"]
        _until(lambda: len(adds()) == PASS_MT - 1,
               "the successor of every MUL but the failed one")
        failed = rel.calls[2]
        with pytest.raises(RuntimeError) as caught:
            ctx.wait(timeout=60)
        assert str(caught.value) == f"task {failed!r} failed"
        assert "injected release fault" in str(caught.value.__cause__)
        assert [t for _exc, t in ctx._errors] == [failed]
        assert dev.stats.faults == 1 and dev.stats.release_passes >= 2
        # the rest of its pass came after it, and went through
        assert [t.task_class.name for t in rel.calls[:PASS_MT + 1]] == \
            ["SRC"] + ["MUL"] * PASS_MT
        assert sorted(t.locals["n"] for t in adds()) == sorted(
            t.locals["n"] for t in rel.calls[1:PASS_MT + 1] if t is not failed)
        ctx._errors.clear()


def test_sync_entered_in_the_middle_of_a_pass_waits_for_all_of_it(
        monkeypatch, pass_mca):
    """``sync()`` called while a pass is half released does not return
    until the whole pass is released and finalized: what the pass took
    stays counted in ``_completing`` until it is in ``_retire``."""
    import threading
    rel = _Releases(monkeypatch, holds={0: "after", 2: "before"})
    A = _tiles(PASS_MT + 1)
    with make_ctx() as ctx:
        (dev,) = ctx.device_registry.accelerators
        _hand_over_behind_src(ctx, dev, rel, A)
        rel.open(0)
        _until(rel.held[2].is_set, "the second pass at its second release")
        assert dev._completing == PASS_MT and not dev._inflight
        assert len(dev._retire) + dev._finalizing <= 1
        done = threading.Event()
        syncer = threading.Thread(
            target=lambda: (dev.sync(timeout=60), done.set()), daemon=True)
        syncer.start()
        assert not done.wait(0.3), "sync() returned with a pass half released"
        assert len(rel.calls) == 3
        rel.open(2)
        assert done.wait(60), "sync() never returned"
        assert len(rel.calls) == PASS_MT + 1
        assert dev._completing == dev._finalizing == 0
        assert not dev._retire and not dev._pins and dev.load == 0.0
        ctx.wait(timeout=120)
        assert dev.stats.faults == 0 and dev.stats.release_passes == 2


def test_hand_over_under_a_short_switch_interval_loses_nothing():
    """Workers, two managers and the completer trading places every
    10 us over a shallow hand-over queue and a tight valve: every task
    is released once, every pin and every unit of load comes back."""
    import sys
    MT = 150
    mca = {"device_max": 1, "device_inflight_depth": 4, "device_runahead": 6}
    for k, v in mca.items():
        params.set(k, v)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        A = _tiles(MT)
        with Context(nb_cores=8) as ctx:
            (dev,) = ctx.device_registry.accelerators
            ctx.add_taskpool(_fan_pool(A, MT, successors=True, src=False))
            ctx.wait(timeout=120)
            st = dev.stats
            assert st.faults == 0 and st.executed_tasks == 2 * MT
            assert 0 < st.release_passes <= 2 * MT
            assert dev._completing == dev._finalizing == 0
            assert not dev._inflight and not dev._retire
            assert not dev._pins and dev.load == 0.0
    finally:
        sys.setswitchinterval(interval)
        for k in mca:
            params.unset(k)
    for n in range(MT):
        np.testing.assert_allclose(
            np.asarray(A.data_of(0, n).pull_to_host().payload), 2.0 * n + 1)


def test_the_idle_completer_keeps_no_entry_of_its_last_pass_alive(
        monkeypatch, pass_mca):
    """An entry names its task's output arrays.  Once a pool is through
    and the device synced, nothing may hold the entries of the last pass
    — not the completer's own frame while it waits for the next job: a
    job's last outputs would stay on the device beside the next job's
    (1.8 GB of a 16 GB chip in the GEMM cell of the benchmark)."""
    import gc
    import weakref
    from parsec_tpu.devices import xla
    made = []

    class Weak(xla._Inflight):
        __slots__ = ("__weakref__",)

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(weakref.ref(self))
    monkeypatch.setattr(xla, "_Inflight", Weak)
    rel = _Releases(monkeypatch, holds={0: "after"})
    A = _tiles(PASS_MT + 1)
    with make_ctx() as ctx:
        (dev,) = ctx.device_registry.accelerators
        _hand_over_behind_src(ctx, dev, rel, A)
        rel.open(0)
        ctx.wait(timeout=120)
        assert dev.stats.release_passes == 2 and len(made) == PASS_MT + 1

        def all_gone():
            gc.collect()
            return not [r for r in made if r() is not None]
        _until(all_gone, "every entry of the last pass collected", 10.0)
