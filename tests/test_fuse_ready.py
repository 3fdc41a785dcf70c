"""Fused-width readiness (devices/xla.py ``XlaKernel.fuse_ready`` and
``_FuseWarmer``): the state of a width belongs to the program on its
device and is shared by every taskpool over the same kernel function;
whoever meets a warming width waits for the warmer's signal, not for a
clock.  Devices here are ``XlaDevice`` objects on the virtual CPU mesh,
driven through ``_dispatch_plain`` the way a manager drives them; the
warmer's compile is held behind a gate the test opens, so what is
asserted is an order of events and not a duration."""

import threading
import time

import jax
import numpy as np
import pytest

from parsec_tpu.devices.xla import XlaDevice, XlaKernel, wait_fuse_warm
from parsec_tpu.utils.mca import params

JOIN_S = 60.0


def _kernel_fn():
    """A kernel function of this test's own: its widths start cold."""
    def double(T):
        return T * 2.0
    return double


def _pool_kernel(fn):
    """What every taskpool build makes anew over the app's memoized
    function: the pool's own XlaKernel."""
    return XlaKernel(fn, ["T"], ["T"], ["T"], cls="MUL")


def _flat(dev, n=2):
    return [jax.device_put(np.full((4, 4), float(i), np.float32), dev.jdev)
            for i in range(n)]


@pytest.fixture
def devs():
    made = []

    def make(i=0):
        made.append(XlaDevice(jax.devices()[i]))
        return made[-1]
    yield make
    wait_fuse_warm()
    for d in made:
        d.fini()


@pytest.fixture
def wait_ms():
    def set_ms(ms):
        params.set("device_fuse_warm_wait_ms", ms)
    yield set_ms
    params.unset("device_fuse_warm_wait_ms")


@pytest.fixture
def gate(monkeypatch):
    """Holds the warmer's thread before its compile until ``open`` is
    set; ``started`` says the warmer has taken a width."""
    class Gate:
        started = threading.Event()
        open = threading.Event()
        taken = 0
    g = Gate()
    real = XlaKernel.jitted_fused

    def gated(self, donate, n):
        if threading.current_thread().name == "xla-fuse-warm":
            g.taken += 1
            g.started.set()
            assert g.open.wait(JOIN_S), "the test never opened the gate"
        return real(self, donate, n)
    monkeypatch.setattr(XlaKernel, "jitted_fused", gated)
    yield g
    g.open.set()


def _until(cond, what):
    deadline = time.monotonic() + JOIN_S
    while not cond():
        assert time.monotonic() < deadline, f"never saw: {what}"
        time.sleep(0.001)


def _dispatch(dev, kernel, flat, out, tag):
    fused, outs = dev._dispatch_plain(kernel, len(flat), list(flat))
    out.append((tag, fused, [np.asarray(o["T"]) for o in outs]))


def _span_log(ctx, monkeypatch):
    """Every span's name as it opens, with the profiler's gate forced
    open (no session needed)."""
    names = []
    monkeypatch.setattr(ctx, "_span_live", lambda: True)
    ctx.pins_register("span_begin",
                      lambda es, event, span: names.append(span.name))
    return names


def test_second_taskpool_finds_the_width_ready(monkeypatch, wait_ms):
    """Two taskpools over one kernel function, same shapes, same device:
    the first warms the x8 width, the second submits nothing to the
    warmer and waits for nothing."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range
    MT, mb = 8, 8
    fn = _kernel_fn()
    # one manager and a window long enough for the eight siblings to
    # meet: each pool is ONE x8 wave however the threads interleave
    mca = {"device_max": 1, "device_fuse": 8, "device_dispatchers": 1,
           "device_fuse_window_ms": 2000.0}
    wait_ms(60000.0)
    for k, v in mca.items():
        params.set(k, v)
    try:
        with Context(nb_cores=2) as ctx:
            spans = _span_log(ctx, monkeypatch)
            (dev,) = ctx.device_registry.accelerators

            def job():
                A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=MT * mb)
                for _m, n in A.local_tiles():
                    A.data_of(0, n).copy_on(0).payload[:] = float(n)
                p = PTG("wave", MT=MT)
                tb = p.task("MUL", n=Range(0, MT - 1)) \
                    .affinity(lambda n, A=A: A(0, n)) \
                    .flow("T", "RW", IN(DATA(lambda n, A=A: A(0, n))),
                          OUT(DATA(lambda n, A=A: A(0, n))))
                tb.body(fn, device="tpu")
                ctx.add_taskpool(p.build())
                ctx.wait(timeout=120)
                for n in range(MT):
                    np.testing.assert_allclose(np.asarray(
                        A.data_of(0, n).pull_to_host().payload), 2.0 * n)
                return dev.stats.as_dict(), list(spans)

            first, spans1 = job()
            second, spans2 = job()
    finally:
        for k in mca:
            params.unset(k)
    assert first["fused_launches"] == 1 and first["fused_tasks"] == MT
    assert first["warm_waits"] == 1 and first["compiles"] == 2
    assert spans1.count("warm.compile") == 1
    assert spans1.count("mgr.warm_wait") == 1
    # the second pool: the same one wave, fused, and nothing else
    assert second["fused_launches"] == 2 and second["fused_tasks"] == 2 * MT
    assert second["compiles"] == first["compiles"]
    assert second["warm_waits"] == first["warm_waits"]
    assert second["defused_waves"] == first["defused_waves"] == 0
    later = spans2[len(spans1):]
    assert "mgr.dispatch" in later
    assert "warm.compile" not in later and "mgr.warm_wait" not in later


def test_waiter_wakes_on_the_warmers_post(monkeypatch, devs, gate, wait_ms):
    """The wave that asked for a cold width is answered when the warmer
    posts the state: after the gate opened, long before its bound, and
    without a sleep on its thread."""
    wait_ms(600000.0)     # a bound no test outlives: only a post wakes
    dev, k = devs(), _pool_kernel(_kernel_fn())
    events, sleepers = [], []
    real_sleep = time.sleep

    def sleep(s):
        sleepers.append(threading.current_thread().name)
        real_sleep(s)
    monkeypatch.setattr(time, "sleep", sleep)

    def ask():
        events.append("asked")
        events.append(("answered", k.fuse_ready(False, 2, _flat(dev), dev)))
    asker = threading.Thread(target=ask, name="asker")
    asker.start()
    _until(lambda: gate.started.is_set() and dev.stats.warm_waits == 1,
           "the asker waiting on a compile in progress")
    assert asker.is_alive() and events == ["asked"]
    events.append("gate opened")
    gate.open.set()
    asker.join(JOIN_S)
    assert not asker.is_alive()
    assert events == ["asked", "gate opened", ("answered", True)]
    assert "asker" not in sleepers
    assert dev.stats.compiles == 1 and gate.taken == 1


@pytest.mark.parametrize("lands", [True, False])
def test_two_managers_meet_one_cold_width(devs, gate, wait_ms, lands):
    """Two threads of one device meet the same cold width: one compile
    is asked for and both wait on it.  Landing inside the bound, both
    waves go out fused; past it, both run as singles — and the width is
    ready once the compile does land."""
    wait_ms(600000.0 if lands else 500.0)
    dev, fn = devs(), _kernel_fn()
    k = _pool_kernel(fn)
    out = []
    threads = [threading.Thread(target=_dispatch, name=f"mgr-{i}",
                                args=(dev, k, _flat(dev), out, i))
               for i in range(2)]
    for t in threads:
        t.start()
    _until(lambda: gate.started.is_set() and dev.stats.warm_waits == 2,
           "both managers waiting on the one compile")
    if lands:
        assert all(t.is_alive() for t in threads) and not out
        gate.open.set()
    for t in threads:
        t.join(JOIN_S)
        assert not t.is_alive()
    assert gate.taken == 1, "the second manager asked for a compile too"
    assert sorted(fused for _t, fused, _o in out) == [lands, lands]
    assert dev.stats.defused_waves == (0 if lands else 2)
    assert dev.stats.launches == (2 if lands else 4)
    for _t, _f, outs in out:
        np.testing.assert_allclose(outs[0], 0.0)
        np.testing.assert_allclose(outs[1], 2.0)
    if not lands:
        # still warming past its deadline: the next wave does not wait
        assert k.fuse_ready(False, 2, _flat(dev), dev) is False
        assert dev.stats.warm_waits == 2
        gate.open.set()
        assert wait_fuse_warm(JOIN_S)
        _dispatch(dev, _pool_kernel(fn), _flat(dev), out, "later")
        assert out[-1][1] is True and dev.stats.defused_waves == 2
    assert dev.stats.warm_waits == 2


def test_failed_width_is_failed_for_the_next_pool(monkeypatch, devs, capfd):
    """A width whose compile failed is failed for every later taskpool
    on that device too — no second compile, no wait — warns once a
    device, and is asked for again after the back-off."""
    class Refused:
        def lower(self, *a):
            raise ValueError("Mosaic failed to compile: Bad lhs type")

    fn = _kernel_fn()
    real = XlaKernel.jitted_fused
    monkeypatch.setattr(
        XlaKernel, "jitted_fused",
        lambda self, donate, n: Refused()
        if threading.current_thread().name == "xla-fuse-warm"
        else real(self, donate, n))
    dev0, dev1 = devs(0), devs(1)
    reason = "ValueError: Mosaic failed to compile: Bad lhs type"
    for pool in range(3):
        assert _pool_kernel(fn).fuse_ready(False, 2, _flat(dev0), dev0) \
            is False
        assert dev0.stats.compiles == 1 and dev0.stats.warm_waits == 1
    name = _pool_kernel(fn).name
    assert dev0.fuse_failures == {(name, 2): reason}
    assert dev1.fuse_failures == {}
    # another device is another program: it asks, fails and warns itself
    for pool in range(2):
        assert _pool_kernel(fn).fuse_ready(False, 2, _flat(dev1), dev1) \
            is False
    assert dev1.stats.compiles == 1 and dev1.fuse_failures == {
        (name, 2): reason}
    assert capfd.readouterr().err.count("fused width 2 of kernel") == 2
    # past the back-off the width is asked for again — and now compiles
    monkeypatch.setattr(XlaKernel, "jitted_fused", real)
    cache = fn.__parsec_jit_cache__
    for key, st in list(cache.items()):
        if key[0] == "w":
            assert st[0] == "failed" and st[2] == reason
            cache[key] = ("failed", st[1] - 61.0, reason)
    assert _pool_kernel(fn).fuse_ready(False, 2, _flat(dev0), dev0) is True
    assert dev0.stats.compiles == 2 and dev0.stats.warm_waits == 2
    assert capfd.readouterr().err.count("fused width") == 0


def test_width_warmed_for_one_device_is_cold_on_the_next(devs):
    """The warm compile is for one device assignment: device 1 asks for
    its own, while a new taskpool on device 0 finds the width ready."""
    fn = _kernel_fn()
    dev0, dev1 = devs(0), devs(1)
    assert _pool_kernel(fn).fuse_ready(False, 2, _flat(dev0), dev0) is True
    assert (dev0.stats.compiles, dev0.stats.warm_waits) == (1, 1)
    assert dev1.stats.compiles == 0
    assert _pool_kernel(fn).fuse_ready(False, 2, _flat(dev1), dev1) is True
    assert (dev1.stats.compiles, dev1.stats.warm_waits) == (1, 1)
    for dev in (dev0, dev1):
        assert _pool_kernel(fn).fuse_ready(False, 2, _flat(dev), dev) is True
        assert (dev.stats.compiles, dev.stats.warm_waits) == (1, 1)
    # other shapes, another width: other programs
    wide = [jax.device_put(np.zeros((8, 4), np.float32), dev0.jdev)] * 2
    assert _pool_kernel(fn).fuse_ready(False, 2, wide, dev0) is True
    assert _pool_kernel(fn).fuse_ready(False, 4, _flat(dev0, 4), dev0) is True
    assert dev0.stats.compiles == 3


def test_callable_that_cannot_carry_the_cache_keeps_its_own(devs):
    """A kernel callable that takes no attribute: each XlaKernel keeps
    state of its own, as before."""
    class Slotted:
        __slots__ = ()
        __name__ = "slotted"

        def __call__(self, T):
            return T * 2.0

    fn, dev = Slotted(), devs()
    for pool in range(2):
        assert _pool_kernel(fn).fuse_ready(False, 2, _flat(dev), dev) is True
        assert dev.stats.compiles == pool + 1
    k = _pool_kernel(fn)
    out = []
    _dispatch(dev, k, _flat(dev), out, 0)
    _dispatch(dev, k, _flat(dev), out, 1)
    assert [fused for _t, fused, _o in out] == [True, True]
    assert dev.stats.warm_waits == 3 and dev.stats.defused_waves == 0
