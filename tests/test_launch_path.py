"""The managers' launch path (devices/xla.py, PR 36): what a launch has
to know about an operand is established once and carried.

(a) every copy state through ``Data.acquire_on`` and the first branch of
``XlaDevice._stage_in``, held to the three calls and the stage-in they
replaced (kept here as the oracle); (b) the fusion signature a task is
queued under, held to the parent's ``_fuse_sig``; (c) a fused width's
state under the wave's signature; (d) ``resident_flows`` /
``staged_flows`` over whole potrf and geqrf jobs; (e) the donation
hazard's table.  Devices are ``XlaDevice`` objects on the virtual CPU
mesh, standalone where no context is needed."""

import threading
import types

import jax
import numpy as np
import pytest

from parsec_tpu.data.arena import Arena
from parsec_tpu.data.data import (ACCESS_READ, ACCESS_RW, ACCESS_WRITE,
                                  Coherency, Data, DataCopy, FLAG_COW,
                                  FLAG_SCRATCH)
from parsec_tpu.devices.device import DeviceStats
from parsec_tpu.devices.xla import (Deferred, XlaDevice, XlaKernel,
                                    device_put_private, wait_fuse_warm)
from parsec_tpu.utils.mca import params

SPACE = 1
ACCESSES = {"READ": ACCESS_READ, "WRITE": ACCESS_WRITE, "RW": ACCESS_RW}


# ---------------------------------------------------------------------
# the oracle: the parent's sequence, as it stood before PR 36
# ---------------------------------------------------------------------
def _old_pin(dev, datum):
    with dev._mem_lock:
        dev._pins[id(datum)] = dev._pins.get(id(datum), 0) + 1


def _old_stage_in(dev, copy, access, pinned=False):
    """``XlaDevice._stage_in`` of the parent commit: ``is_pinned_snapshot``
    + ``copy_on`` + ``transfer_ownership`` under three holds of the
    datum's lock, the resident case its last branch."""
    import jax.numpy as jnp
    datum = copy.data
    p0 = copy.payload
    if isinstance(p0, Deferred):
        if p0.array is not None:
            copy.payload = p0.array
        elif p0.hold.device is not dev:
            copy.payload = p0.force()
        elif copy.flags & FLAG_COW or copy.is_pinned_snapshot(pinned):
            copy.payload = p0.force()
    if copy.flags & FLAG_SCRATCH and copy.version == 0 \
            and access & ACCESS_WRITE and copy.arena is not None:
        nbytes = getattr(copy.payload, "nbytes", 0)
        off = dev._reserve(nbytes)
        dc = datum.copy_on(dev.space)
        if dc is None:
            dc = datum.create_copy(dev.space)
        dc.payload = jax.device_put(
            jnp.zeros(copy.payload.shape, dtype=copy.payload.dtype),
            dev.jdev)
        dc.version = copy.version
        datum.transfer_ownership(dev.space, access)
        dev._account(datum, dc, nbytes, off)
        dev._touch(datum)
        return dc
    if (copy.flags & FLAG_COW) == 0 and copy.is_pinned_snapshot(pinned):
        payload = copy.payload
        nbytes = getattr(payload, "nbytes", 0)
        off = dev._reserve(nbytes)
        if dev._on_this_device(payload):
            staged = jnp.array(payload, copy=True)
        else:
            staged = device_put_private(payload, dev.jdev)
        snap = Data(nb_elts=datum.nb_elts)
        dc = snap.create_copy(dev.space, payload=staged,
                              coherency=Coherency.SHARED,
                              version=copy.version)
        dev.stats.bytes_in += nbytes
        dev._account(snap, dc, nbytes, off)
        return dc
    dc = datum.copy_on(dev.space)
    fresh = dc is None
    if fresh:
        dc = datum.create_copy(dev.space)
    src = datum.transfer_ownership(dev.space, access)
    if src is not None or dc.payload is None:
        payload = src.payload if src is not None else copy.payload
        nbytes = getattr(payload, "nbytes", 0)
        off = dev._reserve(nbytes) if fresh else None
        if dev._on_this_device(payload):
            dc.payload = jnp.array(payload, copy=True)
        else:
            dc.payload = device_put_private(payload, dev.jdev)
        dc.version = src.version if src is not None else copy.version
        dev.stats.bytes_in += nbytes
        if fresh:
            dev._account(datum, dc, nbytes, off)
        if not access & ACCESS_WRITE:
            dev._note_replica(datum, dc)
    if copy.flags & FLAG_COW and copy is not dc:
        datum.detach_copy(copy.device)
        copy.payload = None
        copy.coherency = Coherency.INVALID
        copy.flags &= ~FLAG_COW
    dev._touch(datum)
    return dc


def _old_acquire(datum, space, access, bound, pinned):
    """The three calls ``Data.acquire_on`` answers in one hold."""
    if bound.is_pinned_snapshot(pinned):
        return datum.copy_on(space), True, None
    dc = datum.copy_on(space)
    if dc is None:
        return None, False, None
    return dc, False, datum.transfer_ownership(space, access)


# ---------------------------------------------------------------------
# (a) copy states
# ---------------------------------------------------------------------
def _host(v):
    return np.full((4, 4), float(v), np.float32)


def _on_dev(v):
    return jax.device_put(_host(v), jax.devices()[0])


class _OtherDevice:
    """Stands for the device a foreign chain is held on: forcing the
    hold resolves the placeholder there."""

    def _force_hold(self, hold):
        hold.placeholder.array = jax.device_put(_host(7), jax.devices()[1])


def _deferred(device, resolved):
    hold = types.SimpleNamespace(device=device, state="held", succ=None)
    d = Deferred(hold, "T", (4, 4), np.dtype(np.float32))
    hold.placeholder = d
    if resolved:
        d.array = _on_dev(5)
    return d


def _state_resident(dev):
    d = Data(nb_elts=64)
    d.create_copy(0, _host(1), Coherency.INVALID, 1)
    dc = d.create_copy(SPACE, _on_dev(2), Coherency.EXCLUSIVE, 2)
    dev._account(d, dc, 64)
    return d, dc, False


def _state_resident_host_bound(dev):
    """Valid on both sides at one version; the task is bound to the
    host's copy."""
    d = Data(nb_elts=64)
    h = d.create_copy(0, _host(3), Coherency.SHARED, 3)
    dc = d.create_copy(SPACE, _on_dev(3), Coherency.SHARED, 3)
    dev._account(d, dc, 64)
    return d, h, False


def _state_invalid_under_newer_host(dev):
    d = Data(nb_elts=64)
    h = d.create_copy(0, _host(4), Coherency.OWNED, 4)
    dc = d.create_copy(SPACE, _on_dev(1), Coherency.INVALID, 1)
    dev._account(d, dc, 64)
    return d, h, False


def _state_invalid_same_version(dev):
    """The device's copy invalidated in place at the newest version."""
    d = Data(nb_elts=64)
    h = d.create_copy(0, _host(4), Coherency.EXCLUSIVE, 4)
    dc = d.create_copy(SPACE, _on_dev(1), Coherency.INVALID, 4)
    dev._account(d, dc, 64)
    return d, h, False


def _state_older_valid_device_copy(dev):
    d = Data(nb_elts=64)
    h = d.create_copy(0, _host(5), Coherency.OWNED, 5)
    dc = d.create_copy(SPACE, _on_dev(2), Coherency.SHARED, 2)
    dev._account(d, dc, 64)
    return d, h, False


def _state_detached_by_writeback(dev):
    """The bound host copy was replaced by a writeback: a snapshot."""
    d = Data(nb_elts=64)
    bound = DataCopy(d, 0, _host(1), Coherency.SHARED, 1)
    d.create_copy(0, _host(2), Coherency.OWNED, 2)
    return d, bound, False


def _state_detached_device_snapshot(dev):
    """A detached copy whose payload already lies on this device."""
    d = Data(nb_elts=64)
    bound = DataCopy(d, SPACE, _on_dev(1), Coherency.SHARED, 1)
    dc = d.create_copy(SPACE, _on_dev(2), Coherency.EXCLUSIVE, 2)
    dev._account(d, dc, 64)
    return d, bound, False


def _state_pinned_invalidated_in_place(dev):
    d = Data(nb_elts=64)
    bound = d.create_copy(0, _host(1), Coherency.INVALID, 1)
    dc = d.create_copy(SPACE, _on_dev(2), Coherency.EXCLUSIVE, 2)
    dev._account(d, dc, 64)
    return d, bound, True


def _state_invalidated_in_place_not_pinned(dev):
    d, bound, _pinned = _state_pinned_invalidated_in_place(dev)
    return d, bound, False


def _state_cow_alias(dev):
    d = Data(nb_elts=64)
    bound = d.create_copy(0, _host(6), Coherency.SHARED, 1)
    bound.flags |= FLAG_COW
    return d, bound, False


def _state_cow_alias_on_device(dev):
    """The alias's payload lies on this device already: a private
    buffer is made all the same."""
    d = Data(nb_elts=64)
    bound = d.create_copy(2, _on_dev(6), Coherency.SHARED, 1)
    bound.flags |= FLAG_COW
    return d, bound, False


_ARENA = Arena((4, 4), np.float32)


def _state_new_arena_scratch(dev):
    bound = _ARENA.get_copy(backed=False)
    bound.flags |= FLAG_SCRATCH
    return bound.data, bound, False


def _state_written_arena_buffer(dev):
    """A NEW-arena buffer a host body wrote: scratch no longer."""
    bound = _ARENA.get_copy()
    bound.payload[:] = 8.0
    bound.flags |= FLAG_SCRATCH
    bound.version = 1
    bound.data._version_clock = 1
    return bound.data, bound, False


def _state_deferred_here_unresolved(dev):
    d = Data(nb_elts=64)
    d.create_copy(0, _host(1), Coherency.INVALID, 1)
    dc = d.create_copy(SPACE, _deferred(dev, False), Coherency.EXCLUSIVE, 2)
    dev._account(d, dc, 64)
    return d, dc, False


def _state_deferred_here_resolved(dev):
    d = Data(nb_elts=64)
    dc = d.create_copy(SPACE, _deferred(dev, True), Coherency.EXCLUSIVE, 2)
    dev._account(d, dc, 64)
    return d, dc, False


def _state_deferred_elsewhere(dev):
    d = Data(nb_elts=64)
    bound = d.create_copy(2, _deferred(_OtherDevice(), False),
                          Coherency.EXCLUSIVE, 2)
    return d, bound, False


def _state_evicted(dev):
    """Evicted: the bound device copy is detached, its payload gone."""
    d = Data(nb_elts=64)
    d.create_copy(0, _host(9), Coherency.OWNED, 3)
    bound = DataCopy(d, SPACE, None, Coherency.INVALID, 3)
    return d, bound, False


def _state_attached_without_payload(dev):
    d = Data(nb_elts=64)
    h = d.create_copy(0, _host(9), Coherency.OWNED, 3)
    dc = d.create_copy(SPACE, None, Coherency.SHARED, 3)
    dev._account(d, dc, 64)
    return d, h, False


def _state_first_touch(dev):
    d = Data(nb_elts=64)
    h = d.create_copy(0, _host(2), Coherency.OWNED, 1)
    return d, h, False


#: state -> (factory, the branch each access takes: R = resident)
STATES = {
    "resident": (_state_resident, "RRR"),
    "resident_host_bound": (_state_resident_host_bound, "RRR"),
    "invalid_under_newer_host": (_state_invalid_under_newer_host, "SRS"),
    "invalid_same_version": (_state_invalid_same_version, "SRS"),
    "older_valid_device_copy": (_state_older_valid_device_copy, "SRS"),
    "detached_by_writeback": (_state_detached_by_writeback, "SSS"),
    "detached_device_snapshot": (_state_detached_device_snapshot, "SSS"),
    "pinned_invalidated_in_place": (_state_pinned_invalidated_in_place,
                                    "SSS"),
    "invalidated_in_place_not_pinned":
        (_state_invalidated_in_place_not_pinned, "RRR"),
    "cow_alias": (_state_cow_alias, "SSS"),
    "cow_alias_on_device": (_state_cow_alias_on_device, "SSS"),
    "new_arena_scratch": (_state_new_arena_scratch, "SSS"),
    "written_arena_buffer": (_state_written_arena_buffer, "SSS"),
    "deferred_here_unresolved": (_state_deferred_here_unresolved, "RRR"),
    "deferred_here_resolved": (_state_deferred_here_resolved, "RRR"),
    "deferred_elsewhere": (_state_deferred_elsewhere, "SSS"),
    "evicted": (_state_evicted, "SSS"),
    "attached_without_payload": (_state_attached_without_payload, "SSS"),
    "first_touch": (_state_first_touch, "SSS"),
}
CASES = [(s, a) for s in STATES for a in ACCESSES]


@pytest.fixture(scope="module")
def pair():
    """Two devices over one chip: the change's and the oracle's."""
    devs = [XlaDevice(jax.devices()[0]) for _ in range(2)]
    for d in devs:
        d.space = SPACE
    yield devs
    for d in devs:
        d.fini()


def _fresh(dev):
    with dev._mem_lock:
        dev._lru.clear()
        dev._pins.clear()
        dev._bytes_used = 0
    dev.stats = DeviceStats()
    # two resident bystanders, so that an LRU position can be read
    by = []
    for v in (11, 12):
        d = Data(nb_elts=64)
        dev._account(d, d.create_copy(SPACE, _on_dev(v),
                                      Coherency.EXCLUSIVE, 1), 64)
        by.append(d)
    return by


def _value(p):
    if p is None:
        return None
    if isinstance(p, Deferred):
        return "deferred" if p.array is None else _value(p.array)
    return (str(getattr(p, "dtype", "")),
            np.asarray(p, dtype=np.float64).tolist())


def _copy_state(c):
    return (c.device, c.coherency.name, c.version, c.flags,
            _value(c.payload), c.arena is not None)


def _observe(dev, datum, bound, dc, bystanders):
    names = {id(datum): "datum", id(bystanders[0]): "x",
             id(bystanders[1]): "y"}
    return {
        "dc": _copy_state(dc),
        "dc_is_the_datums": datum.copy_on(SPACE) is dc,
        "dc_is_bound": dc is bound,
        "copies": {sp: _copy_state(c) for sp, c in datum.copies().items()},
        "bound": _copy_state(bound),
        "bound_attached": datum.copy_on(bound.device) is bound,
        "clock": datum._version_clock,
        "bytes_in": dev.stats.bytes_in,
        "bytes_used": dev._bytes_used,
        "pins": sorted((names.get(k, "other"), n)
                       for k, n in dev._pins.items()),
        "lru": [names.get(k, "other") for k in dev._lru],
    }


def _wave_of(bound, access, pinned):
    """One task with one flow, as ``_launch`` sees it."""
    flow = types.SimpleNamespace(name="T", access=access)
    task = types.SimpleNamespace(
        data={"T": bound}, pinned_flows={"T"} if pinned else set(),
        task_class=types.SimpleNamespace(flows=[flow]))
    return [(task, None, 0.0, None)]


@pytest.mark.parametrize("state, access", CASES)
def test_stage_in_leaves_what_the_three_calls_left(pair, state, access):
    """The first branch of ``_stage_in`` and everything behind it: the
    same device copy, coherency and version of every copy, ``bytes_in``,
    pins and LRU order as the parent's sequence, whichever branch the
    copy's state takes; and the counters say which it was."""
    new, old = pair
    factory, branches = STATES[state]
    acc = ACCESSES[access]
    seen = []
    for dev in (new, old):
        by = _fresh(dev)
        datum, bound, pinned = factory(dev)
        if dev is new:
            pinned_per = []
            dev._pin_wave(_wave_of(bound, acc, pinned), pinned_per)
            assert pinned_per == [[datum]]
            dc = dev._stage_in(bound, acc, pinned)
        else:
            _old_pin(dev, datum)
            dc = _old_stage_in(dev, bound, acc, pinned)
        seen.append(_observe(dev, datum, bound, dc, by))
        dev._unpin_all([datum])
        assert not dev._pins
    assert seen[0] == seen[1]
    took = "R" if new.stats.resident_flows else "S"
    assert new.stats.resident_flows + new.stats.staged_flows == 1
    assert took == branches["READ WRITE RW".split().index(access)]
    if took == "R":
        assert seen[0]["bytes_in"] == 0 and seen[0]["dc_is_the_datums"]
    # the oracle's counters never moved: it is the parent's code
    assert old.stats.resident_flows == old.stats.staged_flows == 0


@pytest.mark.parametrize("state, access", CASES)
def test_acquire_on_answers_what_the_three_calls_answered(pair, state,
                                                          access):
    """``Data.acquire_on``: the device's copy, whether the bound copy is
    a snapshot, the source to pull from and every copy's coherency, as
    ``is_pinned_snapshot`` + ``copy_on`` + ``transfer_ownership`` gave
    them — under one hold of the datum's lock."""
    new, old = pair
    factory, _branches = STATES[state]
    acc = ACCESSES[access]
    answers = []
    for dev, ask in ((new, None), (old, _old_acquire)):
        _fresh(dev)
        datum, bound, pinned = factory(dev)
        holds = []
        real = datum._lock

        class Counting:
            def __enter__(self):
                holds.append(1)
                return real.__enter__()

            def __exit__(self, *exc):
                return real.__exit__(*exc)
        datum._lock = Counting()
        if ask is None:
            dc, snapshot, src = datum.acquire_on(SPACE, acc, bound, pinned)
            assert len(holds) == 1
        else:
            dc, snapshot, src = ask(datum, SPACE, acc, bound, pinned)
        datum._lock = real
        answers.append({
            "dc": None if dc is None else _copy_state(dc),
            "dc_is_the_datums": dc is datum.copy_on(SPACE),
            "snapshot": snapshot,
            "src": None if src is None else _copy_state(src),
            "copies": {sp: _copy_state(c)
                       for sp, c in datum.copies().items()},
            "bound": _copy_state(bound)})
    assert answers[0] == answers[1]


def test_transfer_ownership_reads_as_before():
    """The transition ``acquire_on`` shares with ``transfer_ownership``,
    on the three-copy cases ``newest_copy``'s preferences decide."""
    def build():
        d = Data(nb_elts=8)
        d.create_copy(0, _host(1), Coherency.SHARED, 3)
        d.create_copy(1, _host(2), Coherency.OWNED, 3)
        d.create_copy(2, _host(3), Coherency.INVALID, 1)
        d.create_copy(3, _host(4), Coherency.SHARED, 2)
        return d
    d = build()
    assert d.newest_copy() is d.copy_on(1)            # OWNED over SHARED
    assert d.newest_copy(prefer_device=0) is d.copy_on(1)
    assert d.newest_version() == 3
    src = d.transfer_ownership(2, ACCESS_READ)        # a stale reader
    assert src is d.copy_on(1) and d.copy_on(2).coherency == Coherency.SHARED
    assert d.copy_on(1).coherency == Coherency.OWNED
    d = build()
    assert d.transfer_ownership(3, ACCESS_RW) is d.copy_on(1)
    assert [c.coherency for c in d.copies().values()] == [
        Coherency.INVALID, Coherency.INVALID, Coherency.INVALID,
        Coherency.EXCLUSIVE]
    d = build()
    assert d.transfer_ownership(0, ACCESS_WRITE) is None   # overwritten
    with pytest.raises(KeyError):
        d.transfer_ownership(9, ACCESS_READ)
    e = Data(nb_elts=8)
    e.create_copy(0, _host(1), Coherency.EXCLUSIVE, 1)
    e.create_copy(1, None, Coherency.INVALID, 0)
    assert e.transfer_ownership(1, ACCESS_READ) is e.copy_on(0)
    assert e.copy_on(0).coherency == Coherency.OWNED       # shared out


def test_a_wave_is_pinned_and_touched_under_one_hold(pair):
    """``_pin_wave``: every datum of every task of the wave pinned, a
    list a task, resident ones moved to the LRU's young end in flow
    order, under ONE hold of ``_mem_lock``."""
    new, _old = pair
    x, y = _fresh(new)
    fresh = Data(nb_elts=8)                    # not in the LRU yet
    flows = [types.SimpleNamespace(name=n, access=ACCESS_READ)
             for n in ("A", "B", "C")]
    tc = types.SimpleNamespace(flows=flows)

    def task(**data):
        return types.SimpleNamespace(
            task_class=tc, pinned_flows=set(),
            data={k: DataCopy(d, SPACE) if d is not None else None
                  for k, d in data.items()})
    batch = [(task(A=y, B=fresh, C=None), None, 0.0, None),
             (task(A=x, B=y), None, 0.0, None)]
    holds = []
    real = new._mem_lock

    class Counting:
        def __enter__(self):
            holds.append(1)
            return real.__enter__()

        def __exit__(self, *exc):
            return real.__exit__(*exc)
    new._mem_lock = Counting()
    try:
        pinned_per = []
        new._pin_wave(batch, pinned_per)
    finally:
        new._mem_lock = real
    assert len(holds) == 1
    assert pinned_per == [[y, fresh], [x, y]]
    assert new._pins == {id(y): 2, id(fresh): 1, id(x): 1}
    assert list(new._lru) == [id(x), id(y)]     # y, then x, then y again
    new._unpin_all(d for p in pinned_per for d in p)
    assert not new._pins


# ---------------------------------------------------------------------
# (b) signatures
# ---------------------------------------------------------------------
def _old_fuse_sig(task, spec):
    """``XlaDevice._fuse_sig`` of the parent commit."""
    sig = []
    try:
        for a in spec.arg_names:
            if a in spec.flow_names:
                copy = task.data.get(a)
                p = copy.payload if copy is not None else None
                if p is None:
                    return None
                sig.append((a, tuple(p.shape), str(p.dtype)))
            else:
                v = task.locals.get(a, task.taskpool.globals.get(a))
                hash(v)
                sig.append((a, v))
    except Exception:
        return None
    return tuple(sig)


def _sig_spec():
    def fn(L, C, k, alpha):
        return C
    return XlaKernel(fn, ["L", "C", "k", "alpha"], ["L", "C"], ["C"],
                     cls="UPD")


def _sig_task(L=(4, 4), C=(4, 4), Ldt=np.float32, Cdt=np.float32, k=0,
              alpha=None, unbound=None, kind="numpy", properties=None):
    def payload(shape, dt):
        if kind == "numpy":
            return np.zeros(shape, dt)
        if kind == "jax":
            return jax.device_put(np.zeros(shape, dt), jax.devices()[0])
        return Deferred(None, "C", tuple(shape), np.dtype(dt))
    d = Data(nb_elts=1)
    data = {"L": DataCopy(d, 0, payload(L, Ldt)),
            "C": DataCopy(d, 0, payload(C, Cdt))}
    if unbound == "none":
        data["L"] = None
    elif unbound == "missing":
        del data["L"]
    elif unbound == "payload":
        data["L"].payload = None
    return types.SimpleNamespace(
        data=data, locals={"k": k},
        taskpool=types.SimpleNamespace(globals={"alpha": alpha}),
        task_class=types.SimpleNamespace(properties=properties or {},
                                         name="UPD"))


SIG_PAIRS = {
    "equal": ({}, {}),
    "equal_numpy_and_device_array": ({}, {"kind": "jax"}),
    "equal_array_and_placeholder": ({"kind": "jax"}, {"kind": "deferred"}),
    "one_flows_shape": ({}, {"L": (8, 4)}),
    "the_other_flows_shape": ({}, {"C": (4, 8)}),
    "one_flows_dtype": ({}, {"Ldt": np.float16}),
    "one_flows_dtype_bf16": ({"Cdt": jax.numpy.bfloat16}, {}),
    "both_bf16": ({"Cdt": jax.numpy.bfloat16}, {"Cdt": jax.numpy.bfloat16}),
    "a_local_static": ({}, {"k": 1}),
    "a_global_static": ({}, {"alpha": 2.0}),
    "a_static_of_another_type": ({"alpha": "x"}, {"alpha": ("x",)}),
    "an_unbound_flow_none": ({}, {"unbound": "none"}),
    "an_unbound_flow_missing": ({"unbound": "missing"},
                                {"unbound": "missing"}),
    "an_evicted_payload": ({"unbound": "payload"}, {}),
    "an_unhashable_static": ({"alpha": [1]}, {"alpha": [1]}),
    "an_unhashable_static_one_side": ({}, {"alpha": {}}),
}


@pytest.mark.parametrize("pair_name", SIG_PAIRS)
def test_two_tasks_fuse_exactly_as_at_the_parent(pair_name):
    """``XlaKernel.task_sig``: no strings, no per-flow tuple — and two
    tasks fuse exactly when the parent's ``_fuse_sig`` said so; an
    unbound flow or an unhashable static still answers "not fusable"."""
    spec = _sig_spec()
    a, b = (_sig_task(**kw) for kw in SIG_PAIRS[pair_name])
    new = [spec.task_sig(t) for t in (a, b)]
    old = [_old_fuse_sig(t, spec) for t in (a, b)]
    assert [s is None for s in new] == [s is None for s in old]
    assert (new[0] is not None and new[0] == new[1]) == \
        (old[0] is not None and old[0] == old[1])
    for s in new:
        if s is not None:
            hash(s)
            assert not any(isinstance(x, str) and x.startswith(("float",
                           "bfloat")) for x in s)
            # what the width's key is built from when no task is at hand
            assert spec.args_sig([np.zeros(s[0], s[1]),
                                  np.zeros(s[2], s[3]), s[4], s[5]]) == s


@pytest.mark.parametrize("pair_name", SIG_PAIRS)
def test_the_wave_scan_reads_the_queued_signature(pair, pair_name):
    """``_pop_wave_locked`` compares what ``submit`` queued: the wave is
    what the parent's scan made of the same queue."""
    new, _old = pair
    spec = _sig_spec()
    tasks = [_sig_task(**SIG_PAIRS[pair_name][i % 2]) for i in range(4)]
    items = [(t, spec, 0.0, spec.task_sig(t)) for t in tasks]
    olds = [_old_fuse_sig(t, spec) for t in tasks]
    want = [tasks[0]]
    if olds[0] is not None:
        want += [t for t, s in zip(tasks[1:], olds[1:]) if s == olds[0]]
    want = want[:1 << (len(want).bit_length() - 1)]    # a power of two
    params.set("device_fuse", 8)
    try:
        with new._cond:
            new._pending.extend(items[1:])
            batch = new._pop_wave_locked(items[0])
            left = [it[0] for it in new._pending]
            new._pending.clear()
    finally:
        params.unset("device_fuse")
    assert [it[0] for it in batch] == want
    assert sorted(map(id, left)) == sorted(
        id(t) for t in tasks if t not in want)


def test_a_chain_link_is_queued_without_a_signature(pair):
    """A ``fuse_chain`` class goes alone: ``submit`` gives it no
    signature, so the scan never looks at its siblings."""
    new, _old = pair
    spec = _sig_spec()
    ctx = types.SimpleNamespace()
    es = types.SimpleNamespace(context=ctx, hand_in=None)
    link = _sig_task(properties={"fuse_chain": ("C", "UPD"), "flops": 2.0})
    plain = _sig_task(properties={"flops": lambda loc: 3.0})
    held = []
    # keep the managers off the queue while it is read
    with new._cond:
        new.es = new.es or object()
        for t in (link, plain):
            new.submit(es, t, spec)
        held = list(new._pending)
        new._pending.clear()
    new.load_sub(5.0)
    assert [(it[0], it[2]) for it in held] == [(link, 2.0), (plain, 3.0)]
    assert held[0][3] is None
    assert held[1][3] == spec.task_sig(plain) is not None


# ---------------------------------------------------------------------
# (c) the width's state under the wave's signature
# ---------------------------------------------------------------------
def _wave_fn():
    def axpy(X, Y, alpha):
        return Y + alpha * X
    return axpy


def _wave_pool(ctx, fn, MT, mb, alpha=2.0, fill=1.0):
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range
    X = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=MT * mb)
    Y = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=MT * mb)
    for _m, n in X.local_tiles():
        X.data_of(0, n).copy_on(0).payload[:] = float(n)
        Y.data_of(0, n).copy_on(0).payload[:] = fill
    p = PTG("wave", MT=MT, alpha=alpha)
    tb = p.task("AXPY", n=Range(0, MT - 1)) \
        .affinity(lambda n, Y=Y: Y(0, n)) \
        .flow("X", "READ", IN(DATA(lambda n, X=X: X(0, n)))) \
        .flow("Y", "RW", IN(DATA(lambda n, Y=Y: Y(0, n))),
              OUT(DATA(lambda n, Y=Y: Y(0, n))))
    tb.body(fn, device="tpu")
    return p.build(), Y


@pytest.fixture
def one_wave_mca():
    # one manager and a window long enough for the siblings to meet:
    # each pool is ONE wave however the threads interleave
    mca = {"device_max": 1, "device_fuse": 4, "device_dispatchers": 1,
           "device_fuse_window_ms": 2000.0,
           "device_fuse_warm_wait_ms": 60000.0}
    for k, v in mca.items():
        params.set(k, v)
    yield
    wait_fuse_warm()
    for k in mca:
        params.unset(k)


def test_a_width_one_pool_warmed_is_ready_for_the_next(one_wave_mca):
    """Two pools over one kernel function, two flows and a static: the
    second submits no warm compile and waits for none; a pool with
    another static value, or other shapes, is another program."""
    from parsec_tpu.core.context import Context
    MT, mb = 4, 8
    fn = _wave_fn()
    with Context(nb_cores=2) as ctx:
        (dev,) = ctx.device_registry.accelerators

        def job(alpha=2.0, mb=mb):
            tp, Y = _wave_pool(ctx, fn, MT, mb, alpha)
            ctx.add_taskpool(tp)
            ctx.wait(timeout=120)
            for n in range(MT):
                np.testing.assert_allclose(np.asarray(
                    Y.data_of(0, n).pull_to_host().payload),
                    1.0 + alpha * n)
            return dev.stats.as_dict()
        first = job()
        second = job()
        # asked for directly, with no task at hand: the same key
        k = XlaKernel(fn, ["X", "Y", "alpha"], ["X", "Y"], ["Y"],
                      cls="AXPY")
        z = jax.device_put(np.zeros((mb, mb), np.float32), dev.jdev)
        assert k.fuse_ready(False, MT, [z, z, 2.0] * MT, dev) is True
        third = dev.stats.as_dict()
        other_static = job(alpha=3.0)
        other_shape = job(mb=16)
    assert first["fused_launches"] == 1 and first["fused_tasks"] == MT
    assert first["warm_waits"] == 1
    for later in (second, third):
        assert later["warm_waits"] == first["warm_waits"]
        assert later["compiles"] == first["compiles"]
    assert second["fused_launches"] == 2 and second["defused_waves"] == 0
    assert second["resident_flows"] + second["staged_flows"] == 2 * 2 * MT
    assert other_static["warm_waits"] == first["warm_waits"] + 1
    assert other_shape["warm_waits"] == first["warm_waits"] + 2
    assert other_shape["fused_launches"] == 4
    assert other_shape["defused_waves"] == 0


def test_a_failed_width_backs_off_sixty_seconds(monkeypatch, capfd):
    """A width whose compile failed answers False without a wait, to
    the wave that carries its signature and to the next pool's, and is
    asked for again after 60 s."""
    class Refused:
        def lower(self, *a):
            raise ValueError("Mosaic failed to compile: Bad lhs type")
    fn = _wave_fn()
    real = XlaKernel.jitted_fused
    monkeypatch.setattr(
        XlaKernel, "jitted_fused",
        lambda self, donate, n: Refused()
        if threading.current_thread().name == "xla-fuse-warm"
        else real(self, donate, n))
    dev = XlaDevice(jax.devices()[0])
    try:
        z = jax.device_put(np.zeros((4, 4), np.float32), dev.jdev)
        flat = [z, z, 2.0] * 2

        def pool_kernel():
            return XlaKernel(fn, ["X", "Y", "alpha"], ["X", "Y"], ["Y"],
                             cls="AXPY")
        sig = pool_kernel().args_sig(flat[:3])
        for pool in range(3):
            assert pool_kernel().fuse_ready(
                False, 2, flat, dev, sig if pool % 2 else None) is False
            assert (dev.stats.compiles, dev.stats.warm_waits) == (1, 1)
        assert list(dev.fuse_failures) == [(pool_kernel().name, 2)]
        assert capfd.readouterr().err.count("fused width 2 of kernel") == 1
        monkeypatch.setattr(XlaKernel, "jitted_fused", real)
        cache = fn.__parsec_jit_cache__
        (key,) = [k for k in cache if k[0] == "w"]
        assert key == ("w", "AXPY", False, 2, sig, dev.name)
        cache[key] = ("failed", cache[key][1] - 59.0, cache[key][2])
        assert pool_kernel().fuse_ready(False, 2, flat, dev, sig) is False
        assert dev.stats.compiles == 1                 # still backing off
        cache[key] = ("failed", cache[key][1] - 2.0, cache[key][2])
        assert pool_kernel().fuse_ready(False, 2, flat, dev, sig) is True
        assert (dev.stats.compiles, dev.stats.warm_waits) == (2, 2)
    finally:
        wait_fuse_warm()
        dev.fini()


# ---------------------------------------------------------------------
# (d) resident_flows + staged_flows = the flows of the tasks run
# ---------------------------------------------------------------------
def _flows_counter(ctx):
    """Flows with a bound copy, task by task, as the tasks go to the
    device (PINS ``exec_async``): what the managers will stage."""
    seen = []
    lock = threading.Lock()

    def on_async(es, event, task):
        n = sum(1 for f in task.task_class.flows
                if task.data.get(f.name) is not None)
        with lock:
            seen.append(n)
    ctx.pins_register("exec_async", on_async)
    return seen


def _spd(n, seed):
    rng = np.random.default_rng(seed)
    B = rng.standard_normal((n, n)).astype(np.float32)
    return (B @ B.T + n * np.eye(n)).astype(np.float32)


def _stats(ctx):
    (dev,) = ctx.device_registry.accelerators
    return dev, dev.stats.as_dict()


def test_potrf_jobs_count_every_flow_and_stage_only_the_new_panels():
    """potrf at nt = 6 on a CPU device: the two counters add up to the
    flows of the tasks run; a second job over tiles the first left on
    the device stages its nt - 1 NEW-arena ``W`` panels and no tile of
    A; both factors are right."""
    from parsec_tpu.apps.potrf import potrf_taskpool
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    mb, nt = 8, 6
    n = nt * mb
    params.set("device_max", 1)
    try:
        with Context(nb_cores=4) as ctx:
            dev, _ = _stats(ctx)
            seen = _flows_counter(ctx)
            spd = _spd(n, 61)
            A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n,
                                  ln=n).from_array(spd.copy())
            ctx.add_taskpool(potrf_taskpool(A, device="tpu"))
            ctx.wait(timeout=120)
            _, first = _stats(ctx)
            flows1 = sum(seen)
            L = np.tril(A.to_array())
            assert np.abs(L @ L.T - spd).max() / np.abs(spd).max() < 1e-4
            # the next operand is born on the device, over the tiles the
            # first job left there
            spd2 = _spd(n, 62)
            for i in range(nt):
                for j in range(i + 1):
                    A.data_of(i, j).overwrite_on(dev.space, jax.device_put(
                        spd2[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb],
                        dev.jdev))
            ctx.add_taskpool(potrf_taskpool(A, device="tpu"))
            ctx.wait(timeout=120)
            _, second = _stats(ctx)
            flows2 = sum(seen) - flows1
            L = np.tril(A.to_array())
            assert np.abs(L @ L.T - spd2).max() / np.abs(spd2).max() < 1e-4
    finally:
        params.unset("device_max")
    # POTRF T W, POTRFL T, TRSM W C, SYRK T R, GEMM C L R
    by_formula = 2 * (nt - 1) + 1 + 2 * (nt * (nt - 1) // 2) * 2 \
        + 3 * (nt * (nt - 1) * (nt - 2) // 6)
    assert flows1 == flows2 == by_formula == 131
    assert first["executed_tasks"] + first["held_tasks"] == 56
    assert first["resident_flows"] + first["staged_flows"] == flows1
    # the first job pulls each lower tile from the host once
    assert first["staged_flows"] == nt * (nt + 1) // 2 + (nt - 1)
    assert second["resident_flows"] + second["staged_flows"] \
        == flows1 + flows2
    assert second["staged_flows"] - first["staged_flows"] == nt - 1
    assert second["bytes_in"] == first["bytes_in"]


def test_geqrf_job_counts_every_flow():
    """geqrf at nt = 4 (chain programs over placeholders, NEW-arena Q
    panels, two-tile waves): the counters add up, the factor is right."""
    from parsec_tpu.apps.qr import qr_taskpool
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    mb, nt = 8, 4
    n = nt * mb
    a = np.random.default_rng(63).standard_normal((n, n)).astype(np.float32)
    params.set("device_max", 1)
    try:
        with Context(nb_cores=4) as ctx:
            seen = _flows_counter(ctx)
            A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n,
                                  ln=n).from_array(a.copy())
            ctx.add_taskpool(qr_taskpool(A, device="tpu"))
            ctx.wait(timeout=120)
            _, st = _stats(ctx)
    finally:
        params.unset("device_max")
    tasks = nt + 2 * (nt * (nt - 1) // 2) \
        + (nt - 1) * nt * (2 * nt - 1) // 6
    assert len(seen) == tasks == st["executed_tasks"] + st["held_tasks"]
    assert st["resident_flows"] + st["staged_flows"] == sum(seen)
    # each tile once from the host, each Q panel once from its arena
    assert st["staged_flows"] >= nt * nt + nt + nt * (nt - 1) // 2
    assert st["chained_launches"] > 0
    out = A.to_array()
    R = np.triu(out)
    ata = a.T @ a
    assert np.abs(np.tril(out, -1)).max() < 1e-4
    assert np.abs(R.T @ R - ata).max() / np.abs(ata).max() < 1e-4


# ---------------------------------------------------------------------
# (e) the donation hazard
# ---------------------------------------------------------------------
def _old_donation_hazard(spec, flat):
    """``XlaDevice._donation_hazard`` of the parent commit."""
    k = len(spec.arg_names)
    donatable = [i for i, a in enumerate(spec.arg_names)
                 if a in spec.flow_names and a in spec.writable]
    if not donatable:
        return False
    donated_ids = set()
    for t in range(len(flat) // k):
        for i in donatable:
            donated_ids.add(id(flat[t * k + i]))
    seen = {}
    for v in flat:
        seen[id(v)] = seen.get(id(v), 0) + 1
    return any(seen.get(d, 0) > 1 for d in donated_ids)


_T = [np.zeros((2, 2), np.float32) for _ in range(8)]

HAZARDS = {
    # a GEMM-like wave: C (written) L R k
    "a_clean_wave": ([_T[0], _T[1], _T[2], 0, _T[3], _T[4], _T[5], 0],
                     False),
    "shared_read_operands": ([_T[0], _T[1], _T[2], 0,
                              _T[3], _T[1], _T[2], 0], False),
    "a_members_C_is_anothers_L": ([_T[0], _T[1], _T[2], 0,
                                   _T[3], _T[0], _T[5], 0], True),
    "a_members_C_is_anothers_R": ([_T[0], _T[1], _T[2], 0,
                                   _T[3], _T[4], _T[0], 0], True),
    "a_members_C_is_its_own_L": ([_T[0], _T[0], _T[2], 0], True),
    "two_members_write_one_tile": ([_T[0], _T[1], _T[2], 0,
                                    _T[0], _T[4], _T[5], 0], True),
    "a_single_task": ([_T[0], _T[1], _T[2], 7], False),
    "the_last_member_reads_the_firsts_C": (
        [_T[0], _T[1], _T[2], 0, _T[3], _T[1], _T[2], 0,
         _T[4], _T[1], _T[2], 0, _T[5], _T[0], _T[2], 0], True),
}


@pytest.mark.parametrize("wave", HAZARDS)
def test_the_hazard_check_reads_the_wave(wave):
    """A wave whose donated C is another member's L or R does not
    donate, a clean wave does — as at the parent."""
    flat, hazard = HAZARDS[wave]
    spec = XlaKernel(lambda C, L, R, k: C, ["C", "L", "R", "k"],
                     ["C", "L", "R"], ["C"], cls="GEMM")
    assert spec.donate_pos == (0,)
    assert XlaDevice._donation_hazard(spec, flat) is hazard
    assert _old_donation_hazard(spec, flat) is hazard
    reads = XlaKernel(lambda C, L, R, k: None, ["C", "L", "R", "k"],
                      ["C", "L", "R"], [], cls="READS")
    assert reads.donate_pos == ()
    assert XlaDevice._donation_hazard(reads, flat) is False
    two = XlaKernel(lambda C, L, R, k: (C, R), ["C", "L", "R", "k"],
                    ["C", "L", "R"], ["C", "R"], cls="TWO")
    assert two.donate_pos == (0, 2)
    assert XlaDevice._donation_hazard(two, flat) \
        is _old_donation_hazard(two, flat)
