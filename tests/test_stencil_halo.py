"""The stencil's halo exchange (apps/stencil.py, PR 37): what crosses a
tile boundary is the halo rows alone, a tile has one consumer and is
updated in place, and a task costs one launch.

(a) the runtime against ``stencil_reference`` on seeded data: both
incarnations, 1-D and (rows x lanes) tiles, ``fuse`` 1 and > 1 with a
ragged last block, NT = 2 (a tile that is its neighbour's neighbour on
both sides) and more; (b) the same comparison catches planted faults;
(c) what the deployment promises, as counts of the device module: no
private copy of a tile (``snapshot_flows`` / ``snapshot_bytes``), the
NEW halo flows share one blank buffer a shape, every ``S`` wave may
donate ``C`` and does; (d) the two counters on a standalone device, by
the branch of ``_stage_in`` a copy takes, and in the metrics scrape.
"""

import types

import jax
import numpy as np
import pytest

from parsec_tpu.apps import stencil
from parsec_tpu.apps.stencil import stencil_reference, stencil_taskpool
from parsec_tpu.core.context import Context
from parsec_tpu.data.arena import Arena
from parsec_tpu.data.data import (ACCESS_READ, ACCESS_RW, ACCESS_WRITE,
                                  Coherency, Data, DataCopy, FLAG_COW,
                                  FLAG_SCRATCH)
from parsec_tpu.data.matrix import TwoDimBlockCyclic, VectorTwoDimCyclic
from parsec_tpu.devices.xla import XlaDevice
from parsec_tpu.utils.mca import params


def _grid(nt, mb, nb, seed):
    """Seeded data and its collection: a vector of tiles where ``nb`` is
    None, else a matrix one tile wide (mb rows x nb lanes a tile)."""
    rng = np.random.default_rng(seed)
    if nb is None:
        x = rng.standard_normal(nt * mb).astype(np.float32)
        return x, VectorTwoDimCyclic(mb=mb, lm=nt * mb).from_array(x.copy())
    x = rng.standard_normal((nt * mb, nb)).astype(np.float32)
    return x, TwoDimBlockCyclic(mb=mb, nb=nb, lm=nt * mb,
                                ln=nb).from_array(x.copy())


def _run(V, steps, device, fuse, donate=False, watch=None):
    """One job on one device; returns its DeviceStats as a dict (the
    first accelerator's: ``device_max`` 1 keeps the job on it) and the
    blanks it held when the job ended (``fini`` drops them)."""
    params.set("device_max", 1)
    try:
        with Context(nb_cores=4) as ctx:
            dev = ctx.device_registry.accelerators[0]
            if donate:
                dev._donate = True       # the CPU client donates too
            if watch is not None:
                watch(dev)
            ctx.add_taskpool(stencil_taskpool(V, steps, device=device,
                                              fuse=fuse))
            ctx.wait()
            return dev.stats.as_dict(), dict(dev._blanks)
    finally:
        params.unset("device_max")


# ---------------------------------------------------------------------
# (a) against the reference
# ---------------------------------------------------------------------
@pytest.mark.parametrize("device", ["tpu", "cpu"])
@pytest.mark.parametrize("nb", [None, 16], ids=["rows", "rows_x_lanes"])
@pytest.mark.parametrize("nt, fuse, steps", [
    (2, 1, 7), (4, 1, 6), (5, 1, 9), (2, 3, 8), (4, 4, 11), (6, 8, 8)])
def test_runtime_matches_the_reference(device, nb, nt, fuse, steps):
    x, V = _grid(nt, 8, nb, seed=nt * 100 + fuse)
    _run(V, steps, device, fuse)
    np.testing.assert_allclose(V.to_array(), stencil_reference(x, steps),
                               rtol=1e-5, atol=2e-6)


def test_lane_sums_are_conserved():
    """Periodic boundary, weights that sum to 1: every lane's sum stays
    (what the benchmark's whole-grid number holds)."""
    x, V = _grid(4, 8, 16, seed=5)
    _run(V, 12, "tpu", 1)
    np.testing.assert_allclose(V.to_array().sum(axis=0, dtype=np.float64),
                               x.sum(axis=0, dtype=np.float64), atol=1e-4)


@pytest.mark.parametrize("fuse", [0, 9])
def test_halo_deeper_than_the_tile_is_refused(fuse):
    _x, V = _grid(4, 8, None, seed=1)
    with pytest.raises(ValueError):
        stencil_taskpool(V, 4, fuse=fuse)


def test_one_graph_for_every_halo_depth():
    """fuse = 1 is the halo depth 1 of the one definition: the same
    classes and flows, ``-(-steps // fuse)`` blocks of NT tasks."""
    _x, V = _grid(4, 8, 16, seed=1)
    pools = [stencil_taskpool(V, 12, device="cpu", fuse=f) for f in (1, 5)]
    shapes = [{name: [f.name for f in tc.flows]
               for name, tc in tp.task_classes.items()} for tp in pools]
    assert shapes[0] == shapes[1] == {
        "INIT": ["X", "TOP", "BOT"], "S": ["HL", "HR", "C", "TOP", "BOT"]}
    assert [tp.arenas["halo"].shape for tp in pools] == [(1, 16), (5, 16)]
    assert not hasattr(stencil, "_stencil_taskpool_fused")


@pytest.mark.parametrize("mb, nb, bm, bn", [
    (32, 256, 16, 128),      # two row blocks down each of two lane blocks
    (16, 128, 16, 128),      # one block: first and last row block at once
    (48, 384, 16, 128)])
def test_in_place_sweep_kernel_matches_the_plain_form(mb, nb, bm, bn):
    """The chip's sweep kernel (apps/pallas_kernels.py), interpreted:
    the same tile and halos as ``_sweeps`` gives, the output one row
    block behind the input; a shape that does not tile takes the XLA
    form handed in."""
    from parsec_tpu.apps.pallas_kernels import (PALLAS, XLA,
                                                pallas_sweep_tile)
    rng = np.random.default_rng(mb)
    HL, C, HR = (rng.standard_normal(s).astype(np.float32)
                 for s in ((1, nb), (mb, nb), (1, nb)))
    want = stencil._sweeps(np, HL, C, HR, 1)
    fn = pallas_sweep_tile(lambda *a: "xla", bm=bm, bn=bn, interpret=True)
    new, top, bot = fn(HL, C, HR)
    assert fn.selected == {(mb, nb): PALLAS}
    for got, ref in zip((new, top, bot), want):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-6,
                                   atol=1e-6)
    assert fn(HL[:, :100], C[:, :100], HR[:, :100]) == "xla"
    assert fn.selected[(mb, 100)] == XLA


# ---------------------------------------------------------------------
# (b) planted faults come out wrong
# ---------------------------------------------------------------------
def _wrong_side(xp, HL, C, HR, ns):
    return _REAL(xp, HR, C, HL, ns)


def _stale_halos(xp, HL, C, HR, ns):
    H = HL.shape[0]
    # (times one: a private buffer, not a view of the tile the host body
    # overwrites in place before the halos are bound)
    return (_REAL(xp, HL, C, HR, ns)[0], C[:H] * 1.0,
            C[C.shape[0] - H:] * 1.0)


def _dropped_sweep(xp, HL, C, HR, ns):
    if ns > 1:
        return _REAL(xp, HL, C, HR, ns - 1)
    return (C,) + _REAL(xp, HL, C, HR, ns)[1:]


_REAL = stencil._sweeps


@pytest.mark.parametrize("device", ["tpu", "cpu"])
@pytest.mark.parametrize("fault", [_wrong_side, _stale_halos,
                                   _dropped_sweep])
def test_planted_fault_fails_the_comparison(monkeypatch, device, fault):
    monkeypatch.setattr(stencil, "_sweeps", fault)
    # the device kernel is memoized with its traced programs: a fresh
    # function for the fault, the real one back afterwards
    monkeypatch.setattr(stencil, "_kernels", {})
    x, V = _grid(4, 8, 16, seed=3)
    _run(V, 6, device, 1)
    assert np.abs(V.to_array() - stencil_reference(x, 6)).max() > 1e-3


# ---------------------------------------------------------------------
# (c) what the deployment promises, as counts
# ---------------------------------------------------------------------
NT, MB, NBL, STEPS = 8, 8, 128, 6
TILE = MB * NBL * 4


def _watch_waves(seen):
    """Record every plain dispatch of the device: class, width, whether
    the wave might donate (no hazard) and whether it did."""
    def watch(dev):
        real = dev._dispatch_plain

        def spy(spec, n, flat, cover=False, sig=None):
            seen.append((spec.cls, n, tuple(spec.arg_names[i]
                                            for i in spec.donate_pos),
                         XlaDevice._donation_hazard(spec, flat)))
            return real(spec, n, flat, cover, sig)
        dev._dispatch_plain = spy
    return watch


@pytest.fixture(scope="module")
def job():
    seen = []
    x, V = _grid(NT, MB, NBL, seed=11)
    stats, blanks = _run(V, STEPS, "tpu", 1, donate=True,
                         watch=_watch_waves(seen))
    return {"x": x, "got": V.to_array(), "stats": stats, "waves": seen,
            "blanks": blanks}


def test_donating_in_place_job_is_right(job):
    np.testing.assert_allclose(job["got"],
                               stencil_reference(job["x"], STEPS),
                               rtol=1e-5, atol=2e-6)


def test_no_tile_is_copied(job):
    """The device module makes no private copy of anything: what it
    moved in is the NT host-born tiles' first staging, once."""
    st = job["stats"]
    assert st["snapshot_flows"] == 0 and st["snapshot_bytes"] == 0
    assert st["bytes_in"] == NT * TILE
    tasks = NT * STEPS + NT
    assert st["executed_tasks"] == tasks
    # staged: the NT tiles once, and the two NEW halo flows a task
    assert st["staged_flows"] == NT + 2 * tasks
    assert st["resident_flows"] == 3 * NT * STEPS


def test_halo_flows_share_one_blank(job):
    """A flow the kernel only returns costs no buffer and no dispatch:
    one zeros buffer a shape stands in for all of them."""
    assert list(job["blanks"]) == [((1, NBL), np.dtype(np.float32))]


def test_every_wave_donates_the_tile(job):
    """C is the donated position of S, and no S wave holds a buffer
    twice: the tile of one task is no operand of its neighbours."""
    s_waves = [w for w in job["waves"] if w[0] == "S"]
    assert sum(n for _c, n, _d, _h in s_waves) == NT * STEPS
    assert any(n > 1 for _c, n, _d, _h in s_waves)
    assert all(d == ("C",) and not hazard for _c, _n, d, hazard in s_waves)
    # INIT reads its tile and returns the halos: nothing to donate
    assert all(d == () for c, _n, d, _h in job["waves"] if c == "INIT")


def test_a_task_rides_one_launch(job):
    """Every jitted call of the job is a wave of S or INIT tasks: no
    dispatch beside them (a zero fill, a slice, a copy)."""
    st = job["stats"]
    assert st["launches"] == len(job["waves"]) <= st["executed_tasks"]
    assert st["fused_tasks"] > 0 and st["defused_waves"] == 0


# ---------------------------------------------------------------------
# (d) the counters, branch by branch
# ---------------------------------------------------------------------
SPACE = 1


@pytest.fixture
def dev():
    d = XlaDevice(jax.devices()[0])
    d.space = SPACE
    yield d
    d.fini()


def _host():
    return np.full((4, 4), 3.0, np.float32)


def _on_dev():
    return jax.device_put(_host(), jax.devices()[0])


def _cow_on_device():
    d = Data(nb_elts=64)
    bound = d.create_copy(2, _on_dev(), Coherency.SHARED, 1)
    bound.flags |= FLAG_COW
    return bound, False


def _cow_on_host():
    d = Data(nb_elts=64)
    bound = d.create_copy(0, _host(), Coherency.SHARED, 1)
    bound.flags |= FLAG_COW
    return bound, False


def _detached_snapshot():
    d = Data(nb_elts=64)
    bound = DataCopy(d, SPACE, _on_dev(), Coherency.SHARED, 1)
    d.create_copy(0, _host(), Coherency.EXCLUSIVE, 2)
    return bound, True


def _invalidated_in_place():
    d = Data(nb_elts=64)
    bound = d.create_copy(SPACE, _on_dev(), Coherency.INVALID, 1)
    d.create_copy(0, _host(), Coherency.EXCLUSIVE, 2)
    return bound, True


def _resident():
    d = Data(nb_elts=64)
    return d.create_copy(SPACE, _on_dev(), Coherency.EXCLUSIVE, 1), True


def _first_touch():
    d = Data(nb_elts=64)
    return d.create_copy(0, _host(), Coherency.OWNED, 1), False


@pytest.mark.parametrize("state, copies", [
    (_cow_on_device, 1), (_cow_on_host, 1), (_detached_snapshot, 1),
    (_invalidated_in_place, 1), (_resident, 0), (_first_touch, 0)])
@pytest.mark.parametrize("access", [ACCESS_READ, ACCESS_RW])
def test_snapshot_counters_by_branch(dev, state, copies, access):
    bound, pinned = state()
    pinned_per = []
    flow = types.SimpleNamespace(name="T", access=access)
    task = types.SimpleNamespace(
        data={"T": bound}, pinned_flows={"T"} if pinned else set(),
        task_class=types.SimpleNamespace(flows=[flow]))
    dev._pin_wave([(task, None, 0.0, None)], pinned_per)
    dc = dev._stage_in(bound, access, pinned)
    np.testing.assert_array_equal(np.asarray(dc.payload), _host())
    st = dev.stats
    assert (st.snapshot_flows, st.snapshot_bytes) == (copies, 64 * copies)
    assert st.snapshot_bytes <= st.bytes_in
    assert st.resident_flows + st.staged_flows == 1


def test_unhanded_new_flow_takes_the_shared_blank(dev):
    arena = Arena((2, 8), np.float32)

    def stage(handed):
        bound = arena.get_copy(backed=False)
        bound.flags |= FLAG_SCRATCH
        return dev._stage_in(bound, ACCESS_WRITE, False, handed)

    a, b, c, d = stage(False), stage(False), stage(True), stage(True)
    assert a.payload is b.payload is dev._blanks[((2, 8),
                                                  np.dtype(np.float32))]
    assert c.payload is not d.payload and c.payload is not a.payload
    assert a.data is not b.data and a.version == 0
    assert dev.stats.staged_flows == 4 and dev.stats.snapshot_flows == 0
    assert float(np.abs(np.asarray(a.payload)).max()) == 0.0


def test_shared_blank_goes_with_the_scratch_it_served(dev):
    """What a halo flow reserves is its output's bytes, once; the blank
    is dropped beside the flows it stood for, and the next job's first
    halo makes a new one."""
    arena = Arena((2, 8), np.float32)

    def stage():
        bound = arena.get_copy(backed=False)
        bound.flags |= FLAG_SCRATCH
        return dev._stage_in(bound, ACCESS_WRITE, False, False)

    a, b = stage(), stage()
    blank = a.payload
    assert dev._bytes_used == 2 * 64 and len(dev._blanks) == 1
    dev.discard_scratch()
    assert dev._blanks == {} and dev._bytes_used == 0
    assert a.payload is None and b.payload is None
    c = stage()
    assert c.payload is not blank and dev._bytes_used == 64
    dev.fini()
    assert dev._blanks == {}


def test_snapshot_counters_reach_the_metrics_scrape():
    """A whole-tile fan-out (one writer, two readers of a producer's
    output) is what the counters are for: they count its private copies
    and the scrape carries them."""
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    x, V = _grid(2, 8, 16, seed=2)
    p = PTG("fan", NT=2)
    p.task("P", i=Range(0, 1)).affinity(lambda i, V=V: V(i)) \
        .flow("X", "RW", IN(DATA(lambda i, V=V: V(i))),
              OUT(TASK("W", "X", lambda i: dict(i=i))),
              OUT(TASK("R", "X", lambda i: dict(i=i)))) \
        .body(lambda X: X + 1.0, device="tpu")
    p.task("W", i=Range(0, 1)).affinity(lambda i, V=V: V(i)) \
        .flow("X", "RW", IN(TASK("P", "X", lambda i: dict(i=i))),
              OUT(DATA(lambda i, V=V: V(i)))) \
        .body(lambda X: X * 2.0, device="tpu")
    p.task("R", i=Range(0, 1)).affinity(lambda i, V=V: V(i)) \
        .flow("X", "READ", IN(TASK("P", "X", lambda i: dict(i=i)))) \
        .body(lambda X: None)
    params.set("device_max", 1)
    try:
        with Context(nb_cores=2) as ctx:
            ctx.add_taskpool(p.build())
            ctx.wait()
            st = ctx.device_registry.accelerators[0].stats.as_dict()
            samples = {s["n"]: s["v"]
                       for s in ctx.metrics._collect_devices()
                       if s["n"].startswith("parsec_device_snapshot")}
    finally:
        params.unset("device_max")
    np.testing.assert_allclose(V.to_array(), (x + 1.0) * 2.0)
    assert st["snapshot_flows"] == 2 and st["snapshot_bytes"] == 2 * 8 * 16 * 4
    assert samples == {
        "parsec_device_snapshot_flows_total": st["snapshot_flows"],
        "parsec_device_snapshot_bytes_total": st["snapshot_bytes"]}
