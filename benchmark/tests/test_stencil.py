"""The stencil cell's app, reference, control and readers at a size a
test run can hold (CPU devices), under the configuration's real limits."""

import json
import os
import time

import numpy as np
import pytest

import test_harness
from benchmark import harness
from benchmark.apps import stencil
from benchmark.metrics import (substrate_copy_kib_per_task,
                               substrate_device_share_pct,
                               sweep_hbm_roofline_pct)
from benchmark.reference import stencil as reference

CELL = "stencil.n196608_nb8192_mb4096_it48"
TRAFFIC = {"n": 512, "nb": 256, "mb": 128, "steps": 6}
# test_harness.py's tables know the apps of PR 24; its tests that walk
# every cell of BENCHMARK.json find this cell's app and tiny size here
test_harness.CONFIG["stencil"] = "parsec_stencil1d_f32"
test_harness.TRAFFIC["stencil"] = TRAFFIC


def config():
    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           "parsec_stencil1d_f32.json")) as f:
        return json.load(f)


def tiny(factory=None, trace=False, seed=2 ** 31 + 11):
    cfg = {**config(), "warm_jobs": 1}
    return harness.run_cell(test_harness.spec(), {"name": CELL, "chips": 1},
                            cfg, TRAFFIC, seed, 0.3, trace,
                            time.perf_counter(), app_factory=factory)


def test_the_configuration_is_the_deployment_named():
    cfg, t = config(), harness.load_cell(CELL)[3]
    assert (t["n"], t["nb"], t["mb"], t["steps"]) == (196608, 8192, 4096, 48)
    assert cfg["storage"] == "float32" and cfg["mca"] == {}
    assert cfg["reduced"] == [] and cfg["warm_jobs"] == 2
    assert set(cfg["limits"]) == set(stencil.NUMBERS) \
        == set(cfg["limits_why"])
    for word in ("testing_stencil_1D.c", "stencil_1D.jdf", "BASELINE.json"):
        assert word in cfg["source"]
    entry = next(c for c in test_harness.spec()["configs"]
                 if c["name"] == "parsec_stencil1d_f32")
    assert entry["reduced"] == [] and "testing_stencil_1D.c" in entry["source"]


def test_work_formulas():
    n, nb, steps = 196608, 8192, 48
    assert reference.tasks(48, steps) == 2304 + 48
    assert reference.flops(n, nb, steps) == pytest.approx(2.32e11, rel=2e-3)
    assert reference.bytes_moved(n, nb, steps) == 48 * 12 * 2 ** 30
    # at the roof: 0.755 s a job, 0.307 "TFLOP/s"
    roof_s = reference.bytes_moved(n, nb, steps) / 819e9
    assert roof_s == pytest.approx(0.755, rel=2e-3)
    assert reference.flops(n, nb, steps) / roof_s / 1e12 == \
        pytest.approx(0.307, rel=3e-3)


def test_lane_reference_is_the_dense_sweep():
    """The probe's per-lane reference against a dense circulant matrix
    applied ``steps`` times: lanes do not interact, rows wrap."""
    rng = np.random.default_rng(4)
    n, steps = 40, 7
    x = rng.standard_normal((n, 5))
    S = (np.eye(n) + np.roll(np.eye(n), 1, axis=0)
         + np.roll(np.eye(n), -1, axis=0)) / 3.0
    np.testing.assert_allclose(reference.lane_reference(x, steps),
                               np.linalg.matrix_power(S, steps) @ x,
                               atol=1e-14)
    lanes = reference.probe_lanes(7, 8192)
    assert lanes[0] == 0 and lanes[-1] == 8191 and 64 <= lanes.size <= 66
    assert np.array_equal(lanes, reference.probe_lanes(7, 8192))
    assert np.array_equal(reference.probe_lanes(3, 4), [0, 1, 2, 3])


def test_sound_run_is_correct_and_reports_its_metrics():
    r = tiny()
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["compared"]) == {"probe_max_err", "lane_sum_drift",
                                  "device_faults"}
    assert set(r["metrics"]) == {"tflops_per_chip.host_paced", "setup_s"}


def test_traced_run_reports_the_counter_and_leaves_out_the_trace_read():
    r = tiny(trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    # tiles born on the device, halos as outputs of the sweep: nothing
    # is copied (the host-born first staging does not exist here)
    assert m["substrate_copy_kib_per_task"]["value"] == 0.0
    assert "compiles_in_window.host_paced" in m
    assert "sweep_hbm_roofline_pct" not in m        # no TPU plane on a CPU
    assert "substrate_device_share_pct" not in m


def _unchanged():
    class Unchanged(stencil.Job):
        def pool(self):
            from parsec_tpu.dsl.ptg.api import PTG, Range
            p = PTG("noop", N=4)
            p.task("E", i=Range(0, 3)).flow("x", "CTL").body(lambda: None)
            return p.build()
    return Unchanged


def _sweep(mp, fn):
    """Put ``fn(real)`` in the place of the app's sweep; the memoized
    device kernel (and its traced programs) is built anew for it."""
    from parsec_tpu.apps import stencil as app
    mp.setattr(app, "_sweeps", fn(app._sweeps))
    mp.setattr(app, "_kernels", {})


def _wrong_side(real):
    return lambda xp, HL, C, HR, ns: real(xp, HR, C, HL, ns)


def _stale_halos(real):
    """Halos cut from the tile as it was before the sweep: a lane's sum
    is no longer conserved across the boundary."""
    def fn(xp, HL, C, HR, ns):
        return real(xp, HL, C, HR, ns)[0], C[:1] * 1.0, C[-1:] * 1.0
    return fn


def _bf16_arithmetic(real):
    def fn(xp, HL, C, HR, ns):
        import ml_dtypes
        return tuple(v.astype(ml_dtypes.bfloat16).astype(v.dtype)
                     for v in real(xp, HL, C, HR, ns))
    return fn


@pytest.mark.parametrize("fault, number", [
    ("unchanged", "probe_max_err"), (_wrong_side, "probe_max_err"),
    (_stale_halos, "lane_sum_drift"), (_bf16_arithmetic, "probe_max_err"),
    (_bf16_arithmetic, "lane_sum_drift")])
def test_broken_timed_path_comes_out_not_correct(monkeypatch, fault, number):
    factory = None
    if fault == "unchanged":
        factory = _unchanged()
    else:
        _sweep(monkeypatch, fault)
    r = tiny(factory=factory)
    assert r["correct"] is False
    over = [k for k, c in r["compared"].items()
            if not c["value"] <= c["limit"]]
    assert number in over and "device_faults" not in over


def test_a_wrong_tile_outside_the_probe_moves_a_lane_sum():
    """One tile, lanes the probe does not read: the whole-grid number
    sees it, the probe does not."""
    nt, mb, nb, steps, seed = 4, 32, 256, 5, 9
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((nt * mb, nb)).astype(np.float32)
    good = reference.lane_reference(x, steps).astype(np.float32)
    probe = set(reference.probe_lanes(seed, nb).tolist())
    lane = next(c for c in range(nb) if c not in probe)
    bad = good.copy()
    bad[2 * mb + 3, lane] += 0.25

    def tiles_of(a):
        return lambda i: a[i * mb:(i + 1) * mb]
    sound = reference.check(nt, nb, steps, tiles_of(good), tiles_of(x), seed)
    wrong = reference.check(nt, nb, steps, tiles_of(bad), tiles_of(x), seed)
    lim = config()["limits"]
    assert sound["probe_max_err"] < lim["probe_max_err"]
    assert sound["lane_sum_drift"] < lim["lane_sum_drift"]
    assert wrong["probe_max_err"] == sound["probe_max_err"]
    assert wrong["lane_sum_drift"] > lim["lane_sum_drift"]
    assert wrong["drift_worst_lane"] == lane


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7, 3100000019])
def test_control_fails_the_limit(monkeypatch, seed):
    """The plain whole-lane reference reads under both limits in the
    configuration's float32 and over both one precision lower (two
    blocks of 64 lanes here, as twelve of 512 at the cell's size)."""
    monkeypatch.setattr(stencil, "CONTROL_BLOCK_BYTES", 64 * 2048 * 4)
    cfg = config()
    traffic = {"n": 2048, "nb": 128, "mb": 256, "steps": 48}
    lim = cfg["limits"]
    sound = stencil.control(cfg, traffic, seed, "config")
    lower = stencil.control(cfg, traffic, seed, "fp8")
    for k in stencil.NUMBERS:
        assert sound[k] < lim[k] < lower[k], (k, sound[k], lower[k])
    assert lower["probe_max_err"] >= 100 * sound["probe_max_err"]


# ---- the three readers, on synthetic runs ----

def _run(stats_per_chip, modules=None, traffic=None, jobs=2):
    tr = None
    if modules is not None:
        tr = {"devices": {"/device:TPU:0": {"modules": modules, "ops": []}},
              "host": [["bench:window", 0, 10_000_000]]}
    return {"devices": [{"stats": s} for s in stats_per_chip], "trace": tr,
            "traffic": traffic or {"n": 1024, "nb": 1000, "mb": 256,
                                   "steps": 10},
            "jobs": [(0.0, 1.0)] * jobs, "device": {"kind": "TPU v5 lite"}}


MODS = [["jit_parsec_S_x8(1)", 0, 1_000_000],
        ["jit_parsec_S(2)", 1_000_000, 500_000],
        ["jit_parsec_INIT_x8(3)", 2_000_000, 300_000],
        ["jit_parsec_S_x4(4)", 9_900_000, 500_000],   # clipped to 100 000
        ["jit_parsec_SYRK_x8(5)", 3_000_000, 100_000],  # not a sweep
        ["jit_bench_stage_tile(6)", 4_000_000, 700_000]]  # not the runtime's


def test_roofline_reader():
    # 2 jobs x 8 B x 1024 x 1000 x 10 over 819e9 B/s x 1.6 ms of S
    want = 100.0 * 2 * 8 * 1024 * 1000 * 10 / (819e9 * 1.6e-3)
    assert sweep_hbm_roofline_pct.read(_run([{}], MODS)) == \
        pytest.approx(want)
    assert sweep_hbm_roofline_pct.read(_run([{}])) is None      # no trace
    assert sweep_hbm_roofline_pct.read(_run([{}], [])) is None
    potrf = [["jit_parsec_chain_POTRF__TRSM_x8(1)", 0, 1000],
             ["jit_parsec_SYRK_x8(2)", 1000, 1000]]
    assert sweep_hbm_roofline_pct.read(_run([{}], potrf)) is None
    # a run of another app: its traffic has no lanes and no steps
    assert sweep_hbm_roofline_pct.read(
        _run([{}], MODS, traffic={"n": 64, "mb": 8})) is None
    assert sweep_hbm_roofline_pct.read(_run([{}], MODS, jobs=0)) is None


def test_substrate_share_reader():
    assert substrate_device_share_pct.read(_run([{}], MODS)) == \
        pytest.approx(100.0 * 0.4 / 2.0)
    assert substrate_device_share_pct.read(_run([{}])) is None
    assert substrate_device_share_pct.read(_run([{}], [])) is None
    potrf = [["jit_parsec_SYRK_x8(2)", 1000, 1000]]
    assert substrate_device_share_pct.read(_run([{}], potrf)) is None


def test_copy_counter_reader():
    # the program counts a snapshot in snapshot_bytes AND in bytes_in:
    # 3 MiB of snapshots beside 1 MiB staged from the host is 4 MiB
    run = _run([{"snapshot_bytes": 3 * 2 ** 20, "bytes_in": 4 * 2 ** 20,
                 "executed_tasks": 30, "held_tasks": 2},
                {"snapshot_bytes": 0, "bytes_in": 0, "executed_tasks": 32,
                 "held_tasks": 0}])
    assert substrate_copy_kib_per_task.read(run) == 4 * 1024 / 64
    zero = _run([{"snapshot_bytes": 0, "bytes_in": 0, "executed_tasks": 5}])
    assert substrate_copy_kib_per_task.read(zero) == 0.0
    # a program that reports no bytes_in, a device that ran nothing, no
    # device at all: each reads nothing, none raises
    old = _run([{"executed_tasks": 5, "held_tasks": 0}])
    assert substrate_copy_kib_per_task.read(old) is None
    idle = _run([{"snapshot_bytes": 0, "bytes_in": 0, "executed_tasks": 0}])
    assert substrate_copy_kib_per_task.read(idle) is None
    assert substrate_copy_kib_per_task.read(_run([])) is None


def test_one_snapshot_of_a_tile_reads_the_tile_once():
    """The device module's own counters after ONE copy-on-write copy of
    an N-byte payload, one task: N / 1024 KiB, not twice that."""
    import jax
    from parsec_tpu.data.data import (ACCESS_RW, FLAG_COW, Coherency,
                                      Data)
    from parsec_tpu.devices.xla import XlaDevice
    n = 4096
    dev = XlaDevice(jax.devices()[0])
    dev.space = 1
    try:
        bound = Data(nb_elts=n).create_copy(
            0, np.ones(n // 4, np.float32), Coherency.SHARED, 1)
        bound.flags |= FLAG_COW
        dev._stage_in(bound, ACCESS_RW)
        st = dev.stats.as_dict()
    finally:
        dev.fini()
    assert st["snapshot_flows"] == 1 and st["snapshot_bytes"] == n
    run = _run([{**st, "executed_tasks": 1}])
    assert substrate_copy_kib_per_task.read(run) == n / 1024
