"""The QR cell's app, reference, control and readers at a size a test
run can hold (CPU devices, float32 storage: XLA's CPU backend has no
bf16 x bf16 -> f32 dot), under the configuration's real limits."""

import json
import os
import time

import pytest

import test_harness
from benchmark import harness
from benchmark.apps import geqrf
from benchmark.metrics import (chain_links_per_launch, chain_programs,
                               panel_device_share_pct)
from benchmark.reference import geqrf as reference

CELL = "geqrf.n49152_mb6144_ib512"
TRAFFIC = {"n": 256, "mb": 64, "ib": 16}
# test_harness.py's tables know the apps of PR 24; its tests that walk
# every cell of BENCHMARK.json find this cell's app and tiny size here
test_harness.CONFIG["geqrf"] = "dplasma_geqrf_bf16"
test_harness.TRAFFIC["geqrf"] = TRAFFIC


def config():
    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           "dplasma_geqrf_bf16.json")) as f:
        return json.load(f)


def tiny(factory=None, trace=False, seed=2 ** 31 + 11):
    cfg = {**config(), "storage": "float32", "warm_jobs": 1}
    return harness.run_cell(test_harness.spec(), {"name": CELL, "chips": 1},
                            cfg, TRAFFIC, seed, 0.3, trace,
                            time.perf_counter(), app_factory=factory)


@pytest.mark.parametrize("nt, want", [(2, 5), (3, 14), (8, 204)])
def test_flop_and_task_counts(nt, want):
    # nt GEQRT, nt(nt-1)/2 UNMQR and TSQRT each, sum of squares TSMQR
    by_class = nt + nt * (nt - 1) + sum(k * k for k in range(nt))
    assert reference.tasks(nt) == want == by_class
    n = nt * 6144
    assert reference.flops(n) == pytest.approx(2 * n ** 3 - 2 * n ** 3 / 3)


def test_sound_run_is_correct_and_carries_no_panel_switch():
    assert "device_fuse_panel" not in config()["mca"]
    r = tiny()
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["compared"]) == {"factor_resid", "below_diag_max",
                                  "device_faults"}
    assert set(r["metrics"]) >= {"setup_s"} and len(r["metrics"]) == 2


def test_traced_run_reports_the_counters_and_leaves_out_the_trace_read():
    r = tiny(trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    assert m["chain_links_per_launch"]["value"] == 1.0
    assert m["chain_programs"]["value"] in (0, 2)   # 0: built by a test before
    assert "panel_device_share_pct" not in m        # no TPU plane on a CPU


def _unchanged():
    class Unchanged(geqrf.Job):
        def pool(self):
            from parsec_tpu.dsl.ptg.api import PTG, Range
            p = PTG("noop", N=4)
            p.task("E", i=Range(0, 3)).flow("x", "CTL").body(lambda: None)
            return p.build()
    return Unchanged


def _kernel(mp, name, fn):
    """Put ``fn`` in the place of the QR app's memoized device kernel
    whose key starts with ``name``."""
    from parsec_tpu.apps import qr
    from parsec_tpu.apps.qr import effective_ib
    ib = effective_ib(TRAFFIC["mb"])
    key = {"tsmqr": "tsmqr", "tsqrt": ("tsqrt", ib, "default")}[name]
    mp.setitem(qr._kernels, key, fn)


def _tsmqr_left_out(mp):
    _kernel(mp, "tsmqr", lambda Q, C1, C2: {"C1": C1, "C2": C2})


def _tsqrt_r_altered(mp):
    from parsec_tpu.apps import qr
    real = qr._mk_tsqrt(TRAFFIC["ib"])

    def fn(T, B, Q):
        out = real(T, B, Q)
        return {**out, "T": 1.1 * out["T"]}
    _kernel(mp, "tsqrt", fn)


def _b_not_zeroed(mp):
    from parsec_tpu.apps import qr
    real = qr._mk_tsqrt(TRAFFIC["ib"])

    def fn(T, B, Q):
        return {**real(T, B, Q), "B": B}
    _kernel(mp, "tsqrt", fn)


@pytest.mark.parametrize("fault, number", [
    ("unchanged", "below_diag_max"), (_tsmqr_left_out, "factor_resid"),
    (_tsqrt_r_altered, "factor_resid"), (_b_not_zeroed, "below_diag_max")])
def test_broken_timed_path_comes_out_not_correct(monkeypatch, fault, number):
    factory = None
    if fault == "unchanged":
        factory = _unchanged()
    else:
        from parsec_tpu.utils.mca import params
        params.set("qr_ib", TRAFFIC["ib"])       # the key the job will ask for
        try:
            fault(monkeypatch)
        finally:
            params.unset("qr_ib")
    r = tiny(factory=factory)
    assert r["correct"] is False
    over = [k for k, c in r["compared"].items()
            if not c["value"] <= c["limit"]]
    assert number in over and "device_faults" not in over


def test_job_refuses_a_program_that_does_not_count_chain_programs(
        monkeypatch):
    """The parent of PR 31, given these files, must fail at set-up and
    not compile for an hour."""
    from parsec_tpu.devices.device import DeviceStats
    real = DeviceStats.as_dict
    monkeypatch.setattr(DeviceStats, "as_dict", lambda self: {
        k: v for k, v in real(self).items() if k != "chain_programs"})
    with pytest.raises(RuntimeError, match="chain_programs"):
        tiny()


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7, 3100000019])
def test_control_fails_the_limit(seed):
    """The plain Householder reference reads under the limit at the
    configuration's storage and over it one storage precision lower;
    with its panel construction alone rounded to bfloat16's eight bits
    it reads over the sound one."""
    cfg = config()
    limit = cfg["limits"]["factor_resid"]
    traffic = {"n": 512, "mb": 128, "ib": 32}
    sound = geqrf.control(cfg, traffic, seed, "config")
    lower = geqrf.control(cfg, traffic, seed, "fp8")
    panel = geqrf.control({**cfg, "control_panel": "bfloat16"}, traffic,
                          seed, "config")
    assert sound["factor_resid"] < limit < lower["factor_resid"]
    assert lower["factor_resid"] >= 3 * sound["factor_resid"]
    assert panel["factor_resid"] >= 2 * sound["factor_resid"]
    assert sound["below_diag_max"] == 0.0 == lower["below_diag_max"]


# ---- the three readers, on synthetic runs ----

def _run(stats_per_chip, modules=None):
    tr = None
    if modules is not None:
        tr = {"devices": {"/device:TPU:0": {"modules": modules, "ops": []}},
              "host": [["bench:window", 0, 10_000]]}
    return {"devices": [{"stats": s} for s in stats_per_chip], "trace": tr}


def test_chain_counter_readers():
    run = _run([{"held_tasks": 32, "chained_launches": 16,
                 "chain_programs": 2},
                {"held_tasks": 4, "chained_launches": 2,
                 "chain_programs": 1}])
    assert chain_links_per_launch.read(run) == 2.0
    assert chain_programs.read(run) == 3
    # the parent of PR 31 has no such counter; potrf on four chips holds
    # no head: both read nothing, neither raises
    old = _run([{"held_tasks": 0, "chained_launches": 0}])
    assert chain_links_per_launch.read(old) is None
    assert chain_programs.read(old) is None
    assert chain_programs.read(_run([{"chain_programs": 0}])) == 0


def test_panel_share_reader():
    mods = [["jit_parsec_GEQRT(1)", 0, 1000],
            ["jit_parsec_chain_TSQRT__TSQRT_x1(2)", 1000, 2000],
            ["jit_parsec_TSMQR_x8(3)", 3000, 6000],
            ["jit_parsec_UNMQR_x4(4)", 9000, 5000],      # clipped to 1000
            ["jit_bench_stage_tile(5)", 9500, 100]]      # not the runtime's
    assert panel_device_share_pct.read(_run([{}], mods)) == \
        pytest.approx(100.0 * 3000 / 10000)
    assert panel_device_share_pct.read(_run([{}])) is None
    assert panel_device_share_pct.read(_run([{}], [])) is None
    potrf = [["jit_parsec_chain_POTRF__TRSM_x8(1)", 0, 1000]]
    assert panel_device_share_pct.read(_run([{}], potrf)) == 0.0
