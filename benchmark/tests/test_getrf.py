"""The LU cell's app, reference, control and readers at a size a test run
can hold (CPU devices), under the configuration's real limit."""

import json
import os
import time

import numpy as np
import pytest

import test_harness
from benchmark import harness
from benchmark.apps import getrf
from benchmark.metrics import (chain_heads_forced_pct,
                               lu_panel_device_share_pct,
                               lu_panel_roofline_pct)
from benchmark.reference import getrf as reference

CELL = "getrf.n67584_mb6144"
TRAFFIC = {"n": 768, "mb": 128}
# test_harness.py's tables know the apps of PR 24; its tests that walk
# every cell of BENCHMARK.json find this cell's app and tiny size here
test_harness.CONFIG["getrf"] = "dplasma_getrf_nopiv_bf16"
test_harness.TRAFFIC["getrf"] = TRAFFIC


def config():
    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           "dplasma_getrf_nopiv_bf16.json")) as f:
        return json.load(f)


def tiny(factory=None, trace=False, seed=2 ** 31 + 13, storage="float32"):
    cfg = {**config(), "storage": storage, "warm_jobs": 1}
    return harness.run_cell(test_harness.spec(), {"name": CELL, "chips": 1},
                            cfg, TRAFFIC, seed, 0.3, trace,
                            time.perf_counter(), app_factory=factory)


def test_the_configuration_is_the_deployment_named():
    cfg, t = config(), harness.load_cell(CELL)[3]
    assert (t["n"], t["mb"]) == (67584, 6144)
    assert cfg["app"] == "getrf" and cfg["storage"] == "bfloat16"
    assert cfg["mca"] == {"device_fuse": 8, "device_runahead": 48,
                          "device_inflight_depth": 32}
    assert cfg["reduced"] == [] and set(cfg["limits"]) == {"offdiag_resid"}
    assert set(cfg["limits_why"]) == set(cfg["limits"])
    assert "testing_dgetrf_nopiv" in cfg["source"]
    entry = next(c for c in test_harness.spec()["configs"]
                 if c["name"] == "dplasma_getrf_nopiv_bf16")
    assert entry["reduced"] == [] and entry["file"].endswith(
        "dplasma_getrf_nopiv_bf16.json")


@pytest.mark.parametrize("nt, want", [(1, 1), (2, 5), (3, 14), (11, 506)])
def test_flop_and_task_counts(nt, want):
    # nt GETRF + nt(nt-1)/2 TRSMR and TRSMC each + sum of squares GEMM
    by_class = nt + nt * (nt - 1) + sum(k * k for k in range(nt))
    assert reference.tasks(nt) == want == by_class
    mb = 6144
    n = nt * mb
    assert reference.flops(n) == pytest.approx(2 * n ** 3 / 3)
    per_class = (nt * reference.class_flops("GETRF", mb)
                 + nt * (nt - 1) * reference.class_flops("TRSM", mb)
                 + sum(k * k for k in range(nt))
                 * reference.class_flops("GEMM", mb))
    assert per_class == pytest.approx(reference.flops(n))
    assert reference.panel_flops(n, mb) == pytest.approx(
        per_class - sum(k * k for k in range(nt))
        * reference.class_flops("GEMM", mb))


def test_the_cells_flop_shares():
    """GEMM waves 86.8% of the flop, the panels' TRSMs 12.4%, GETRF 0.8%
    (ISSUE 40's sizing)."""
    n, mb = 67584, 6144
    total = reference.flops(n)
    getrf_share = 11 * reference.class_flops("GETRF", mb) / total
    trsm_share = 110 * reference.class_flops("TRSM", mb) / total
    assert round(100 * getrf_share, 1) == 0.8
    assert round(100 * trsm_share, 1) == 12.4
    assert round(100 * (1 - getrf_share - trsm_share), 1) == 86.8


def _exact(nt, mb, seed):
    """An operand built from a known unit lower L and upper U: its tiles
    and the packed factor's, float64 products rounded once to float32."""
    rng = np.random.default_rng(seed)
    n = nt * mb
    L = np.tril(rng.standard_normal((n, n)) * 0.05, -1) + np.eye(n)
    U = np.triu(rng.standard_normal((n, n))) + 4 * n ** 0.5 * np.eye(n)
    A = (L @ U).astype(np.float32)
    F = (np.tril(L, -1) + U).astype(np.float32)
    cut = lambda M: {(i, j): M[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb]
                     for i in range(nt) for j in range(nt)}
    return cut(A), cut(F)


def test_factor_check_reads_zero_on_an_exact_factor_and_sees_one_tile():
    nt, mb = 4, 32
    A, F = _exact(nt, mb, 3)
    r = reference.factor_check(nt, mb, lambda i, j: F[(i, j)],
                               lambda i, j: A[(i, j)], 7)
    assert r["offdiag_resid"] < 1e-5 and r["diag_resid"] < 1e-6
    limit = config()["limits"]["offdiag_resid"]
    for tile in ((2, 1), (1, 3), (2, 2)):
        bad = dict(F)
        bad[tile] = F[tile] * 1.2                  # one tile 20% off
        r = reference.factor_check(nt, mb, lambda i, j: bad[(i, j)],
                                   lambda i, j: A[(i, j)], 7)
        assert r["offdiag_resid"] > limit and r["offdiag_row"] == tile[0]


def test_plain_lu_is_the_factor():
    import jax.numpy as jnp
    nt, mb = 3, 64
    A, F = _exact(nt, mb, 4)
    got = reference.plain_lu({k: jnp.asarray(v) for k, v in A.items()}, nt,
                             reference.store_as(np.float32))
    scale = max(np.abs(v).max() for v in F.values())
    assert max(np.abs(np.asarray(got[k]) - F[k]).max() for k in F) \
        < 1e-5 * scale


def test_sound_run_is_correct_in_both_storages():
    for storage in ("float32", "bfloat16"):
        r = tiny(storage=storage)
        assert r["correct"] is True and r["failed"] == 0
        assert set(r["compared"]) == {"offdiag_resid", "device_faults"}
        assert set(r["metrics"]) == {"setup_s", "tflops_per_chip"}


def test_traced_run_reports_the_counters_and_leaves_out_the_trace_read():
    from parsec_tpu.apps import getrf as program
    program._kernels.clear()                # the chain programs anew
    r = tiny(trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    assert m["chain_heads_forced_pct"]["value"] == 0.0
    assert m["chain_programs"]["value"] >= 1
    # the chip sets the pace (two sets of six spread under 0.2%): the cell
    # reports the 1%-bound rate and that rate's per-layer metrics
    assert "compiles_in_window" in m and not any(
        k.endswith(".host_paced") for k in m)
    assert "lu_panel_device_share_pct" not in m     # no TPU plane on a CPU
    assert "lu_panel_roofline_pct" not in m


def test_the_first_warm_job_runs_the_heads_plain_program():
    """The first pool a Job hands out runs GETRF unheld, so the warm jobs
    build ``jit_parsec_GETRF``, the program a head forced out alone goes
    through; every later pool holds GETRF for its row panel."""
    job = getrf.Job(config(), TRAFFIC, None, 1)
    job.setup()
    first, later = job.pool(), job.pool()
    assert "fuse_chain" not in first.task_classes["GETRF"].properties
    assert later.task_classes["GETRF"].properties["fuse_chain"] == \
        ("WL", "TRSMR")
    assert config()["warm_jobs"] == 3          # the plain one + two held


def _unchanged():
    class Unchanged(getrf.Job):
        def pool(self):
            from parsec_tpu.dsl.ptg.api import PTG, Range
            p = PTG("noop", N=4)
            p.task("E", i=Range(0, 3)).flow("x", "CTL").body(lambda: None)
            return p.build()
    return Unchanged


def _updates_left_out(mp):
    from parsec_tpu.apps import getrf as program
    mp.setitem(program._kernels, ("gemm", None), lambda C, L, R: C)


def _row_panel_altered(mp):
    import jax.numpy as jnp
    from parsec_tpu.apps import getrf as program
    mp.setitem(program._kernels, ("trsmr", None),
               lambda W, C: (1.1 * jnp.matmul(W, C)).astype(C.dtype))


@pytest.mark.parametrize("fault", ["unchanged", _updates_left_out,
                                   _row_panel_altered])
def test_broken_timed_path_comes_out_not_correct(monkeypatch, fault):
    factory = None
    if fault == "unchanged":
        factory = _unchanged()
    else:
        fault(monkeypatch)
    r = tiny(factory=factory)
    assert r["correct"] is False
    over = [k for k, c in r["compared"].items()
            if not c["value"] <= c["limit"]]
    assert over == ["offdiag_resid"]


def test_a_program_without_the_taskpool_fails_at_set_up(monkeypatch):
    """The parent commit, given these files, must end the run at once:
    no tile is made and nothing compiles."""
    import builtins
    real = builtins.__import__

    def no_getrf(name, *args, **kw):
        if name == "parsec_tpu.apps.getrf":
            raise ModuleNotFoundError("No module named 'parsec_tpu.apps.getrf'")
        return real(name, *args, **kw)
    monkeypatch.setattr(builtins, "__import__", no_getrf)
    t = time.perf_counter()
    with pytest.raises(ModuleNotFoundError):
        tiny()
    assert time.perf_counter() - t < 30


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7, 3100000019])
def test_control_fails_the_limit(seed):
    """The plain reference reads under the limit at the configuration's
    storage and over it one storage precision lower."""
    cfg = config()
    limit = cfg["limits"]["offdiag_resid"]
    sound = getrf.control(cfg, TRAFFIC, seed, "config")["offdiag_resid"]
    lower = getrf.control(cfg, TRAFFIC, seed, "fp8")["offdiag_resid"]
    assert sound < limit < lower
    assert lower >= 10 * sound


# ---- the three readers, on synthetic runs ----

def _run(stats_per_chip, modules=None, jobs=2, app="getrf"):
    tr = None
    if modules is not None:
        tr = {"devices": {"/device:TPU:0": {"modules": modules, "ops": []}},
              "host": [["bench:window", 0, 10_000]]}
    return {"devices": [{"stats": s} for s in stats_per_chip], "trace": tr,
            "jobs": [(0.0, 1.0)] * jobs, "config": {"app": app},
            "traffic": {"n": 67584, "mb": 6144},
            "device": {"kind": "TPU v5 lite"}}


MODS = [["jit_parsec_chain_GETRF__TRSMR_x8(1)", 0, 1000],
        ["jit_parsec_TRSMC_x8(2)", 1000, 500],
        ["jit_parsec_GETRFL(3)", 1500, 500],
        ["jit_parsec_GEMM_x8(4)", 2000, 6000],
        ["jit_parsec_GEMM_x2(5)", 9000, 5000],       # clipped to 1000
        ["jit_bench_stage_tile(6)", 9500, 100]]      # not the runtime's


def test_lu_panel_share_reader():
    assert lu_panel_device_share_pct.read(_run([{}], MODS)) == \
        pytest.approx(100.0 * 2000 / 9000)
    assert lu_panel_device_share_pct.read(_run([{}])) is None
    assert lu_panel_device_share_pct.read(_run([{}], [])) is None
    qr = [["jit_parsec_chain_GEQRT__TSQRT_x1(1)", 0, 1000]]
    assert lu_panel_device_share_pct.read(_run([{}], qr)) == 0.0


def test_lu_panel_roofline_reader():
    # two jobs' panel flop over 197 TF/s x 2000 ns of panel programs
    want = 100.0 * 2 * reference.panel_flops(67584, 6144) / (197e12 * 2e-6)
    assert lu_panel_roofline_pct.read(_run([{}], MODS)) == \
        pytest.approx(want)
    assert lu_panel_roofline_pct.read(_run([{}])) is None
    assert lu_panel_roofline_pct.read(_run([{}], MODS, app="potrf")) is None
    gemm_only = [m for m in MODS if "GEMM" in m[0]]
    assert lu_panel_roofline_pct.read(_run([{}], gemm_only)) is None


def test_heads_forced_reader():
    run = _run([{"held_tasks": 30, "heads_forced": 3},
                {"held_tasks": 10, "heads_forced": 1}])
    assert chain_heads_forced_pct.read(run) == pytest.approx(10.0)
    assert chain_heads_forced_pct.read(
        _run([{"held_tasks": 10, "heads_forced": 0}])) == 0.0
    # the parent has no such counter; nothing held reads nothing
    assert chain_heads_forced_pct.read(_run([{"held_tasks": 10}])) is None
    assert chain_heads_forced_pct.read(
        _run([{"held_tasks": 0, "heads_forced": 0}])) is None
