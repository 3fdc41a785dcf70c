"""The harness end to end at a tiny size on CPU devices, called as
functions (the look for a chip skipped), sound and with the timed path
broken underneath; and the command itself where it must refuse to run.

The tiny cells take the real configurations' files, limits included, with
float32 storage (XLA's CPU backend has no bf16 x bf16 -> f32 dot) and one
warm job.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmark import harness

ROOT = harness.ROOT
TRAFFIC = {"potrf": {"n": 384, "mb": 64},
           "gemm": {"m": 192, "n": 192, "k": 256, "mb": 64}}
CONFIG = {"potrf": "dplasma_potrf_bf16", "gemm": "dplasma_gemm_bf16"}


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny(app, chips=1, factory=None, trace=False, seed=2 ** 31 + 5,
         name=None):
    path = os.path.join(ROOT, "benchmark", "configs", CONFIG[app] + ".json")
    if not os.path.exists(path):
        pytest.skip(f"{CONFIG[app]} is not a configuration of this benchmark")
    with open(path) as f:
        config = {**json.load(f), "storage": "float32", "warm_jobs": 1,
                  "distribute": chips > 1}
    # the cell's name picks its metrics: take a real cell of the config
    name = name or next(c["name"] for c in spec()["workloads"]
                        if c["config"] == CONFIG[app])
    cell = {"name": name, "chips": chips}
    return harness.run_cell(spec(), cell, config, TRAFFIC[app], seed, 0.3,
                            trace, time.perf_counter(), app_factory=factory)


@pytest.mark.parametrize("app, chips", [("potrf", 1), ("potrf", 4),
                                        ("gemm", 1)])
def test_sound_run_is_correct_and_reports_its_metrics(app, chips):
    r = tiny(app, chips)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"tflops_per_chip", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "compared"             # comes last in the line
    assert all(c["value"] <= c["limit"] for c in r["compared"].values())
    assert r["device"]["platform"] == "cpu"      # named, never passed off
    json.dumps(r)


@pytest.mark.parametrize("cell", [c["name"] for c in spec()["workloads"]])
def test_every_cell_reports_the_end_to_end_metrics_that_list_it(cell):
    """The data alone decides: whatever ``end_to_end`` entry lists the
    cell (or lists none) is read by the file its name starts with."""
    s = spec()
    c = next(c for c in s["workloads"] if c["name"] == cell)
    app = next(a for a, cfg in CONFIG.items() if c["config"].startswith(cfg))
    want = {m["name"] for m in s["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert "setup_s" in want and len(want) >= 2
    r = tiny(app, c["chips"], name=cell)
    assert r["correct"] is True and set(r["metrics"]) == want


def test_traced_run_reports_per_layer_metrics_and_leaves_out_the_unreadable():
    r = tiny("potrf", trace=True)
    assert r["correct"] is True
    # host-side metrics are there; the CPU's trace has no TPU plane, so
    # every trace-read metric is left out rather than reported as 0
    assert {"stage_share_pct", "tasks_per_s", "compiles_in_window"} \
        <= set(r["metrics"])
    assert not {"kernel_roofline_pct", "device_idle_pct",
                "tasks_per_launch", "tflops_per_chip"} & set(r["metrics"])
    assert r["device"]["window_s"] > 0 and "breakdown" in r


def _unchanged(app):
    """A job whose taskpool runs and leaves every tile as it was staged."""
    import importlib
    Job = importlib.import_module(f"benchmark.apps.{app}").Job

    class Unchanged(Job):
        def pool(self):
            from parsec_tpu.dsl.ptg.api import PTG, Range
            p = PTG("noop", N=4)
            p.task("E", i=Range(0, 3)).flow("x", "CTL").body(lambda: None)
            return p.build()
    return Unchanged


def _updates_left_out(monkeypatch):
    from parsec_tpu.apps import potrf
    monkeypatch.setitem(potrf._kernels, ("gemm", None), lambda C, L, R: C)


def _answer_altered(monkeypatch):
    import jax.numpy as jnp
    from parsec_tpu.apps import potrf
    monkeypatch.setitem(
        potrf._kernels, ("trsm", None),
        lambda W, C: (1.1 * jnp.matmul(C, W.T)).astype(C.dtype))


def _half_product(monkeypatch):
    from parsec_tpu.apps import gemm
    import jax.numpy as jnp
    monkeypatch.setitem(
        gemm._kernels, (1.0, None),
        lambda Ai, Bi, Ci: Ci + 0.5 * jnp.matmul(Ai, Bi))


def _exchange_left_out(monkeypatch):
    """Broadcast replicas arrive empty on every chip but the sender's."""
    import jax.numpy as jnp
    from parsec_tpu.comm.ici import IciEngine
    real = IciEngine.bcast

    def bcast(self, payload, dst_spaces):
        here = next(iter(payload.devices()))
        return {s: (v if here in v.devices() else jnp.zeros_like(v))
                for s, v in real(self, payload, dst_spaces).items()}
    monkeypatch.setattr(IciEngine, "bcast", bcast)


@pytest.mark.parametrize("app, chips, fault", [
    ("potrf", 1, "unchanged"), ("potrf", 1, _updates_left_out),
    ("potrf", 1, _answer_altered), ("potrf", 4, _exchange_left_out),
    ("gemm", 1, "unchanged"), ("gemm", 1, _half_product)])
def test_broken_timed_path_comes_out_not_correct(monkeypatch, app, chips,
                                                 fault):
    factory = None
    if fault == "unchanged":
        factory = _unchanged(app)
    else:
        fault(monkeypatch)
    r = tiny(app, chips, factory=factory)
    assert r["correct"] is False
    over = [k for k, c in r["compared"].items() if not c["value"] <= c["limit"]]
    assert over and "device_faults" not in over


def _command(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "x"}
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    cell = spec()["workloads"][0]["name"]
    p = _command(ROOT, "--workload", cell, "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "platform='cpu'" in p.stderr and "Nothing was measured" in p.stderr


def test_without_the_program_the_command_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = spec()["workloads"][0]["name"]
    p = _command(tmp_path, "--workload", cell, "--seed", "1", "--seconds",
                 "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
