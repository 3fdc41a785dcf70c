"""The DTD Cholesky cell's app, reference and readers at a size a test
run can hold (CPU devices, float32 storage: XLA's CPU backend has no
bf16 x bf16 -> f32 dot), under the configuration's real limits."""

import json
import os
import sys
import time

if __name__ == "__main__":      # run as a script: no conftest put the
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))           # checkout on the path

import pytest

import test_harness
from benchmark import harness
from benchmark.apps import potrf_dtd
from benchmark.metrics import insert_us_per_task, insert_window_wait_pct
from benchmark.reference import potrf_dtd as reference

CELL = "potrf_dtd.n65536_mb2048"
CONFIG = "dplasma_potrf_dtd_bf16"
TRAFFIC = {"n": 384, "mb": 64}
# test_harness.py's tables know the apps of PR 24; its tests that walk
# every cell of BENCHMARK.json find this cell's app and tiny size here
test_harness.CONFIG["potrf_dtd"] = CONFIG
test_harness.TRAFFIC["potrf_dtd"] = TRAFFIC


def config():
    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def tiny(factory=None, trace=False, seed=2 ** 31 + 33):
    cfg = {**config(), "storage": "float32", "warm_jobs": 1}
    return harness.run_cell(test_harness.spec(), {"name": CELL, "chips": 1},
                            cfg, TRAFFIC, seed, 0.3, trace,
                            time.perf_counter(), app_factory=factory)


def test_configuration_is_the_ptgs_but_for_the_front_end():
    """Same storage, operand, MCA settings, warm jobs and limit as cell
    3's configuration; the default insert window is what is measured."""
    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           "dplasma_potrf_bf16.json")) as f:
        ptg = json.load(f)
    dtd = config()
    for k in ("storage", "accumulate", "diag_over_sqrt_n", "mca",
              "warm_jobs", "limits"):
        assert dtd[k] == ptg[k], k
    assert not any(k.startswith("dtd_") for k in dtd["mca"])
    assert dtd["app"] == "potrf_dtd" and dtd["reduced"] == []
    s = test_harness.spec()
    cell = next(c for c in s["workloads"] if c["name"] == CELL)
    ptg_cell = next(c for c in s["workloads"]
                    if c["name"] == "potrf.n65536_mb2048")
    assert cell["traffic"] == ptg_cell["traffic"] and cell["chips"] == 1
    assert cell["config"] == CONFIG


def test_stream_is_the_dags_task_count():
    from benchmark import work
    for nt in (1, 2, 6, 32):
        assert reference.stream_tasks(nt) == work.potrf_tasks(nt) \
            == sum(1 for _ in reference.insert_stream(nt))
    assert reference.stream_tasks(32) == 5984
    assert reference.window_waits(5984, 2048, 1024) == 4


def test_sound_run_walks_through_the_harness():
    r = tiny()
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"tflops_per_chip.host_paced", "setup_s"}
    assert set(r["compared"]) == {"offdiag_resid", "device_faults"}
    assert r["compared"]["device_faults"]["value"] == 0.0
    json.dumps(r)


def test_traced_run_reports_the_host_side_and_leaves_out_the_trace_read():
    r = tiny(trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    assert {"stage_share_pct.host_paced", "tasks_per_s.host_paced",
            "compiles_in_window.host_paced"} <= set(m)
    # the CPU's trace has no TPU plane: nothing read from it is reported
    assert not {"kernel_roofline_pct.host_paced",
                "tasks_per_launch.host_paced"} & set(m)
    # the program's own spans are host spans: both readers find them
    assert m["insert_us_per_task"]["value"] > 0
    assert 0 <= m["insert_window_wait_pct"]["value"] < 100


def _unchanged():
    class Unchanged(potrf_dtd.Job):
        def pool(self):
            from parsec_tpu.dsl.dtd import DTDTaskpool
            return DTDTaskpool("noop", inserter=lambda tp: None)
    return Unchanged


def _fresh_classes(mp):
    """The process's DTD classes hold the kernels they were made with."""
    from parsec_tpu.apps import potrf
    mp.setattr(potrf, "_dtd_classes", {})


def _updates_left_out(mp):
    from parsec_tpu.apps import potrf
    _fresh_classes(mp)
    mp.setitem(potrf._kernels, ("gemm", None), lambda C, L, R: C)


def _answer_altered(mp):
    import jax.numpy as jnp
    from parsec_tpu.apps import potrf
    _fresh_classes(mp)
    mp.setitem(potrf._kernels, ("trsm", None),
               lambda W, C: (1.1 * jnp.matmul(C, W.T)).astype(C.dtype))


def plant_written_tile_declared_input(setattr_):
    """The fault only DTD can commit: GEMM declares the tile it writes
    INPUT (class and inserts alike, as a caller who mistypes the mode
    would), so the discovery orders nothing after it and the runtime
    takes no result from it.  ``setattr_(obj, name, value)`` plants it
    (a test's ``monkeypatch.setattr``, or plain ``setattr`` in a
    process that runs one cell: ``--plant`` below)."""
    from parsec_tpu.apps import potrf
    from parsec_tpu.dsl.dtd import INPUT
    real = potrf._potrf_dtd_classes

    def classes(device, precision, mb):
        got = dict(real(device, precision, mb))
        g = got["GEMM"]
        bad = type(g)(g.name, g.arg_names, [INPUT, INPUT, INPUT],
                      g.properties)
        for dev, fn in g.chores:
            bad.add_chore(dev, fn)
        got["GEMM"] = bad
        return got
    setattr_(potrf, "_potrf_dtd_classes", classes)


@pytest.mark.parametrize("fault", [
    "unchanged", _updates_left_out, _answer_altered,
    lambda mp: plant_written_tile_declared_input(mp.setattr)],
    ids=["unchanged", "updates_left_out", "answer_altered",
         "written_tile_declared_input"])
def test_broken_timed_path_comes_out_not_correct(monkeypatch, fault):
    factory = None
    if fault == "unchanged":
        factory = _unchanged()
    else:
        fault(monkeypatch)
    try:
        r = tiny(factory=factory)
    except RuntimeError as exc:
        # a fault that fails a WARM job never reaches the window: the
        # run ends with the task's error and prints no result
        assert "GEMM" in str(exc) and "failed" in str(exc)
        return
    assert r["correct"] is False
    over = [k for k, c in r["compared"].items()
            if not c["value"] <= c["limit"]]
    # a job that failed outright compares nothing; one that ran to its
    # end reads over the configuration's limit
    assert r["failed"] or (over and "device_faults" not in over)


def test_control_is_the_ptgs():
    from benchmark.apps import potrf
    assert potrf_dtd.control is potrf.control


# -- the two readers on a synthetic span set -------------------------------

def _trace(monkeypatch, threads):
    from benchmark import runtime_spans
    data = {"devices": {}, "threads": threads, "done": []}
    monkeypatch.setattr(runtime_spans, "load", lambda path=None: data)
    return {"trace": object()}


P = "parsec:dtd."


def test_readers_on_a_synthetic_span_set(monkeypatch):
    ms = 1_000_000
    run = _trace(monkeypatch, [
        [["bench:window", 100 * ms, 1000 * ms, {}],
         # cut by the window's opening: left out, with its wait
         [P + "insert", 50 * ms, 100 * ms, {"n": 999}],
         [P + "window_wait", 60 * ms, 20 * ms, {"inflight": 2048}],
         # 300 ms for 1000 tasks, 100 of them blocked on the window
         [P + "insert", 200 * ms, 300 * ms, {"pool": 7, "n": 1000}],
         [P + "window_wait", 250 * ms, 60 * ms, {"inflight": 2048}],
         [P + "window_wait", 400 * ms, 40 * ms, {"inflight": 2048}],
         [P + "flush", 499 * ms, 1 * ms, {"pool": 7}],
         # 100 ms for 1000 tasks, never blocked
         [P + "insert", 700 * ms, 100 * ms, {"pool": 8, "n": 1000}]],
        [["parsec:fin.release", 300 * ms, 1 * ms, {}]]])
    assert insert_us_per_task.read(run) == pytest.approx(
        (300 + 100 - 100) * 1e3 / 2000)
    assert insert_window_wait_pct.read(run) == pytest.approx(10.0)


def test_readers_return_nothing_where_there_is_nothing_to_read(monkeypatch):
    assert insert_us_per_task.read({"trace": None}) is None
    assert insert_window_wait_pct.read({"trace": None}) is None
    # a program without the spans (the parent; a PTG cell)
    run = _trace(monkeypatch, [[["bench:window", 0, 10, {}],
                                ["parsec:mgr.launch", 1, 2, {}]]])
    assert insert_us_per_task.read(run) is None
    assert insert_window_wait_pct.read(run) is None
    # no window span at all
    run = _trace(monkeypatch, [[["parsec:dtd.insert", 1, 2, {"n": 3}]]])
    assert insert_us_per_task.read(run) is None
    assert insert_window_wait_pct.read(run) is None


if __name__ == "__main__":
    # one run of the cell with the DTD-only fault planted, on whatever
    # device JAX reports (the builder's planted fault on the chip):
    #   python3 benchmark/tests/test_potrf_dtd.py --plant --seed 7
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--plant", action="store_true")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    a = ap.parse_args()
    harness.place_compile_cache()
    spec_, cell, cfg, traffic = harness.load_cell(CELL)
    harness.require_chips(1)
    if a.plant:
        plant_written_tile_declared_input(setattr)
    print(json.dumps(harness.run_cell(spec_, cell, cfg, traffic, a.seed,
                                      a.seconds, False,
                                      time.perf_counter())), flush=True)
    sys.exit(0)
