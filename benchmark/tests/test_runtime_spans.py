"""The reduction of the runtime's spans (benchmark/runtime_spans.py): a
hand-made trace in which every bucket's answer is known, the closure
check and the per-program join, and a trace recorded on the chip (PR 25:
two jobs of potrf.n65536_mb2048, ``runtime_spans.load``'s output)."""

import copy
import gzip
import importlib
import json
import os

import pytest

from benchmark import runtime_spans as rs

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
NEW_METRICS = ("idle_starved_pct", "idle_launch_pct", "idle_backpressure_pct",
               "idle_device_queue_pct", "launch_host_ms",
               "release_us_per_task", "worker_us_per_task")


def _p(name, s, e, **args):
    return ["parsec:" + name, s, e - s, args]


def _b(name, s, e):
    return ["bench:" + name, s, e - s, {}]


def hand_made():
    """A window of 10 000 ns on one chip, two manager threads.

    gap [0, 200) ends at the benchmark's own staging program and begins
    under ``bench:stage``: outside 200.
    gap [400, 2000) ends at A, dispatched [1700, 1900) in manager 1's
    launch [1500, 2100): device_queue 100, launch 400; before 1500,
    manager 2 holds a chain head [1150, 1300) (a launch with no
    execution): launch 150; insert opens at 1000: starved 350, outside
    600.
    gap [2500, 3000) ends at B_x2, whose dispatch [2800, 3100) returns
    after the chip started, in manager 2's launch from 2200: launch 500
    (manager 1 overlaps it in a launch of its own from 2600).
    gap [3600, 5000) ends at C, dispatched [4700, 4800) in a launch from
    4600: device_queue 200, launch 200; before it manager 1 sat in
    inflight_wait until 4300: backpressure 700; then nobody had work:
    starved 300.
    gaps [5200, 5300) and [5400, 6000) end at the two singles of a
    de-fused wave, dispatched [5150, 5250) and [5600, 5700) in ONE launch
    [5100, 6500): device_queue 50 + 300, launch 50 + 300.
    gap [6100, 10000) ends with the window: that launch goes on to 6500
    (launch 400), wait is open to 9000 (starved 2500), then the fence
    (outside 1000)."""
    devices = {DEV: [["jit_bench_stage_tile(9)", 200, 200],
                     ["jit_parsec_A(1)", 2000, 500],
                     ["jit_parsec_B_x2(2)", 3000, 300],
                     ["jit_parsec_E(3)", 3300, 300],
                     ["jit_parsec_C(4)", 5000, 200],
                     ["jit_parsec_F(5)", 5300, 100],
                     ["jit_parsec_F(5)", 6000, 100]]}
    bench = [_b("window", 0, 10000), _b("stage", 0, 1000),
             _b("insert", 1000, 1100), _b("wait", 1100, 9000),
             _b("fence", 9000, 9500)]
    mgr1 = [_p("mgr.starved", 1100, 1490, dev="tpu:0"),
            _p("mgr.launch", 1500, 2100, dev="tpu:0", seq=2, pool=7,
               cls="A", n=1, held=0, wait_us=1),
            _p("mgr.pop_wave", 1500, 1520), _p("mgr.stage_in", 1520, 1690),
            _p("mgr.dispatch", 1700, 1900, program="jit_parsec_A", first=1),
            _p("mgr.launch", 2600, 4300, dev="tpu:0", seq=4, pool=7,
               cls="E", n=1, held=0),
            _p("mgr.dispatch", 2700, 2750, program="jit_parsec_E", first=0),
            _p("mgr.inflight_wait", 2750, 4300),
            _p("mgr.launch", 4600, 5100, dev="tpu:0", seq=5, pool=7,
               cls="C", n=1, held=0),
            _p("mgr.dispatch", 4700, 4800, program="jit_parsec_C", first=0)]
    mgr2 = [_p("mgr.launch", 1150, 1300, dev="tpu:0", seq=1, pool=7,
               cls="H", n=1, held=1),
            _p("mgr.launch", 2200, 3300, dev="tpu:0", seq=3, pool=7,
               cls="B", n=2, held=0),
            _p("mgr.dispatch", 2800, 3100, program="jit_parsec_B_x2",
               first=0),
            _p("mgr.inflight_wait", 3100, 3300),
            _p("mgr.launch", 5100, 6500, dev="tpu:0", seq=6, pool=7,
               cls="F", n=2, held=0),
            _p("mgr.dispatch", 5150, 5250, program="jit_parsec_F", first=0),
            _p("mgr.dispatch", 5600, 5700, program="jit_parsec_F", first=0)]
    fin = [_p("fin.release", 2100, 2150, pool=7, cls="A", seq=2),
           _p("fin.release", 3200, 3260, pool=7, cls="B", seq=3),
           _p("fin.release", 3270, 3310, pool=7, cls="B", seq=3),
           _p("fin.idle", 3310, 4900, dev="tpu:0")]
    w1 = [_p("worker.idle", 0, 1050, th=0), _p("worker.idle", 1400, 5000, th=0),
          _p("worker.idle", 5050, 9900, th=0)]
    w2 = [_p("worker.idle", 100, 1060, th=1),
          _p("worker.idle", 1100, 9950, th=1)]
    ends = sorted(s + d for _n, s, d in devices[DEV])
    return {"devices": devices, "threads": [bench, mgr1, mgr2, fin, w1, w2],
            "done": [e + lat for e, lat in
                     zip(ends, (170, 165, 150, 190, 155, 160, 220))]}


ANSWER = {"outside": 1800, "starved": 3150, "launch": 2000,
          "backpressure": 700, "device_queue": 650}


def test_every_bucket_of_the_hand_made_trace():
    red = rs.reduce(hand_made())
    assert {k: round(v * 1e9) for k, v in red["buckets_s"].items()} == ANSWER
    assert red["idle_s"] * 1e9 == pytest.approx(10000 - 1700)
    assert red["join_ok"] and red["closed"]
    assert red["join"][DEV] == {
        "jit_parsec_A": [1, 1], "jit_parsec_B_x2": [1, 1],
        "jit_parsec_C": [1, 1], "jit_parsec_E": [1, 1],
        "jit_parsec_F": [2, 2]}


def test_thread_times_of_the_hand_made_trace():
    red = rs.reduce(hand_made())
    # six launches, the held one too; inflight_wait taken off
    assert red["launches"] == 6
    assert red["launch_host_ms"] * 1e6 == pytest.approx(
        (150 + 600 + (1100 - 200) + (1700 - 1550) + 500 + 1400) / 6)
    assert red["released"] == 3
    assert red["release_us_per_task"] * 1e3 == pytest.approx(50.0)
    assert red["workers"] == 2
    assert red["worker_busy_s"] * 1e9 == pytest.approx(350 + 50 + 40)
    assert red["span_totals"]["mgr.inflight_wait"] == [2, pytest.approx(
        1750e-9, abs=1e-10)]


def test_closure_is_checked_against_the_harness_idle_time():
    data = hand_made()
    assert rs.reduce(data, idle_s=8300e-9)["closed"]
    assert rs.reduce(data, idle_s=8390e-9)["closed"]        # within a point
    assert not rs.reduce(data, idle_s=8500e-9)["closed"]


def test_join_fails_when_spans_and_executions_disagree():
    data = hand_made()
    data["threads"][2] = [e for e in data["threads"][2]
                          if e[3].get("program") != "jit_parsec_F"]
    data["devices"][DEV].append(["jit_parsec_F(5)", 6200, 50])
    red = rs.reduce(data)
    assert red["join"][DEV]["jit_parsec_F"] == [0, 3] and not red["join_ok"]
    # a program of the runtime's stage-in has no dispatch span by design
    data = hand_made()
    data["devices"][DEV] += [["jit_broadcast_in_dim(6)", 6200 + i, 1]
                             for i in range(5)]
    assert rs.reduce(data)["join_ok"]


def test_clock_offset_is_measured_and_taken_off():
    data = hand_made()
    low, high = rs.clock_offset_ns(data)
    assert (low, high) == (-150, 150)
    ahead = copy.deepcopy(data)
    ahead["devices"][DEV] = [[n, s + 777, d] for n, s, d in data["devices"][DEV]]
    low, high = rs.clock_offset_ns(ahead)
    assert (low, high) == (627, 927) and low <= 777 <= high
    red = rs.reduce(ahead, shift_ns=777)
    assert {k: round(v * 1e9) for k, v in red["buckets_s"].items()} == ANSWER
    # without the Done events only the upper end is known
    ahead["done"] = []
    assert rs.clock_offset_ns(ahead) == (None, 927)


def test_dispatch_outside_any_launch_still_joins():
    """A chain forced from ``sync()`` is called on another thread: its
    dispatch span has no launch around it and stands for both."""
    data = hand_made()
    data["threads"].append(
        [_p("mgr.dispatch", 6600, 6700, program="jit_parsec_chain_H")])
    data["devices"][DEV].append(["jit_parsec_chain_H(8)", 6900, 100])
    red = rs.reduce(data)
    assert red["join_ok"]
    got = {k: round(v * 1e9) for k, v in red["buckets_s"].items()}
    # [6100, 6900): launch 400 as before, then starved to 6600, the
    # orphan's own call 100, device_queue 200; 100 less idle after it
    assert got["device_queue"] == ANSWER["device_queue"] + 200
    assert got["launch"] == ANSWER["launch"] + 100
    assert got["starved"] == ANSWER["starved"] - 100 - 200 - 100


def _run_of(data, monkeypatch, jobs=2):
    monkeypatch.setattr(rs, "load", lambda path=None: data)
    lo, hi = rs.window(data)
    busy = rs.total(rs.union((s, s + d) for _n, s, d in
                             data["devices"].get(DEV, [])))
    return {"trace": {"host": []}, "jobs": [(0.0, 1.0)] * jobs,
            "tasks_per_job": 11, "device": {"kind": "test"},
            "traced": {"window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9}}


def test_readers_report_the_reduction(monkeypatch):
    run = _run_of(hand_made(), monkeypatch)
    got = {m: importlib.import_module(f"benchmark.metrics.{m}").read(run)
           for m in NEW_METRICS}
    assert got["idle_starved_pct"] == pytest.approx(31.5)
    assert got["idle_launch_pct"] == pytest.approx(20.0)
    assert got["idle_backpressure_pct"] == pytest.approx(7.0)
    assert got["idle_device_queue_pct"] == pytest.approx(6.5)
    assert got["launch_host_ms"] == pytest.approx(3700 / 6 / 1e6)
    assert got["release_us_per_task"] == pytest.approx(0.05)
    assert got["worker_us_per_task"] == pytest.approx(440e-3 / 22)


def test_readers_find_nothing_in_a_program_without_the_spans(monkeypatch):
    """The parent of PR 25 writes no ``parsec:`` span: every reader says
    None and none raises.  So does a run that was not traced, and one
    whose buckets do not close."""
    data = hand_made()
    bare = {"devices": data["devices"], "threads": [data["threads"][0]],
            "done": data["done"]}
    run = _run_of(bare, monkeypatch)
    for m in NEW_METRICS:
        assert importlib.import_module(
            f"benchmark.metrics.{m}").read(run) is None
    untraced = {"trace": None, "jobs": [], "tasks_per_job": 1}
    for m in NEW_METRICS:
        assert importlib.import_module(
            f"benchmark.metrics.{m}").read(untraced) is None
    run = _run_of(data, monkeypatch)
    run["traced"]["busy_s"] += 1e-6          # a tenth of the window
    assert rs.of_run(run) is None


@pytest.fixture(scope="module")
def chip():
    with gzip.open(os.path.join(
            HERE, "data", "trace_potrf_nt32_spans_chip.json.gz"), "rt") as f:
        return json.load(f)


def test_chip_trace_joins_exactly(chip):
    joined = rs.join(chip)[DEV]
    assert joined["ok"]
    ours = {p: c for p, c in joined["counts"].items()
            if p.startswith("jit_parsec_")}
    assert len(ours) == 17 and all(a == b for a, b in ours.values())
    assert ours["jit_parsec_GEMM_x8"] == [1200, 1200]
    assert ours["jit_parsec_chain_POTRF__TRSM_x8"] == [48, 48]
    assert sum(a for a, _b in ours.values()) == 1817
    # the stage-in's zeros for the NEW flows: executed, never dispatched
    assert joined["counts"]["jit_broadcast_in_dim"] == [0, 62]


def test_chip_trace_clock_offset(chip):
    """On the chip the device's events lie 1.09-1.27 ms BEFORE the host
    events that caused them; uncorrected, no execution could be put
    after its own dispatch."""
    low, high = rs.clock_offset_ns(chip)
    assert (low, high) == (-1269722, -1092889)
    raw = rs.reduce(chip)["buckets_s"]
    mid = rs.reduce(chip, shift_ns=(low + high) // 2)["buckets_s"]
    assert raw["device_queue"] < 1e-4 < mid["device_queue"]
    for end in (low, high):
        edge = rs.reduce(chip, shift_ns=end)["buckets_s"]
        # what is not known of the clock moves no bucket by a point
        assert all(abs(edge[k] - mid[k]) < 0.01 * 3.98 for k in rs.BUCKETS)


def test_chip_trace_buckets_close_on_the_idle_time(chip):
    lo, hi = rs.window(chip)
    assert (hi - lo) == 3980445504
    busy = rs.total(rs.union((max(s, lo), min(s + d, hi))
                             for _n, s, d in chip["devices"][DEV]))
    idle_s = (hi - lo - busy) / 1e9
    low, high = rs.clock_offset_ns(chip)
    red = rs.reduce(chip, idle_s, (low + high) // 2)
    assert red["closed"] and red["join_ok"]
    assert red["idle_s"] == pytest.approx(idle_s, abs=2e-3)
    b = red["buckets_s"]
    assert b["launch"] == pytest.approx(1.4116, abs=1e-3)
    assert b["backpressure"] == pytest.approx(0.8333, abs=1e-3)
    assert b["starved"] == pytest.approx(0.2581, abs=1e-3)
    assert b["outside"] == pytest.approx(0.2476, abs=1e-3)
    assert b["device_queue"] == pytest.approx(0.0230, abs=1e-3)
    assert red["launches"] == 1865 and red["released"] == 11968
    assert red["launch_host_ms"] == pytest.approx(2.2440, abs=1e-3)
    assert red["release_us_per_task"] == pytest.approx(82.42, abs=0.01)
    assert red["workers"] == 4
