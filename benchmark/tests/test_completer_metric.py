"""``completer_us_per_task`` (PR 30) on hand-made spans: two completer
lines, an idle episode, a drain that waits for the chip and one that
does not, releases inside and outside the window; and its two entries
in ``BENCHMARK.json``."""

import gzip
import json
import os

import pytest

from benchmark import harness, runtime_spans as rs
from benchmark.metrics import completer_us_per_task
from benchmark.tests.test_harness import tiny

US = 1_000


def _p(name, s, e, **args):
    return ["parsec:" + name, s * US, (e - s) * US, args]


def hand_made():
    """The window is [1000, 11000) us.

    Completer A's first span opens before the window and its last ends
    in it at 10000: its part is [1000, 10000), 9000 us.  Off come its
    idle episodes (500 + 2000) and the drain that blocked on the chip
    (1000): busy 5500.  Its other drain (block 0) is its own work.  Of
    its releases, the one begun at 900 is before the window: 3 count.

    Completer B's first span opens at 3000 and its last ends past the
    window: [3000, 11000), 8000 us, less an idle episode cut by the
    window's end (10500 to 11000: 500): busy 7500; 2 releases, the one
    begun at 11200 is after the window.

    A manager's and a worker's lines carry no ``fin.`` span: not read."""
    a = [_p("fin.idle", 500, 1500, dev="tpu:0"),
         _p("fin.release", 900, 950, cls="GEMM"),
         _p("fin.release", 1600, 1700, cls="GEMM"),
         _p("fin.release", 1700, 1800, cls="GEMM"),
         _p("fin.drain", 1800, 2000, block=0, n=2),
         _p("fin.drain", 2000, 3000, block=1, n=1),
         _p("fin.idle", 4000, 6000, dev="tpu:0"),
         _p("fin.release", 9000, 9900, cls="SYRK"),
         _p("fin.drain", 9900, 10000, block=0, n=1)]
    b = [_p("fin.release", 3000, 3400, cls="TRSM"),
         _p("fin.release", 5000, 5400, cls="TRSM"),
         _p("fin.idle", 10500, 11100, dev="tpu:1"),
         _p("fin.release", 11200, 11300, cls="TRSM")]
    mgr = [_p("mgr.launch", 1000, 9000, dev="tpu:0", seq=1),
           _p("mgr.inflight_wait", 2000, 8000)]
    worker = [_p("worker.idle", 0, 12000, th=0)]
    bench = [["bench:window", 1000 * US, 10000 * US, {}]]
    return {"devices": {}, "done": [], "threads": [bench, a, mgr, b, worker]}


def run_of(**over):
    run = {"jobs": [(0.0, 1.0)], "devices": [], "trace": None}
    run.update(over)
    return run


def test_busy_time_of_every_completer_over_the_releases_of_the_window(
        monkeypatch):
    monkeypatch.setattr(rs, "load", lambda path=None: hand_made())
    assert completer_us_per_task.read(run_of(trace={})) == \
        (5500 + 7500) / (3 + 2)


def test_a_blocking_drain_is_the_chips_time_and_a_plain_one_the_completers(
        monkeypatch):
    data = hand_made()
    for ev in data["threads"][1]:
        if ev[0] == "parsec:fin.drain":
            ev[3]["block"] = 1 - ev[3]["block"]
    monkeypatch.setattr(rs, "load", lambda path=None: data)
    # A: 9000 - 2500 idle - (200 + 100) blocking drains = 6200
    assert completer_us_per_task.read(run_of(trace={})) == \
        (6200 + 7500) / 5


def test_finds_nothing(monkeypatch):
    monkeypatch.setattr(rs, "load", lambda path=None: hand_made())
    assert completer_us_per_task.read(run_of()) is None        # untraced
    bare = hand_made()
    bare["threads"] = [[ev for ev in evs if ev[0] != "parsec:fin.release"]
                       for evs in bare["threads"]]
    monkeypatch.setattr(rs, "load", lambda path=None: bare)
    assert completer_us_per_task.read(run_of(trace={})) is None
    no_window = hand_made()
    no_window["threads"] = no_window["threads"][1:]
    monkeypatch.setattr(rs, "load", lambda path=None: no_window)
    assert completer_us_per_task.read(run_of(trace={})) is None

    def gone(path=None):
        raise FileNotFoundError("no .xplane.pb")
    monkeypatch.setattr(rs, "load", gone)
    assert completer_us_per_task.read(run_of(trace={})) is None


def test_the_recorded_chip_trace_reads_the_parents_completer(monkeypatch):
    """PR 25's recording of two jobs of potrf.n65536_mb2048, when the
    completer took one task a turn."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_potrf_nt32_spans_chip.json.gz")
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    monkeypatch.setattr(rs, "load", lambda path=None: data)
    value = completer_us_per_task.read(run_of(trace={}))
    assert value == pytest.approx(240.99, abs=0.01)
    assert rs.reduce(data)["release_us_per_task"] == \
        pytest.approx(82.42, abs=0.01)       # a third of it


def test_both_entries_are_listed_with_their_cells():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    rate = {m["name"]: m["workloads"] for m in spec["end_to_end"]
            if m["name"] != "setup_s"}
    for name, moves in (("completer_us_per_task", "tflops_per_chip"),
                        ("completer_us_per_task.host_paced",
                         "tflops_per_chip.host_paced")):
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            ("us", "lower", "device_trace", "device module")
        assert m["moves"] == moves and m["workloads"] == rate[moves]
    assert [m["name"] for m in spec["per_layer"][-2:]] == \
        ["completer_us_per_task", "completer_us_per_task.host_paced"]


@pytest.mark.parametrize("chips, cell", [(1, "potrf.n65536_mb2048"),
                                         (4, "potrf4.n147456_mb6144")])
def test_a_tiny_traced_potrf_reads_it_on_one_chip_and_on_four(chips, cell):
    r = tiny("potrf", chips, trace=True, name=cell)
    assert r["correct"] is True and r["failed"] == 0
    got = r["metrics"]["completer_us_per_task.host_paced"]
    # (self-contained: it reads where the CPU's trace, with no device
    # plane, gives ``runtime_spans.of_run`` nothing to join)
    assert got["unit"] == "us" and got["value"] > 0
