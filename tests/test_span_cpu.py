"""Thread CPU time on the runtime's spans (PR 35): the sink under
contention (prof/pins.py TraceMePins reads ``time.thread_time_ns`` at
both ends of a span, for one span a process every ``_CLOCK_GAP_NS``),
the collector's callback, the span names in one place, and the
benchmark's CPU readers on a hand-made trace whose answers are worked
out by hand (benchmark/metrics/)."""

import copy
import gc
import importlib
import json
import os
import re
import threading
import time

import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.prof import pins
from parsec_tpu.prof.pins import (ALL_SPAN_NAMES, SPAN_PREFIX, TraceMePins,
                                  open_span)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHIPPED_GAP_NS = pins._CLOCK_GAP_NS


# -- (a) the sink ----------------------------------------------------------

class Recording:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps what the
    sink hands it, and the wall clock at enter and exit."""

    closed = []
    lock = threading.Lock()

    def __init__(self, name, **args):
        self.name, self.args, self.late = name, args, {}

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def set_metadata(self, **late):
        self.late.update(late)

    def __exit__(self, *exc):
        self.t1 = time.perf_counter_ns()
        with Recording.lock:
            Recording.closed.append(self)


@pytest.fixture
def recorded(monkeypatch):
    """A context whose installed sink records into ``Recording``, whose
    gate is forced on and whose every span is due its turn at the clock
    (no gap): (context, the list of closed spans)."""
    Recording.closed = []
    with Context(nb_cores=1) as ctx:
        sink = ctx._traceme
        assert sink is not None, "a context with an XLA device has the sink"
        monkeypatch.setattr(sink, "_annotation", Recording)
        monkeypatch.setattr(pins, "_CLOCK_GAP_NS", 0)
        monkeypatch.setattr(ctx, "_span_live", lambda: True)
        yield ctx, Recording.closed


def _named(closed, name):
    """The test's own spans: the context's threads (a worker, the
    device's manager and completer) record theirs beside them."""
    return [r for r in closed if r.name == SPAN_PREFIX + name]


def _by_thread(closed, name):
    out = {}
    for r in _named(closed, name):
        out.setdefault(r.args.get("th"), []).append(r)
    return out


def test_three_threads_under_the_lock_run_a_third_of_their_wall(recorded):
    """Three threads spin 0.2 s of wall each inside a span, under one
    interpreter lock: the thread clock says how much of it each RAN."""
    ctx, closed = recorded
    es = ctx.streams[0]
    gate = threading.Barrier(3)

    def spin(th):
        gate.wait(timeout=10)
        for _ in range(2):                 # two spans: the clock goes on
            with open_span(es, "mgr.stage_in", th=th):
                t_end = time.perf_counter() + 0.2
                while time.perf_counter() < t_end:
                    pass

    threads = [threading.Thread(target=spin, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    spans = _by_thread(closed, "mgr.stage_in")
    assert sorted(spans) == [0, 1, 2]
    for th, mine in spans.items():
        assert len(mine) == 2
        for r in mine:
            wall, cpu = r.t1 - r.t0, r.late["cpu_ns"]
            assert 0 < cpu <= wall + 1_000_000
            assert cpu < 0.75 * wall, f"thread {th}: {cpu} of {wall} ns"
        first, second = sorted(mine, key=lambda r: r.t0)
        # the absolute readings: the next span begins where the thread's
        # clock had got to, not below the last span's end
        assert second.late["cpu_end_ns"] - second.late["cpu_ns"] >= \
            first.late["cpu_end_ns"]


def test_a_sleeping_span_burns_no_cpu_and_late_arguments_ride_along(recorded):
    ctx, closed = recorded
    es = ctx.streams[0]
    with open_span(es, "fin.idle", dev="x"):
        time.sleep(0.05)
    open_span(es, "fin.drain", block=0).end(n=2)
    (idle,) = _named(closed, "fin.idle")
    (drain,) = _named(closed, "fin.drain")
    assert idle.args == {"dev": "x"}
    assert idle.t1 - idle.t0 >= 50_000_000
    assert 0 <= idle.late["cpu_ns"] < 5_000_000
    assert set(idle.late) == {"cpu_ns", "cpu_end_ns"}
    assert set(drain.late) == {"cpu_ns", "cpu_end_ns", "n"}
    assert drain.late["n"] == 2 and drain.args == {"block": 0}
    assert drain.late["cpu_end_ns"] - drain.late["cpu_ns"] >= \
        idle.late["cpu_end_ns"]


def test_a_parent_includes_its_children(recorded):
    ctx, closed = recorded
    es = ctx.streams[0]
    with open_span(es, "mgr.launch", seq=1):
        for _ in range(3):
            with open_span(es, "mgr.dispatch", program="p"):
                sum(range(20000))
    kids, (parent,) = _named(closed, "mgr.dispatch"), \
        _named(closed, "mgr.launch")
    assert len(kids) == 3
    assert parent.late["cpu_ns"] >= sum(k.late["cpu_ns"] for k in kids) > 0
    assert parent.late["cpu_end_ns"] >= kids[-1].late["cpu_end_ns"]


def test_the_clock_is_rationed_a_thread(recorded, monkeypatch):
    """With a gap of 4 ms, on a clock the test moves: the first span of a
    thread carries the integers, the spans inside its gap leave without
    them (their late arguments still travel), the first span past the
    gap times the threads that take turns carries them again; another
    thread has a turn of its own."""
    ctx, closed = recorded
    es = ctx.streams[0]
    now = [10 ** 12]
    monkeypatch.setattr(pins, "_now_ns", lambda: now[0])
    monkeypatch.setattr(pins, "_CLOCK_GAP_NS", 4_000_000)
    monkeypatch.setattr(ctx._traceme, "_next_read", {})

    def four(th):
        for i in range(3):
            open_span(es, "mgr.stage_in", th=th, i=i).end(bytes_in=i)
            now[0] += 1_000_000
        now[0] += 4_000_000 * 64        # more threads than any context has
        open_span(es, "mgr.stage_in", th=th, i=3).end(bytes_in=3)

    four(0)
    other = threading.Thread(target=four, args=(1,))
    other.start()
    other.join(timeout=30)
    assert not other.is_alive()
    for th, mine in _by_thread(closed, "mgr.stage_in").items():
        mine.sort(key=lambda r: r.args["i"])
        assert [set(r.late) for r in mine] == [
            {"cpu_ns", "cpu_end_ns", "bytes_in"}, {"bytes_in"},
            {"bytes_in"}, {"cpu_ns", "cpu_end_ns", "bytes_in"}], th
        assert [r.late["bytes_in"] for r in mine] == [0, 1, 2, 3]
        assert mine[3].late["cpu_end_ns"] - mine[3].late["cpu_ns"] >= \
            mine[0].late["cpu_end_ns"]


def test_the_gap_is_a_process_not_a_thread(recorded, monkeypatch):
    """Three threads emit spans back to back for a quarter of a second
    under the gap as shipped: beside each thread's first, the clock is
    read for no more than one span a gap in the whole process, however
    many threads emit."""
    ctx, closed = recorded
    es = ctx.streams[0]
    monkeypatch.setattr(pins, "_CLOCK_GAP_NS", SHIPPED_GAP_NS)
    monkeypatch.setattr(ctx._traceme, "_next_read", {})
    del closed[:]
    t0 = time.perf_counter_ns()

    def emit(th):
        while time.perf_counter_ns() - t0 < 250_000_000:
            open_span(es, "mgr.stage_in", th=th).end()

    threads = [threading.Thread(target=emit, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    elapsed = time.perf_counter_ns() - t0
    with Recording.lock:
        mine = list(closed)
    clocked = [r for r in mine if "cpu_ns" in r.late]
    assert len(_named(mine, "mgr.stage_in")) > 300
    assert {r.args["th"] for r in _named(clocked, "mgr.stage_in")} \
        == {0, 1, 2}
    assert len(clocked) <= elapsed // SHIPPED_GAP_NS + 1 \
        + len(ctx._traceme._next_read)


def test_collector_callback_is_one_a_process_and_leaves_with_the_last_sink(
        monkeypatch):
    """``install`` puts ONE callback on ``gc.callbacks`` whatever the
    number of contexts; the last ``uninstall`` takes it off.  Live, a
    collection is a ``gc.collect`` span with ``gen`` (its wall time is
    the number: it takes no turn at the clock); with no session it
    records nothing."""
    def ours():
        return [cb for cb in gc.callbacks
                if getattr(cb, "__func__", None) is TraceMePins._gc]

    assert ours() == []
    Recording.closed = []
    with Context(nb_cores=1) as a:
        with Context(nb_cores=1) as b:
            assert a._traceme is not None and b._traceme is not None
            assert len(ours()) == 1
            gc.collect()                     # no session: the probe alone
            assert Recording.closed == []
        (cb,) = ours()
        monkeypatch.setattr(cb.__self__, "_annotation", Recording)
        gc.collect()
        assert ours() == [cb]
    assert ours() == []
    full = [r for r in Recording.closed if r.args == {"gen": 2}]
    assert full and all(r.name == "parsec:gc.collect" for r in full)
    assert all(r.late == {} and r.t1 >= r.t0 for r in full)


# -- the names in one place ------------------------------------------------

def test_every_emission_site_uses_a_listed_name():
    """Every literal name an ``open_span`` site passes is in
    ``ALL_SPAN_NAMES``, every listed name but the collector's (emitted
    by the sink itself) has a site, and no name is listed twice."""
    used = set()
    for dirpath, _dirs, files in os.walk(os.path.join(ROOT, "parsec_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    used |= set(re.findall(
                        r'open_span\(\s*[^,()]+(?:\([^()]*\))?[^,()]*,\s*'
                        r'"([a-z_.]+)"', fh.read()))
    assert used, "no open_span site found: the pattern is stale"
    assert used <= set(ALL_SPAN_NAMES), used - set(ALL_SPAN_NAMES)
    assert set(ALL_SPAN_NAMES) - used == {"gc.collect"}
    assert len(set(ALL_SPAN_NAMES)) == len(ALL_SPAN_NAMES)
    assert "gc.collect" in pins.SPAN_NAMES


# -- (c) the readers on a hand-made trace ----------------------------------

US = 1_000
READERS = ("host_cpu_us_per_task", "completer_cpu_us_per_task",
           "gc_pause_pct")


def _p(name, s, e, cpu, cpu_end, **args):
    """A span in microseconds: host begin and end, CPU inside it, the
    thread clock at its end."""
    return ["parsec:" + name, s * US, (e - s) * US,
            dict(args, cpu_ns=cpu * US, cpu_end_ns=cpu_end * US)]


def hand_made():
    """The window is [1000, 11000) us; a span's thread clock at its
    begin is ``cpu_end - cpu``.

    **client** (its line carries ``bench:window`` too): first boundary in
    the window 1100 (clock 5000), last 10900 (clock 6000): CPU 1000 over
    9800 of wall, 8600 of it declared waits (``ctx.wait`` 3700 + 4800,
    ``dtd.window_wait`` 100).  Between the first ``ctx.wait``'s end
    (5200) and the second ``ctx.startup``'s begin (5900) it burned 700
    with no span open: the staging.

    **manager A**: a launch that began before the window ends in it at
    1500 (clock 19 900): the line's first boundary; its last is the end
    of the dispatch at 10 000 (clock 21 350), inside a launch that
    outlives the window: CPU 1450 over 8500 of wall, waits 500 + 5000.
    **manager B**: one launch, 5000 to 6000, CPU 700.

    **completer**: first boundary the end of an idle episode at 1500
    (clock 40 005), last the begin of a release at 10 950 (clock
    41 150): CPU 1145 over 9450, waits 1000 (the blocking drain; the
    idle episode ends where the line begins).  Releases begun in the
    window 4: 286.25 a task.

    **worker**: between two idle episodes, 3000 (clock 50 020) to 8000
    (clock 50 620): CPU 600 over 5000, its task bodies.

    All lines: 1000 + 1450 + 700 + 1145 + 600 = 4895 us over a window of
    10 000: 0.4895 threads; 2 jobs of 5 tasks: 489.5 us a task.  Of the
    27 spans 21 begin in the window."""
    client = [
        ["bench:window", 1000 * US, 10000 * US, {}],
        _p("ctx.startup", 1100, 1300, 150, 5150),
        _p("dtd.insert", 1110, 1290, 140, 5145, pool=1, n=4),
        _p("dtd.window_wait", 1150, 1250, 10, 5060, inflight=2048),
        _p("ctx.wait", 1300, 5000, 50, 5200),
        _p("ctx.startup", 6000, 6100, 80, 5980),
        _p("ctx.wait", 6100, 10900, 20, 6000)]
    mgr_a = [
        _p("mgr.launch", 500, 1500, 400, 19900, dev="tpu:0", seq=1),
        _p("mgr.launch", 2000, 4000, 900, 20900, dev="tpu:0", seq=2),
        _p("mgr.dispatch", 2100, 3100, 600, 20650, program="p", first=0),
        _p("mgr.inflight_wait", 3200, 3700, 10, 20700),
        _p("mgr.starved", 4000, 9000, 5, 20910, dev="tpu:0"),
        _p("mgr.launch", 9500, 11500, 500, 21500, dev="tpu:0", seq=4),
        _p("mgr.dispatch", 9600, 10000, 300, 21350, program="p", first=0)]
    mgr_b = [
        _p("mgr.launch", 5000, 6000, 700, 30700, dev="tpu:0", seq=3),
        _p("mgr.dispatch", 5100, 5900, 500, 30600, program="q", first=0)]
    fin = [
        _p("fin.idle", 500, 1500, 5, 40005, dev="tpu:0"),
        _p("fin.pass", 1600, 2000, 300, 40320, n=2),
        _p("fin.release", 1650, 1750, 80, 40110, cls="GEMM"),
        _p("fin.release", 1800, 1950, 100, 40300, cls="GEMM"),
        _p("fin.drain", 2000, 3000, 10, 40340, block=1, n=2),
        _p("fin.release", 9000, 9900, 400, 41000, cls="SYRK"),
        _p("fin.release", 10950, 11050, 50, 41200, cls="SYRK")]
    worker = [
        _p("worker.idle", 0, 3000, 20, 50020, th=0),
        _p("worker.idle", 8000, 12000, 10, 50630, th=0)]
    return {"devices": {}, "done": [],
            "threads": [client, mgr_a, fin, mgr_b, worker]}


ANSWERS = {"host_cpu_us_per_task": 489.5,
           "completer_cpu_us_per_task": 286.25, "gc_pause_pct": 0.0}


def _read(monkeypatch, name, data):
    from benchmark import runtime_spans as rs
    from benchmark.metrics import host_cpu_us_per_task
    monkeypatch.setattr(rs, "load", lambda path=None: data)
    monkeypatch.setattr(host_cpu_us_per_task, "_reduced", {})
    run = {"jobs": [(0.0, 1.0), (1.0, 2.0)], "tasks_per_job": 5,
           "trace": {}, "device": None}
    reader = importlib.import_module(f"benchmark.metrics.{name}")
    return reader.read(run), reader.read(dict(run, trace=None))


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_hand_made_trace(monkeypatch, name):
    value, untraced = _read(monkeypatch, name, hand_made())
    assert value == pytest.approx(ANSWERS[name], rel=1e-12)
    assert untraced is None


@pytest.mark.parametrize("name", READERS)
def test_reader_says_nothing_without_cpu_ns(monkeypatch, name):
    """The parent's trace: the same spans without the two integers."""
    data = hand_made()
    for evs in data["threads"]:
        for ev in evs:
            ev[3].pop("cpu_ns", None)
            ev[3].pop("cpu_end_ns", None)
    assert _read(monkeypatch, name, data) == (None, None)


def test_thread_lines_by_role_and_their_waits(monkeypatch):
    from benchmark.metrics import host_cpu_us_per_task
    red = host_cpu_us_per_task.reduce(hand_made())
    got = [(ln["role"], ln["wall_ns"] // US, ln["wait_ns"] // US,
            ln["cpu_ns"] // US, ln["back"]) for ln in red["lines"]]
    assert got == [("client", 9800, 8600, 1000, 0),
                   ("manager", 8500, 5500, 1450, 0),
                   ("completer", 9450, 1000, 1145, 0),
                   ("manager", 1000, 0, 700, 0),
                   ("worker", 5000, 0, 600, 0)]
    assert red["released"] == 4 and red["gc_ns"] == 0
    assert (red["spans"], red["clocked"]) == (21, 21)
    # what the log line calls threads: cores' worth over the window
    lo, hi = red["window"]
    assert red["cpu_ns"] / (hi - lo) == pytest.approx(0.4895)
    # the staging: the client's clock between one job's wait and the
    # next job's start-up, with no span open
    marks = host_cpu_us_per_task.boundaries(hand_made()["threads"][0])
    clock = dict(marks)
    assert clock[6000 * US] - clock[5000 * US] == 700 * US


RATIONED = {"host_cpu_us_per_task": 373.5,
            "completer_cpu_us_per_task": 335 / 4, "gc_pause_pct": 0.0}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_rationed_trace(monkeypatch, name):
    """What the sink leaves on the chip: the per-task spans
    (``fin.release``, ``mgr.dispatch``) went by between two turns at the
    clock.  The readers take what readings there are — manager A's last
    is now the begin of its last launch (clock 21 000 at 9500: CPU 1100),
    the completer's the end of the drain (clock 40 340 at 3000: CPU
    335): 1000 + 1100 + 700 + 335 + 600 = 3735."""
    data = hand_made()
    for evs in data["threads"]:
        for ev in evs:
            if ev[0] in ("parsec:fin.release", "parsec:mgr.dispatch"):
                del ev[3]["cpu_ns"], ev[3]["cpu_end_ns"]
    value, untraced = _read(monkeypatch, name, data)
    assert value == pytest.approx(RATIONED[name], rel=1e-12)
    assert untraced is None
    from benchmark.metrics import host_cpu_us_per_task
    red = host_cpu_us_per_task.reduce(data)
    assert (red["spans"], red["clocked"], red["released"]) == (21, 14, 4)


def test_collections_are_wall_time_clipped_to_the_window(monkeypatch):
    """One collection inside the window (500 us), one cut by its end
    (200 of 500), one before it: 700 of 10 000 us.  The collector's span
    carries no clock reading, so a line of its own adds no CPU."""
    data = hand_made()
    data["threads"].append([
        ["parsec:gc.collect", 100 * US, 200 * US, {"gen": 0}],
        ["parsec:gc.collect", 9000 * US, 500 * US, {"gen": 2}],
        ["parsec:gc.collect", 10800 * US, 500 * US, {"gen": 1}]])
    value, _none = _read(monkeypatch, "gc_pause_pct", data)
    assert value == pytest.approx(7.0)
    value, _none = _read(monkeypatch, "host_cpu_us_per_task",
                         copy.deepcopy(data))
    assert value == pytest.approx(489.5)
    from benchmark.metrics import host_cpu_us_per_task
    assert host_cpu_us_per_task.reduce(data)["gc_gens"] == {
        1: [1, 500 * US, 500 * US], 2: [1, 500 * US, 500 * US]}


# -- (d) the entries in BENCHMARK.json -------------------------------------

@pytest.mark.parametrize("name", READERS)
def test_benchmark_entry_names_cells_that_report_what_it_moves(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"benchmark.metrics.{name}")
    assert callable(reader.read)
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                       name + ".py"))
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["better"] == "lower"
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "tflops_per_chip.host_paced"
    (moved,) = [m for m in spec["end_to_end"] if m["name"] == entry["moves"]]
    cells = {c["name"] for c in spec["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    assert set(entry["workloads"]) <= set(moved["workloads"])
    older = {m["layer"] for m in spec["per_layer"]
             if m["name"] not in READERS}
    assert entry["layer"] in older


# -- the readers behind the harness, on a CPU trace ------------------------

def test_a_traced_tiny_dtd_cell_reports_the_three(monkeypatch, tmp_path):
    """The DTD cell at a size a test can hold (CPU devices, float32
    storage, as benchmark/tests/ runs it), traced through the harness
    with the sink as shipped: every new reader finds its spans in a real
    trace (they read host spans alone, so a trace without a TPU plane
    does), and the completer's CPU figure stays under its wall-clock
    twin."""
    from benchmark import harness
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    cell = "potrf_dtd.n65536_mb2048"
    spec, _cell, config, _traffic = harness.load_cell(cell)
    config = {**config, "storage": "float32", "warm_jobs": 1}
    r = harness.run_cell(spec, {"name": cell, "chips": 1}, config,
                         {"n": 384, "mb": 64}, 2 ** 31 + 35, 0.5, True,
                         time.perf_counter())
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(READERS) <= set(m)
    assert 0 < m["completer_cpu_us_per_task"] <= \
        m["completer_us_per_task.host_paced"]
    assert 0 <= m["gc_pause_pct"] < 100
    # cores' worth the runtime's threads ran: over none, under all
    threads = m["host_cpu_us_per_task"] * m["tasks_per_s.host_paced"] / 1e6
    assert 0 < threads < len(os.sched_getaffinity(0)) + 16
