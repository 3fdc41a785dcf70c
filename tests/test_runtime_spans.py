"""Thread-state spans of the runtime on the profiler's clock
(prof/pins.py Span / TraceMePins; emitted by devices/xla.py and the
worker loop), the counters at the same boundaries, and the stable
program names.  A tiny Cholesky (nt = 4, the device path on one CPU
device) runs under ``jax.profiler``; the trace is read back with
``ProfileData`` the way benchmark/runtime_spans.py reads a chip's."""

import gc
import glob
import os
import re
import threading

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.prof import pins
from parsec_tpu.prof.pins import SPAN_NAMES, SPAN_PREFIX
from parsec_tpu.utils.mca import params

MB, NT, JOBS = 16, 4, 3
#: tasks of one tiled Cholesky: POTRF nt, TRSM and SYRK nt(nt-1)/2 each,
#: GEMM nt(nt-1)(nt-2)/6
TASKS = NT + NT * (NT - 1) + NT * (NT - 1) * (NT - 2) // 6
#: depth 1 makes every launch behind an entry the completer has not
#: taken yet wait for room (mgr.inflight_wait; the traced jobs slow the
#: completer's releases, as a loaded host does, so that some do); the
#: window lets siblings meet in fused waves (warm.compile) however the
#: threads interleave
MCA = {"device_max": 1, "device_inflight_depth": 1,
       "device_fuse_window_ms": 2.0}
MGR_CHILDREN = ("mgr.pop_wave", "mgr.stage_in", "mgr.dispatch",
                "mgr.inflight_wait", "mgr.warm_wait")


def _slow_releases(mp, seconds):
    """Every dep release takes the completer ``seconds`` longer."""
    import time
    from parsec_tpu.core import scheduling
    real = scheduling.complete_execution

    def slow(es, task, *a, **kw):
        if threading.current_thread().name.startswith("xla-fin"):
            time.sleep(seconds)
        return real(es, task, *a, **kw)
    mp.setattr(scheduling, "complete_execution", slow)


def _run_jobs(jobs=JOBS, collect=False):
    """``jobs`` factorizations on one Context; the device's counters.
    ``collect`` forces one full collection of the interpreter's heap
    after the first job, while the Context is open."""
    from parsec_tpu.apps.potrf import potrf_taskpool
    n = MB * NT
    rng = np.random.default_rng(0)
    b = rng.standard_normal((n, n)).astype(np.float32)
    spd = (b @ b.T + n * np.eye(n)).astype(np.float32)
    for k, v in MCA.items():
        params.set(k, v)
    try:
        with Context(nb_cores=4) as ctx:
            for _ in range(jobs):
                A = TwoDimBlockCyclic(mb=MB, nb=MB, lm=n,
                                      ln=n).from_array(spd.copy())
                ctx.add_taskpool(potrf_taskpool(A, device="tpu"))
                ctx.wait()
                L = np.tril(A.to_array())
                assert np.abs(L @ L.T - spd).max() < 1e-3 * np.abs(spd).max()
                if collect:
                    gc.collect()
                    collect = False
            (dev,) = ctx.device_registry.accelerators
            samples = {s["n"]: s["v"]
                       for s in ctx.metrics._collect_devices()}
            return dev.stats.as_dict(), samples
    finally:
        for k in MCA:
            params.unset(k)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The jobs under a profiler session that covers the whole Context:
    ({"stats", "samples", "lines", "modules"}), ``lines`` a list (one
    per thread line that holds runtime spans) of (name, start, end,
    args) sorted by start, ``modules`` the HLO module names the CPU
    client's events carry."""
    import jax
    from jax.profiler import ProfileData
    from parsec_tpu.apps import potrf
    # kernel functions of this run's own: which fused widths are ready
    # is kept on them, and another test of this process may have warmed
    # the app's memoized ones
    potrf._kernels.clear()
    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        with pytest.MonkeyPatch.context() as mp:
            _slow_releases(mp, 0.001)
            # every span is due its turn at the thread's clock
            mp.setattr(pins, "_CLOCK_GAP_NS", 0)
            stats, samples = _run_jobs(collect=True)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    lines, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            evs = []
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIX):
                    evs.append((e.name[len(SPAN_PREFIX):], int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats)))
                else:
                    mod = dict(e.stats).get("hlo_module")
                    if mod:
                        modules.add(mod)
            if evs:
                lines.append(sorted(evs, key=lambda ev: ev[1]))
    return {"stats": stats, "samples": samples, "lines": lines,
            "modules": modules}


def _spans(traced, name):
    return [ev for ln in traced["lines"] for ev in ln if ev[0] == name]


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_span_reaches_the_trace(traced, name):
    assert _spans(traced, name), f"no parsec:{name} in the trace"


def test_each_thread_line_holds_one_role(traced):
    """mgr.* only on manager lines (at most device_dispatchers of them),
    fin.* on the one completer line, worker.idle on worker lines with
    one ``th`` each, warm.compile on the warmer's line, ctx.* on the
    caller's; the collector's span lands on whichever thread tripped
    it."""
    roles = []
    for ln in traced["lines"]:
        kinds = {ev[0].split(".")[0] for ev in ln} - {"gc"}
        if not kinds:
            continue            # a thread that only ever collected
        assert len(kinds) == 1, f"a thread line mixes {kinds}"
        roles.append(kinds.pop())
        if roles[-1] == "worker":
            assert len({ev[3]["th"] for ev in ln if ev[0] != "gc.collect"}) == 1
    assert 1 <= roles.count("mgr") <= 2
    assert roles.count("fin") == 1
    assert 1 <= roles.count("worker") <= 4
    assert roles.count("warm") == 1
    assert roles.count("ctx") == 1
    assert all(ev[3]["dev"] == "cpu:0" for name in
               ("mgr.launch", "mgr.starved", "fin.idle")
               for ev in _spans(traced, name))


def test_every_span_carries_the_thread_clock(traced):
    """With no gap between two turns, both integers on every ``parsec:``
    event but the collector's (whose wall time is the number); a span's
    CPU time fits in its wall time; along a thread line the clock never
    runs back."""
    seen = 0
    for ln in traced["lines"]:
        marks = []
        for name, s, e, a in ln:
            if name == "gc.collect":
                assert set(a) == {"gen"}
                continue
            seen += 1
            assert isinstance(a["cpu_ns"], int), (name, a)
            assert isinstance(a["cpu_end_ns"], int), (name, a)
            assert 0 <= a["cpu_ns"] <= e - s + 1_000_000, (name, e - s, a)
            assert a["cpu_end_ns"] >= a["cpu_ns"]
            marks += [(s, a["cpu_end_ns"] - a["cpu_ns"]),
                      (e, a["cpu_end_ns"])]
        marks.sort()
        assert all(b[1] >= a[1] for a, b in zip(marks, marks[1:]))
    assert seen


def test_a_parent_cpu_time_holds_its_children(traced):
    checked = 0
    for ln in traced["lines"]:
        evs = [ev for ev in ln if ev[0] != "gc.collect"]
        for name, s, e, a in evs:
            inside = [c for c in evs if s <= c[1] and c[2] <= e
                      and c[1:3] != (s, e)]
            kids = [c for c in inside
                    if not any(o is not c and o[1] <= c[1] and c[2] <= o[2]
                               for o in inside)]
            if kids:
                checked += 1
                assert a["cpu_ns"] >= sum(c[3]["cpu_ns"] for c in kids), \
                    (name, a, [(c[0], c[3]["cpu_ns"]) for c in kids])
    assert checked


def test_the_callers_thread_is_on_the_map(traced):
    """``ctx.startup`` then ``ctx.wait``, once a job, on one line (the
    thread that called ``Context.wait``)."""
    (line,) = [ln for ln in traced["lines"]
               if any(ev[0].startswith("ctx.") for ev in ln)]
    mine = [ev for ev in line if ev[0].startswith("ctx.")]
    assert [ev[0] for ev in mine] == ["ctx.startup", "ctx.wait"] * JOBS
    for (_n0, _s0, e0, _a0), (_n1, s1, _e1, _a1) in zip(mine[::2], mine[1::2]):
        assert e0 <= s1


def test_a_forced_collection_is_a_span(traced):
    full = [ev for ev in _spans(traced, "gc.collect") if ev[3]["gen"] == 2]
    assert full, "the gc.collect() forced inside the session left no span"
    # the forced one ran on the caller's thread, between two jobs
    (line,) = [ln for ln in traced["lines"]
               if any(ev[0].startswith("ctx.") for ev in ln)]
    assert any(ev in line for ev in full)


def test_launch_children_nest_in_their_launch(traced):
    seen = 0
    for ln in traced["lines"]:
        launches = [ev for ev in ln if ev[0] == "mgr.launch"]
        for name, s, e, _a in ln:
            if name in MGR_CHILDREN:
                seen += 1
                assert any(ls <= s and e <= le
                           for _n, ls, le, _la in launches), \
                    f"{name} at {s} lies in no mgr.launch of its thread"
            elif name == "mgr.starved":
                assert not any(ls < e and s < le
                               for _n, ls, le, _la in launches)
    assert seen


def test_launch_seq_strictly_increasing(traced):
    launches = sorted(_spans(traced, "mgr.launch"), key=lambda ev: ev[1])
    seqs = [ev[3]["seq"] for ev in launches]
    assert seqs == sorted(set(seqs)) and seqs[0] == 1
    for ev in launches:
        assert ev[3]["cls"] in ("POTRF", "POTRFL", "TRSM", "SYRK", "GEMM")
        assert ev[3]["n"] >= 1 and ev[3]["held"] in (0, 1)
    # one pool a job, shared by the spans of that job
    assert len({ev[3]["pool"] for ev in launches}) == JOBS


def test_dispatch_spans_equal_the_launch_counter(traced):
    st = traced["stats"]
    assert len(_spans(traced, "mgr.dispatch")) == st["launches"] > 0
    firsts = sum(ev[3]["first"] for ev in _spans(traced, "mgr.dispatch"))
    assert firsts + len(_spans(traced, "warm.compile")) == st["compiles"]
    assert len(_spans(traced, "mgr.inflight_wait")) == st["inflight_waits"]
    held = [ev for ev in _spans(traced, "mgr.launch") if ev[3]["held"]]
    assert len(held) == st["held_tasks"]


def test_warm_wait_spans_equal_the_counter(traced):
    """Every launch that blocked on a warming width is one
    ``mgr.warm_wait``; a width is compiled once a program, not once a
    taskpool: the later jobs find it ready."""
    waits = _spans(traced, "mgr.warm_wait")
    assert len(waits) == traced["stats"]["warm_waits"] > 0
    compiled = [ev[3]["program"] for ev in _spans(traced, "warm.compile")]
    assert len(compiled) == len(set(compiled))
    assert {ev[3]["program"] for ev in waits} <= set(compiled)
    # only a fused wave has a width to wait for
    for ln in traced["lines"]:
        launches = [ev for ev in ln if ev[0] == "mgr.launch"]
        for _n, s, e, _a in (ev for ev in ln if ev[0] == "mgr.warm_wait"):
            (la,) = [la for la in launches if la[1] <= s and e <= la[2]]
            assert la[3]["n"] > 1


def test_every_task_is_counted_and_released(traced):
    st = traced["stats"]
    assert st["executed_tasks"] + st["held_tasks"] == JOBS * TASKS
    # every POTRF but a job's last has a TRSM to be traced into
    assert st["held_tasks"] == JOBS * (NT - 1)
    releases = _spans(traced, "fin.release")
    assert len(releases) == JOBS * TASKS
    by_seq = {ev[3]["seq"]: ev[3] for ev in _spans(traced, "mgr.launch")}
    for _n, _s, _e, a in releases:
        # the launch that caused it: same pool, same class
        assert by_seq[a["seq"]]["pool"] == a["pool"]
        assert by_seq[a["seq"]]["cls"] == a["cls"]
    assert sum(a["n"] for a in by_seq.values()) == JOBS * TASKS


def test_pass_spans_equal_the_pass_counter_and_hold_every_release(traced):
    """One ``fin.pass`` a pass of the completer that took something, its
    ``n`` what it took; each holds exactly ``n`` ``fin.release`` (one a
    task, as ever) and none lies outside; what a ``fin.drain`` finalizes
    was released before it."""
    st = traced["stats"]
    passes = _spans(traced, "fin.pass")
    assert len(passes) == st["release_passes"] > 0
    assert sum(ev[3]["n"] for ev in passes) == JOBS * TASKS
    assert st["release_passes"] <= st["executed_tasks"] + st["held_tasks"]
    (fin,) = [ln for ln in traced["lines"] if ln[0][0].startswith("fin.")]
    releases = [ev for ev in fin if ev[0] == "fin.release"]
    inside = 0
    for _name, s, e, a in passes:
        mine = [r for r in releases if s <= r[1] and r[2] <= e]
        assert len(mine) == a["n"] >= 1
        inside += len(mine)
    assert inside == len(releases) == JOBS * TASKS
    finalized = 0
    for _name, s, _e, a in (ev for ev in fin if ev[0] == "fin.drain"):
        finalized += a["n"]
        assert a["n"] >= 1 and a["block"] in (0, 1)
        assert finalized <= sum(1 for r in releases if r[2] <= s)
    assert traced["samples"]["parsec_device_release_passes_total"] == \
        st["release_passes"]


def test_program_names_in_spans_and_modules(traced):
    pat = re.compile(r"^jit_parsec_(chain_)?[A-Z]+")
    programs = {ev[3]["program"] for name in ("mgr.dispatch", "warm.compile")
                for ev in _spans(traced, name)}
    assert programs and all(pat.match(p) for p in programs)
    ours = {m for m in traced["modules"] if m.startswith("jit_parsec_")}
    # what the spans call a program is what XLA calls its module
    assert {ev[3]["program"] for ev in _spans(traced, "mgr.dispatch")} <= ours
    assert not any(re.match(r"jit_(fn|target|prog)\b", m)
                   for m in traced["modules"])
    assert any(p.startswith("jit_parsec_chain_POTRF__TRSM_x")
               for p in programs)


def test_counters_reach_the_metrics_scrape(traced):
    assert traced["samples"]["parsec_device_launches_total"] == \
        traced["stats"]["launches"]
    assert traced["samples"]["parsec_device_held_tasks_total"] == \
        traced["stats"]["held_tasks"]


def test_no_profiler_session_same_counts():
    """The same jobs with no session: nothing raised, nothing recorded,
    and the counters that do not depend on how waves met are equal."""
    from jax.profiler import TraceAnnotation
    from parsec_tpu.prof.pins import TraceMePins
    assert not TraceAnnotation.is_enabled()
    st, _samples = _run_jobs(collect=True)
    # the last context closed: the collector's callback went with it
    assert not any(getattr(cb, "__func__", None) is TraceMePins._gc
                   for cb in gc.callbacks)
    assert st["faults"] == 0
    assert st["executed_tasks"] + st["held_tasks"] == JOBS * TASKS
    assert st["held_tasks"] == JOBS * (NT - 1)
    assert st["launches"] > 0 and st["starved_waits"] > 0
    assert 0 < st["release_passes"] <= JOBS * TASKS


def _module_name(jitted, *args):
    return re.search(r"module @(\w+)", jitted.lower(*args).as_text()).group(1)


def test_lowered_module_names():
    """single, fused wave and chain programs are named from the task
    classes they run, not from the Python function that wraps them."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.devices.xla import XlaKernel, _chain_jitted

    def gemm(C, L, R):
        return C - L @ R.T

    def potrf(T):
        return jnp.linalg.cholesky(T)

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    k = XlaKernel(gemm, ["C", "L", "R"], ["C", "L", "R"], ["C"], cls="GEMM")
    assert _module_name(k.jitted(False), x, x, x) == "jit_parsec_GEMM"
    assert _module_name(k.jitted_fused(False, 2), *[x] * 6) == \
        "jit_parsec_GEMM_x2"
    # the scope of each application is in the lowered text's locations
    assert "GEMM" in k.jitted_fused(False, 2).lower(*[x] * 6).as_text(
        debug_info=True)
    # a second class on the same function gets a program of its own name
    k2 = XlaKernel(gemm, ["C", "L", "R"], ["C", "L", "R"], ["C"], cls="SYRK")
    assert _module_name(k2.jitted(False), x, x, x) == "jit_parsec_SYRK"
    assert XlaKernel(gemm, ["C", "L", "R"], ["C", "L", "R"],
                     ["C"]).cls == "gemm"
    head = XlaKernel(potrf, ["T"], ["T"], ["T"], cls="POTRF")
    descs = [(("l", 0),)]
    wave = ((("l", 1), ("n", 0, "T"), ("l", 2)),) * 2
    jf = _chain_jitted(("test_lowered_module_names", 1), [head], descs,
                       k, wave)
    assert _module_name(jf, x, x, x) == "jit_parsec_chain_POTRF__GEMM_x2"
    jf = _chain_jitted(("test_lowered_module_names", 2), [head, head],
                       [descs[0], (("n", 0, "T"),)], None, ())
    assert _module_name(jf, x) == "jit_parsec_chain_POTRF2"


def test_span_pairs_late_args_and_silence():
    """An open span emits begin and end through PINS with the late
    arguments on the end; with no sink recording, or without an
    execution stream, ``open_span`` hands out SPAN_OFF and emits
    nothing."""
    from parsec_tpu.prof.pins import SPAN_OFF, open_span, spans_live
    seen = []
    with Context(nb_cores=1) as ctx:
        cb = lambda es, event, span: seen.append(  # noqa: E731
            (event, span.name, dict(span.args), span.late))
        ctx.pins_register("span_begin", cb)
        ctx.pins_register("span_end", cb)
        es = ctx.streams[0]
        # no profiler session: the installed sink says nobody records
        assert not spans_live(es)
        assert open_span(es, "mgr.stage_in") is SPAN_OFF
        live, ctx._span_live = ctx._span_live, lambda: True
        try:
            assert spans_live(es) and not spans_live(None)
            with open_span(es, "mgr.stage_in") as sp:
                sp.late = {"bytes_in": 3}
            open_span(es, "fin.drain", block=0).end(n=2)
            with open_span(None, "mgr.starved", dev="x") as off:
                off.late = {"ignored": 1}
            assert off is SPAN_OFF and off.late is None
        finally:
            ctx._span_live = live
            ctx.pins_unregister("span_begin", cb)
            ctx.pins_unregister("span_end", cb)
    assert seen == [
        ("span_begin", "mgr.stage_in", {}, None),
        ("span_end", "mgr.stage_in", {}, {"bytes_in": 3}),
        ("span_begin", "fin.drain", {"block": 0}, None),
        ("span_end", "fin.drain", {"block": 0}, {"n": 2})]
