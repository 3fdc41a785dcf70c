"""PINS: performance instrumentation hooks on the task lifecycle.

Rebuild of the reference's PINS framework (reference: parsec/mca/pins/ —
callback chains on task lifecycle events SELECT/EXEC/COMPLETE_EXEC/...
(pins.h:22-50) invoked by PARSEC_PINS macros in scheduling.c; the
``task_profiler`` module feeds the binary tracer).  The runtime already
emits events through ``ExecutionStream.pins`` (core/context.py); modules
here subscribe to them.

**Thread-state spans** (``open_span`` -> ``TraceMePins``): what a runtime
thread is doing, on the profiler's clock.  Beside its wall time (the
event's duration) a span may carry two integers the sink reads off the
emitting thread's CPU clock (``time.thread_time_ns``), for one span a
process every 10 ms, the threads taking turns (``TraceMePins``):

- ``cpu_ns``: the time the thread was ON a core between the span's begin
  and its end — Python executed under the interpreter lock, and C that
  runs with the lock released (the tail of a jitted call inside PJRT).
  Children nest in their parents, so a parent's ``cpu_ns`` includes its
  children's, exactly as its wall time does.
- ``cpu_end_ns``: the thread clock's absolute reading at the end, so
  ``cpu_end_ns - cpu_ns`` is its reading at the begin: a reader has the
  thread's clock at both ends of every span that carries the two, and
  the CPU a thread burned BETWEEN two readings is known whatever lay
  between (a worker's task bodies, the client's staging, the spans that
  went by without their turn).

``wall - cpu`` is everything else: waits for the interpreter lock, for a
Python lock or condition inside the span, blocking inside PJRT, and
preemption (small while the host has more cores than runnable threads).
So the CPU figure is exact for "what would a faster implementation have
to execute less of", and the wait figure is an UPPER bound of the
interpreter lock's share: tight for ``fin.release`` (pure Python), looser
for ``mgr.dispatch`` (the jitted call may block inside PJRT).  A reader
takes a thread's clock where it finds it: what a thread line ran between
two readings is exact, a single span's ``cpu_ns`` is a sample.
"""

from __future__ import annotations

import gc
import threading
from time import perf_counter as _now
from time import perf_counter_ns as _now_ns
from time import thread_time_ns as _thread_cpu_ns
from typing import Any, Dict, Optional

from parsec_tpu.prof.profiling import EV_END, EV_POINT, EV_START, Profile

#: lifecycle events emitted by the runtime (scheduling.py / context.py).
#: ``task_discard`` fires for tasks dropped by pool cancellation; the
#: ``job_*`` events are emitted by the job service (service/service.py)
#: with the Job as payload.
#: ``device_dispatch``/``device_done`` bracket a device task's
#: accelerator-pipeline residency (devices/xla.py, gated on the causal
#: tracer being installed).
#: ``span_begin``/``span_end`` bracket what a runtime THREAD is doing
#: (manager, completer, worker, fuse warmer, the caller's); the payload
#: is the :class:`Span`, whose names are listed in ``ALL_SPAN_NAMES``.
PINS_EVENTS = ("select", "exec_begin", "exec_end", "exec_async",
               "complete_exec", "task_discard",
               "device_dispatch", "device_done",
               "span_begin", "span_end",
               "job_submit", "job_start", "job_done")

#: thread-state spans (PERF.md section 3 says which metric reads each);
#: every name an ``open_span`` site uses is in one of the three tuples
#: below (``ALL_SPAN_NAMES``; tests/test_span_cpu.py holds the sites to
#: it).  Each lands in the trace with its own arguments and, when its
#: thread's turn at the clock has come, the sink's ``cpu_ns`` /
#: ``cpu_end_ns`` (the module's docstring: thread CPU time inside the
#: span, and the thread clock at its end; ``wall - cpu`` is lock waits,
#: blocking and preemption together).
#: These are what every context with an XLA device emits: ``mgr.*`` by a
#: device's manager threads, ``fin.*`` by its completer, ``worker.idle``
#: by a worker, ``warm.compile`` by the background fused-width compiler
#: (devices/xla.py, core/scheduling.py); ``ctx.startup`` (one a pool:
#: its ``startup()`` and the scheduling of what that returned) and
#: ``ctx.wait`` (the blocking part of ``Context.wait``) by the CALLER's
#: thread (core/context.py); ``gc.collect`` (``gen``) by whichever thread
#: tripped the interpreter's collector — every other Python thread stands
#: still for its length, so its WALL time is the number and it carries
#: no CPU reading (``TraceMePins._gc``).
SPAN_NAMES = ("mgr.starved", "mgr.launch", "mgr.pop_wave", "mgr.stage_in",
              "mgr.dispatch", "mgr.inflight_wait", "mgr.warm_wait",
              "fin.idle", "fin.pass", "fin.release", "fin.drain",
              "worker.idle", "warm.compile",
              "ctx.startup", "ctx.wait", "gc.collect")
#: the DTD front end's spans (dsl/dtd/insert.py), on the thread that runs
#: a pool's inserter (inside its ``ctx.startup``): one ``dtd.insert`` a
#: run of the inserter (``pool``, late ``n``), a ``dtd.window_wait`` a
#: stall on the insert window (``inflight``), ``dtd.flush`` around
#: ``data_flush_all``.  Only a DTD pool emits them
DTD_SPAN_NAMES = ("dtd.insert", "dtd.window_wait", "dtd.flush")
#: the ICI transport's spans (comm/ici.py), around each data movement
#: between chips, on whichever thread releases the deps (mostly a
#: completer, inside its ``fin.release``); arguments ``bytes``, ``ndst``.
#: Only a context that drives several chips emits them
ICI_SPAN_NAMES = ("ici.put", "ici.bcast", "ici.permute")
ALL_SPAN_NAMES = SPAN_NAMES + DTD_SPAN_NAMES + ICI_SPAN_NAMES
#: what the spans are called in the profiler's trace: ``parsec:mgr.launch``
SPAN_PREFIX = "parsec:"


class Span:
    """One begin/end pair on the emitting thread, opened by
    :func:`open_span`.  ``args`` are known when the span opens; ``late``
    (the keyword arguments of ``end``, or set before it) are those known
    only when it closes.  Begin and end happen on ONE thread, properly
    nested with the thread's other spans."""

    __slots__ = ("es", "name", "args", "late", "sink", "cpu0")

    def __init__(self, es, name: str, args: dict):
        self.es = es
        self.name = name
        self.args = args
        self.late = None
        self.sink = None        # the sink's own handle for this pair
        self.cpu0 = 0           # the sink's: thread CPU clock at the begin

    def end(self, **late) -> None:
        if late:
            self.late = late
        self.es.pins("span_end", self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class _SpanOff:
    """What :func:`open_span` hands out while nobody records: ends into
    nothing, and forgets what is set on it."""

    __slots__ = ()
    late = property(lambda self: None, lambda self, late: None)

    def end(self, **late) -> None:
        pass

    def __enter__(self) -> "_SpanOff":
        return self

    def __exit__(self, *exc) -> None:
        pass


SPAN_OFF = _SpanOff()


def spans_live(es) -> bool:
    """Whether a span opened now on ``es`` would be recorded.  Per-task
    emission sites ask this first (one C call) and skip building the
    span's arguments; ``es`` None (a device no task has reached yet)
    records nothing."""
    return es is not None and es.context._span_live()


def open_span(es, name: str, **args):
    """Open the thread-state span ``name`` on the calling thread: emits
    ``span_begin`` with the new :class:`Span` and returns it, to be
    closed by ``end()`` (or as a context manager) on the same thread;
    returns ``SPAN_OFF`` while no sink records (``Context._span_live``,
    the gate the sink installed: no profiler session, no cost but the
    probe)."""
    if es is None or not es.context._span_live():
        return SPAN_OFF
    span = Span(es, name, args)
    es.pins("span_begin", span)
    return span


def no_span_sink() -> bool:
    return False


#: the least time between two spans of a process whose thread clock the
#: sink reads (the threads take turns, ``TraceMePins._due``): the tick of
#: the chip host's thread clock, which costs a system call of 17-48 us
#: there (PERF.md section 6, PR 35) — under half a percent of the
#: interpreter lock, however many threads emit and whatever the host
_CLOCK_GAP_NS = 10_000_000


class TraceMePins:
    """The sink that puts the thread-state spans on the profiler's own
    clock: each :class:`Span` becomes a ``jax.profiler.TraceAnnotation``
    (TraceMe) named ``parsec:<span>`` on the emitting thread, its
    arguments the annotation's, so a trace taken with ``jax.profiler``
    shows what every runtime thread was doing beside the device's
    timeline.  TraceMe is its own gate: the sink makes
    ``TraceAnnotation.is_enabled`` the context's ``_span_live``, so with
    no profiler session a span costs that one probe and nothing is
    built or recorded.  Installed by every Context with an XLA device.

    **The thread clock is rationed by time.**  The sink reads the emitting
    thread's CPU clock for at most one span in ``_CLOCK_GAP_NS``, the
    threads taking turns (``_due``); that span leaves with ``cpu_ns`` and
    ``cpu_end_ns`` (the module's docstring), read INSIDE the annotation
    so that the sink's own cost is outside the figure: the last thing
    ``_begin`` does, the first thing ``_end`` does.  The spans between
    leave without the two integers.  A reader so has each thread's clock
    several times a second, which is what the per-thread figures need
    (benchmark/metrics/host_cpu_us_per_task.py); a span's own ``cpu_ns``
    is a sample, not a census.  Reading on EVERY span, as ISSUE 35 first
    asked, made the chip's traced window 40% slower (PERF.md section 6,
    PR 35).  Nothing else in the runtime reads that clock."""

    #: the collector's span is one callback on ``gc.callbacks`` a process,
    #: whatever the number of contexts: how many sinks are installed, the
    #: callback they share, and the collection under way (annotation,
    #: thread CPU clock at its start; collections do not nest)
    _gc_lock = threading.Lock()
    _gc_users = 0
    _gc_callback = None
    _gc_open = None

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        #: per emitting thread, when its clock may next be read
        self._next_read = {}

    def install(self, context) -> None:
        context.pins_register("span_begin", self._begin)
        context.pins_register("span_end", self._end)
        context._span_live = self._annotation.is_enabled
        cls = TraceMePins
        with cls._gc_lock:
            cls._gc_users += 1
            if cls._gc_callback is None:
                cls._gc_callback = self._gc
                gc.callbacks.append(cls._gc_callback)

    def uninstall(self, context) -> None:
        context._span_live = no_span_sink
        context.pins_unregister("span_begin", self._begin)
        context.pins_unregister("span_end", self._end)
        cls = TraceMePins
        with cls._gc_lock:
            cls._gc_users -= 1
            if cls._gc_users <= 0 and cls._gc_callback is not None:
                gc.callbacks.remove(cls._gc_callback)
                cls._gc_callback, cls._gc_users = None, 0

    def _due(self) -> bool:
        """Whether the calling thread may read its clock for the span
        that begins now; if so, its next turn is a gap away for every
        thread that takes turns."""
        now, ident = _now_ns(), threading.get_ident()
        turns = self._next_read
        if now < turns.get(ident, 0):
            return False
        turns[ident] = now + _CLOCK_GAP_NS * (len(turns) or 1)
        return True

    def _begin(self, es, event, span) -> None:
        due = self._due()                       # settled outside the span
        ann = span.sink = self._annotation(SPAN_PREFIX + span.name,
                                           **span.args)
        ann.__enter__()
        span.cpu0 = _thread_cpu_ns() if due else -1

    def _end(self, es, event, span) -> None:
        cpu0 = span.cpu0
        cpu1 = _thread_cpu_ns() if cpu0 >= 0 else 0
        ann = span.sink
        if ann is not None:
            span.sink = None
            if cpu0 >= 0:
                ann.set_metadata(cpu_ns=cpu1 - cpu0, cpu_end_ns=cpu1,
                                 **(span.late or {}))
            elif span.late:
                ann.set_metadata(**span.late)
            ann.__exit__(None, None, None)

    def _gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` entry: the span ``gc.collect`` (``gen``)
        around every collection of the interpreter's heap, on whichever
        thread tripped it; its WALL time is the number, so it takes no
        turn at the thread clock.  Emitted from the sink directly, not
        through ``open_span``: the collector's thread need not be a
        runtime thread, and has no execution stream to emit through.
        Untraced it costs one probe a collection."""
        cls = TraceMePins
        if phase == "start":
            if self._annotation.is_enabled():
                cls._gc_open = self._annotation(SPAN_PREFIX + "gc.collect",
                                                gen=info["generation"])
                cls._gc_open.__enter__()
            return
        ann, cls._gc_open = cls._gc_open, None
        if ann is not None:
            ann.__exit__(None, None, None)


class TaskProfilerPins:
    """Feed task execution intervals into the binary trace
    (reference: mca/pins/task_profiler).

    Hot-path discipline (reference: profiling.c writes one fixed-size
    record with no allocation): the per-event path caches the stream
    buffer per es and the dictionary key per task class, and by default
    records NO Python info payload — info-less events land straight in
    the native C++ packed buffer.  ``with_locals=True`` restores the
    per-event ``{"locals": ...}`` payload (richer traces, Python-path
    cost; the reference's converter-string info analog).
    """

    def __init__(self, profile: Profile, with_locals: bool = False):
        self.profile = profile
        self.with_locals = with_locals
        self._sbs: Dict[int, Any] = {}         # th_id -> StreamBuffer
        self._keys: Dict[str, int] = {}        # class name -> dict key
        self._tagged: list = []                # objects carrying caches
        # hot-path bindings: the raw event-id counter and (per stream,
        # below) the C sink's interval FASTCALL — each skipped Python
        # frame is ~0.1us of the 1us/task tracer budget
        ids = getattr(profile, "_event_ids", None)
        self._next_eid = ids.__next__ if ids is not None \
            else profile.next_event_id

    def install(self, context) -> None:
        # one task_profiler per context: the interval state rides the
        # shared Task.prof slot, so two instances would corrupt each
        # other's streams (the reference's task_profiler is likewise a
        # per-process singleton — PINS modules are MCA-selected once)
        cur = getattr(context, "_task_profiler", None)
        if cur is not None and cur is not self:
            raise RuntimeError(
                "a TaskProfilerPins is already installed on this "
                "context; uninstall it first")
        context._task_profiler = self
        context.pins_register("exec_begin", self._begin)
        context.pins_register("exec_end", self._end)
        context.pins_register("complete_exec", self._complete)

    def uninstall(self, context) -> None:
        if getattr(context, "_task_profiler", None) is self:
            context._task_profiler = None
        context.pins_unregister("exec_begin", self._begin)
        context.pins_unregister("exec_end", self._end)
        context.pins_unregister("complete_exec", self._complete)
        # drop the hot-path caches planted on streams/task classes so an
        # uninstalled profiler (and its Profile's event buffers) does not
        # stay reachable for the life of the context
        for obj, attr in self._tagged:
            if getattr(obj, attr, (None,))[0] is self:
                try:
                    delattr(obj, attr)
                except AttributeError:
                    pass
        self._tagged.clear()

    def _sb(self, es):
        sb = self._sbs.get(es.th_id)
        if sb is None:
            sb = self._sbs[es.th_id] = \
                self.profile.stream(es.th_id, f"worker-{es.th_id}")
        # hot-path cache, owner-tagged so a second profiler instance
        # on the same context cannot reuse the wrong stream; the third
        # slot is the C sink's interval FASTCALL (or None), called
        # directly from _end/_complete — no Python frame.  The tag is
        # (re)planted on EVERY slow-path call, not only on stream
        # creation: _end/_complete re-read es._prof_sb after calling
        # here, and a tag left behind by a previous profiler must not
        # route our END records into its streams
        cs = es.__dict__.get("_prof_sb")
        if cs is None or cs[0] is not self or cs[1] is not sb:
            es._prof_sb = (self, sb, getattr(sb, "_sink_interval", None))
            self._tagged.append((es, "_prof_sb"))
        return sb

    def _key(self, name: str) -> int:
        k = self._keys.get(name)
        if k is None:
            k = self._keys[name] = self.profile.add_event_class(name).key
        return k

    # The per-task state rides the Task.prof slot as
    # [dict key, event id, object id, closed-by-end, taskpool id,
    # begin-timestamp] — no module-level dict/set traffic on the hot
    # path (reference: profiling.c's record path touches only the
    # per-thread buffer; sp-perf.c is the bar).  Info-less intervals
    # DEFER the begin record: _begin only captures a perf_counter()
    # read, and the closing edge writes BOTH records through ONE C
    # crossing (StreamBuffer.interval -> pinsext interval, VERDICT r5
    # #5).  Events carrying an info payload keep the eager two-record
    # path.

    def _begin(self, es, event, task) -> None:
        if not self.profile.enabled:
            return
        tc = task.task_class
        ck = tc.__dict__.get("_prof_key")
        if ck is None or ck[0] is not self:
            k = self._key(tc.name)
            tc._prof_key = (self, k)
            self._tagged.append((tc, "_prof_key"))
        else:
            k = ck[1]
        eid = self._next_eid()
        oid = hash(task.key)
        tpid = task.taskpool.taskpool_id
        if not self.with_locals:
            # the timestamp is the last thing taken: it marks the edge
            task.prof = [k, eid, oid, False, tpid, _now()]
            return
        cs = es.__dict__.get("_prof_sb")
        sb = cs[1] if (cs is not None and cs[0] is self) else self._sb(es)
        task.prof = [k, eid, oid, False, tpid, None]
        sb.trace(k, EV_START, tpid, eid, oid,
                 {"locals": dict(task.locals)})

    def _end(self, es, event, task) -> None:
        p = task.prof
        if p is None or not self.profile.enabled:
            return
        p[3] = True
        cs = es.__dict__.get("_prof_sb")
        if cs is None or cs[0] is not self:
            self._sb(es)
            cs = es._prof_sb
        if p[5] is not None and cs[2] is not None:
            cs[2](p[0], p[4], p[1], p[2], p[5], EV_START, EV_END)
        elif p[5] is not None:
            cs[1].interval(p[0], p[4], p[1], p[2], p[5])
        else:
            cs[1].trace(p[0], EV_END, p[4], p[1], p[2])

    def _complete(self, es, event, task) -> None:
        # device (ASYNC) tasks never ran exec_end on a worker stream:
        # close their interval at completion
        p = task.prof
        if p is None:
            return
        task.prof = None
        if p[3] or not self.profile.enabled:    # closed by _end already
            return
        cs = es.__dict__.get("_prof_sb")
        if cs is None or cs[0] is not self:
            self._sb(es)
            cs = es._prof_sb
        if p[5] is not None and cs[2] is not None:
            cs[2](p[0], p[4], p[1], p[2], p[5], EV_START, EV_END)
        elif p[5] is not None:
            cs[1].interval(p[0], p[4], p[1], p[2], p[5])
        else:
            cs[1].trace(p[0], EV_END, p[4], p[1], p[2])


def install_task_profiler(context, profile: Profile,
                          with_locals: bool = False) -> TaskProfilerPins:
    mod = TaskProfilerPins(profile, with_locals=with_locals)
    mod.install(context)
    return mod


class StealCounterPins:
    """Per-stream select counters (reference: mca/pins/print_steals)."""

    def __init__(self):
        self.selects: Dict[int, int] = {}

    def install(self, context) -> None:
        context.pins_register("select", self._select)

    def uninstall(self, context) -> None:
        context.pins_unregister("select", self._select)

    def _select(self, es, event, task) -> None:
        self.selects[es.th_id] = self.selects.get(es.th_id, 0) + 1

    def display(self) -> str:
        total = sum(self.selects.values())
        per = " ".join(f"es{t}={n}" for t, n in sorted(self.selects.items()))
        return f"selects total={total} {per}"


class GaugesPins:
    """Bridge to the live gauges (reference: the alperf/papi_sde-style
    modules exporting runtime counters)."""

    def __init__(self):
        from parsec_tpu.prof.gauges import Gauges
        self.gauges = Gauges()

    def install(self, context) -> None:
        self.gauges.install(context)

    def uninstall(self, context) -> None:
        self.gauges.uninstall(context)

    def display(self) -> str:
        return str(self.gauges.snapshot())


class IteratorsCheckerPins:
    """Successor-iteration validator (reference:
    mca/pins/iterators_checker — re-derives a completed task's successor
    set and cross-checks it against the dependencies the engine actually
    delivered; valuable precisely because this runtime's dep engine is
    hand-written per front-end).  Per completed PTG task it re-walks the
    flow expressions (iterate_successors) and compares with the
    ``deliver_dep`` calls observed through the PINS hook: a lost or
    extra delivery is reported as a context error.  Dynamic (DTD) pools
    resolve successors from their runtime graph, not flow expressions,
    and are skipped."""

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        #: id(task) -> set of (succ class name, succ key, flow name)
        self._delivered: Dict[int, set] = {}
        self.checked = 0
        self.flagged = 0

    def install(self, context) -> None:
        context.pins_register("deliver_dep", self._deliver)
        context.pins_register("complete_exec", self._complete)

    def uninstall(self, context) -> None:
        context.pins_unregister("deliver_dep", self._deliver)
        context.pins_unregister("complete_exec", self._complete)

    def _deliver(self, es, event, payload) -> None:
        task, succ_tc, succ_locals, dflow = payload
        with self._lock:
            self._delivered.setdefault(id(task), set()).add(
                (succ_tc.name, succ_tc.make_key(succ_locals), dflow))

    def _expected(self, task) -> set:
        from parsec_tpu.core.task import ToTask
        tp = task.taskpool
        myrank = tp.context.rank if tp.context else 0
        want = set()
        for flow in task.task_class.flows:
            for dep in flow.active_outputs(task.locals):
                end = dep.end
                if not isinstance(end, ToTask):
                    continue
                succ_tc = tp.task_classes[end.task_class]
                for succ_locals in end.instances(task.locals):
                    # dep instances carry free params only; fill derived
                    # locals before keying/ranking (mirrors release_deps,
                    # else every derived-local successor class silently
                    # escapes validation via the except below)
                    succ_locals = succ_tc.complete_locals(succ_locals)
                    if succ_tc.rank_of(succ_locals) != myrank:
                        continue
                    want.add((succ_tc.name, succ_tc.make_key(succ_locals),
                              end.flow))
        return want

    def _complete(self, es, event, task) -> None:
        if getattr(task.taskpool, "dynamic_release", None) is not None:
            return          # DTD: successors come from the runtime graph
        with self._lock:
            got = self._delivered.pop(id(task), set())
        try:
            want = self._expected(task)
        except Exception:
            return          # un-evaluable expressions: nothing to check
        self.checked += 1
        if got != want:
            self.flagged += 1
            missing = want - got
            extra = got - want
            es.context.record_error(AssertionError(
                f"iterators_checker: {task} successor mismatch — "
                f"missing deliveries: {sorted(missing)}; "
                f"unexpected deliveries: {sorted(extra)}"), task)

    def display(self) -> str:
        return f"iterators_checker checked={self.checked} " \
               f"flagged={self.flagged}"


#: name -> zero-arg constructor; the MCA-selected modules of ``--mca
#: pins a,b`` (reference: the pins framework's module list, pins_init.c)
_MODULES = {
    "print_steals": StealCounterPins,
    "alperf": GaugesPins,
    "iterators_checker": IteratorsCheckerPins,
}


def install_selected(context) -> list:
    """Install the PINS modules named by ``--mca pins`` (comma list) on
    a context; returns the module instances (reference: pins_init
    iterating the selected module list).  Unknown names warn rather than
    fail — a missing instrumentation module must not kill the run."""
    from parsec_tpu.utils.mca import params
    from parsec_tpu.utils.output import warning
    params.register("pins", "",
                    "comma-separated PINS instrumentation modules to "
                    "install at context init "
                    f"(available: {', '.join(sorted(_MODULES))})")
    spec = str(params.get("pins", "") or "").strip()
    mods = []
    if not spec:
        return mods
    for name in spec.split(","):
        name = name.strip()
        if not name:
            continue
        ctor = _MODULES.get(name)
        if ctor is None:
            warning("unknown PINS module %r (available: %s)", name,
                    ", ".join(sorted(_MODULES)))
            continue
        mod = ctor()
        mod.install(context)
        mods.append(mod)
    return mods
