#!/usr/bin/env python
"""Host canaries: the probes ``tools/premerge_bench.sh`` gates, run on
the CPU.  Counts and canaries, not statements about speed: they time
Python, the native scheduler and the transports of whatever container
runs them, and exist to catch a behaviour regression (a native path
gone inactive, tasks bailing out of the C chain, a tracing or telemetry
plane that got dearer per task) before a change merges.

The chip is measured elsewhere and by nothing here: ``BENCHMARK.json``
and ``benchmark/`` are the benchmark (``python -m benchmark.run
--workload <cell> --seed S --seconds 10``; ``benchmark/README.md``),
``PERF.md`` is its record, and ``chip_smoke.py`` is the stand-alone
proof that the main path starts on a chip.

``PARSEC_BENCH_APP`` names the probe (``_AUX_MODES``: tasks, ntasks,
rtt, bw, aggregate, telemetry, journal, tracer, fabric, release, launch);
anything else, or nothing, is an error.  A probe prints exactly ONE
JSON line on stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "device": {"platform": ..., "kind": ..., "count": N}, ...}
``device`` is what JAX reports for the process; ``vs_baseline`` is the
value against the probe's self-declared target in ``_AUX_MODES`` (the
reference publishes no numbers: BASELINE.md).
"""

import json
import os
import sys
import threading
import time

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _device() -> dict:
    """What JAX reports for this process — carried by every JSON line."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _emit(obj: dict) -> None:
    print(json.dumps({**obj, "device": _device()}))


# ---------------------------------------------------------------------------
# The probes (SURVEY.md section 6; reference harnesses:
# tests/apps/pingpong/rtt.jdf, bandwidth.jdf,
# tests/profiling-standalone/sp-perf.c).
# ---------------------------------------------------------------------------

def _pp_worker(ctx, rank, nranks, nbytes, hops):
    from parsec_tpu.apps.pingpong import run_pingpong
    trace_dir = os.environ.get("PARSEC_BENCH_TRACE_DIR")
    mod = tr = prof = None
    run_pingpong(ctx, nbytes, 8)          # warm the link + code paths
    if trace_dir:
        # install AFTER the warmup: the embedded attribution must
        # describe the measured run, not the warmup pool + gap
        from parsec_tpu.prof.causal import install_causal_tracer
        from parsec_tpu.prof.pins import install_task_profiler
        from parsec_tpu.prof.profiling import Profile
        prof = Profile(f"bench-pp-r{rank}")
        mod = install_task_profiler(ctx, prof)
        tr = install_causal_tracer(ctx, prof)
        la = getattr(ctx.metrics, "liveattr", None) \
            if ctx.metrics is not None else None
        if la is not None:
            la.reset()   # the online window = the measured run
    before = ctx.comm.stats()
    res = run_pingpong(ctx, nbytes, hops)
    after = ctx.comm.stats()
    delta = {k: after[k] - before[k] for k, v in after.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)
             and isinstance(before.get(k), (int, float))}
    delta["transport"] = after.get("transport")
    # which native paths were live on this rank (the r11 A/B record):
    # a 1 here with zero frames_parsed_native movement is a no-op
    # native path — exactly what the premerge pairing exists to catch
    delta["sched_native"] = 1 if ctx.scheduler.name == "native" else 0
    if trace_dir:
        mod.uninstall(ctx)
        tr.uninstall(ctx)
        prof.dump(os.path.join(trace_dir, f"rank{rank}.ptt"))
        la = getattr(ctx.metrics, "liveattr", None) \
            if ctx.metrics is not None else None
        if la is not None:
            # the ONLINE attribution section rides home next to the
            # trace so run_rtt_bench can embed online-vs-offline
            # agreement in the JSON line (numeric-filtered out of the
            # protocol aggregation)
            delta["liveattr_section"] = la.section()
    return res[0], res[1], delta


def _trace_attribution(trace_dir) -> dict:
    """Merge the per-rank bench traces and fold the critical-path
    attribution into the bench JSON line (informational: the buckets
    reshuffle with host load, and the tracer overhead gate lives in
    premerge_bench.sh)."""
    import glob as _glob
    from parsec_tpu.prof import critpath
    paths = sorted(_glob.glob(os.path.join(trace_dir, "rank*.ptt")))
    att = critpath.attribution(paths)
    return {"makespan_s": round(att["makespan"], 6),
            "coverage": att["coverage"],
            "flows": att["flows"],
            **{k: round(v, 6) for k, v in att["buckets"].items()}}


def _protocol_breakdown(res) -> dict:
    """Aggregate the per-rank comm stats deltas of a pingpong run into
    the JSON protocol breakdown: frames + syscalls per MB moved, and
    the eager/rdv/inline activation mix."""
    agg: dict = {}
    for _hop, _mbps, delta in res:
        for k, v in delta.items():
            if isinstance(v, (int, float)):
                agg[k] = agg.get(k, 0) + v
    mb = max(agg.get("bytes_sent", 0) + agg.get("bytes_recv", 0), 1) / 1e6
    out = {
        "transport": res[0][2].get("transport"),
        "sched_native": 1 if agg.get("sched_native") else 0,
        "frames_parsed_native": int(agg.get("frames_parsed_native", 0)),
        "frames_sent": int(agg.get("frames_sent", 0)),
        "act_eager": int(agg.get("act_eager", 0)),
        "act_rdv": int(agg.get("act_rdv", 0)),
        "act_inline": int(agg.get("act_inline", 0)),
        "coalesced_msgs": int(agg.get("coalesced_msgs", 0)),
        "wakeups": int(agg.get("wakeups", 0)),
        "partial_writes": int(agg.get("partial_writes", 0)),
        "syscalls_per_mb": round(
            (agg.get("syscalls_send", 0) + agg.get("syscalls_recv", 0))
            / mb, 3),
    }
    return out


def run_rtt_bench(hops: int = 400):
    """2-rank task round-trip latency over loopback (rtt.jdf analog):
    seconds per dataflow hop, reported in microseconds.

    ``PARSEC_BENCH_TRACE=1`` additionally traces both ranks, merges the
    traces, and embeds the critical-path attribution (exec/queue/comm/
    idle buckets, prof/critpath.py) in the JSON line — the per-hop time
    breakdown PR 3 reconstructed by hand, now tool-produced."""
    from parsec_tpu.comm.launch import run_distributed
    extras = {}
    trace_dir = None
    traced_env = {}
    if os.environ.get("PARSEC_BENCH_TRACE", "0") == "1":
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="bench-rtt-trace-")
        os.environ["PARSEC_BENCH_TRACE_DIR"] = trace_dir
        # the traced leg also arms the full online split (stride 1 +
        # the queue-wait/exec hooks) so the embedded liveattr section
        # is comparable bucket-for-bucket with the offline dict — an
        # opt-in diagnostic leg, like the tracer itself
        for k in ("PARSEC_MCA_METRICS_SAMPLE",
                  "PARSEC_MCA_METRICS_QUEUE_WAIT"):
            traced_env[k] = os.environ.get(k)
            os.environ[k] = "1"
    try:
        res = run_distributed(_pp_worker, 2, args=(8, hops), timeout=300)
    finally:
        os.environ.pop("PARSEC_BENCH_TRACE_DIR", None)
        for k, v in traced_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    value = float(np.mean([r[0] for r in res])) * 1e6
    if trace_dir:
        import shutil
        try:
            extras["attribution"] = _trace_attribution(trace_dir)
        except Exception as exc:   # the headline must still publish
            log(f"rtt trace attribution FAILED: {exc!r}")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        try:
            extras.update(_online_attribution(
                res, extras.get("attribution")))
        except Exception as exc:
            log(f"rtt online attribution FAILED: {exc!r}")
    return value, {"protocol": _protocol_breakdown(res),
                   "host": _host_info(), **extras}


def _online_attribution(res, offline) -> dict:
    """Fold the per-rank liveattr sections into the ONLINE split and —
    when the offline dict landed — the per-bucket agreement in
    percentage points (informational: the bound of 10pp/bucket is
    enforced by tests/test_liveattr.py on the same leg)."""
    from parsec_tpu.prof import liveattr as la_mod
    sections = {i: r[2].get("liveattr_section")
                for i, r in enumerate(res)
                if r[2].get("liveattr_section")}
    if not sections:
        return {}
    merged = la_mod.merge_sections(sections)
    ex, qu = la_mod._bucket_sums(list(merged["recs"].values()))
    online = la_mod.telescope(merged["window_s"], ex, qu,
                              merged["comm_s"])
    out = {"attribution_online": online}
    ms = (offline or {}).get("makespan_s") or 0.0
    if ms and online["elapsed"]:
        out["attribution_agreement_pp"] = {
            b: round(abs(offline.get(b, 0.0) / ms
                         - online[b] / online["elapsed"]) * 100, 1)
            for b in ("exec", "queue", "comm", "idle")}
    return out


def run_bw_bench(nbytes: int = 8 << 20, hops: int = 32):
    """2-rank dataflow edge bandwidth (bandwidth.jdf analog), MB/s.

    The eager/rendezvous switchover is a transport-tuning knob (MPI
    implementations tune it per interconnect); on loopback the extra
    GET round-trips of rendezvous cost ~30% at this payload size, so
    the bench declares eager coverage for its own message size — the
    same choice bandwidth.jdf runs make via MCA."""
    from parsec_tpu.comm.launch import run_distributed
    prior = os.environ.get("PARSEC_MCA_comm_eager_limit")
    prior_ad = os.environ.get("PARSEC_MCA_comm_adaptive_eager")
    prior_ring = os.environ.get("PARSEC_MCA_COMM_SHM_RING_MB")
    os.environ.setdefault("PARSEC_MCA_comm_eager_limit",
                          str(nbytes * 2))
    # the probe PINS its protocol: adaptation would let a loaded host
    # demote hops to rendezvous mid-run and flip what is being measured
    os.environ.setdefault("PARSEC_MCA_comm_adaptive_eager", "0")
    # shm: size the ring for the probe's payload class (4x message —
    # measured r11: 8MB ring 379, 16MB 538, 32MB 708 MB/s at 8MB
    # payloads; a ring the producer can stream a whole frame into
    # without interleaving the consumer's parse wins).  The same MCA
    # tuning the eager pin above is; no-op on the TCP transports.
    os.environ.setdefault("PARSEC_MCA_COMM_SHM_RING_MB",
                          str(max(8, (nbytes * 4) >> 20)))
    try:
        res = run_distributed(_pp_worker, 2, args=(nbytes, hops),
                              timeout=300)
    finally:
        for key, val in (("PARSEC_MCA_comm_eager_limit", prior),
                         ("PARSEC_MCA_comm_adaptive_eager", prior_ad),
                         ("PARSEC_MCA_COMM_SHM_RING_MB", prior_ring)):
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
    value = float(np.mean([r[1] for r in res]))
    return value, {"protocol": _protocol_breakdown(res),
                   "host": _host_info()}


def _host_info() -> dict:
    """Host core inventory for the bw/rtt JSON lines (the 'evloop frees
    a core' claim is only testable where cores >= 2, so every datapoint
    records where it was measured)."""
    try:
        avail = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        avail = os.cpu_count() or 1
    return {"cpu_count": os.cpu_count() or 1, "cores_available": avail}


def _empty_pool(n):
    from parsec_tpu.dsl.ptg.api import PTG, Range
    p = PTG("empty", N=n)
    p.task("E", i=Range(0, n - 1)).flow("x", "CTL").body(lambda: None)
    return p.build()


def _tasks_budget(ctx, total_us: float, k: int = 4000):
    """Staged per-task budget breakdown (µs/task), so a future tasks/s
    regression localizes to a stage instead of showing up as one opaque
    headline drop:

      construction  task-object build (C build_range or Task.__init__)
      termdet       one LOCKED counter move — the cost the per-worker
                    batching amortizes away (termdet_batch)
      dispatch      one complete_exec PINS fan-out (metrics et al.)
      progress      everything else: end-to-end per-task budget minus
                    the measured construction share (scheduling +
                    prepare/execute/complete chain, incl. the termdet
                    and dispatch shares above)

    Micro-measured in-process on the bench context; informational."""
    from parsec_tpu.core.task import Task, TaskClass
    from parsec_tpu.core.taskpool import ParameterizedTaskpool
    from parsec_tpu.core.termdet import LocalTermdet
    tp = ParameterizedTaskpool("budget-probe")
    tc = tp.add_task_class(TaskClass(
        "Bgt", params=[("i", lambda g, l: range(k))],
        body=lambda es, task: None))
    vt = tc.native_vt()
    t0 = time.perf_counter()
    if vt is not None:
        tasks = vt.build_range("i", 0, k, 1)
    else:
        tasks = [Task(tc, tp, {"i": j}) for j in range(k)]
    construction = (time.perf_counter() - t0) / k * 1e6
    td = LocalTermdet()
    td.monitor(tp, lambda: None)   # NOT_READY: counters move, no fire
    t0 = time.perf_counter()
    for _ in range(k):
        td.taskpool_addto_nb_tasks(tp, 1)
        td.taskpool_addto_nb_tasks(tp, -1)
    termdet = (time.perf_counter() - t0) / (2 * k) * 1e6
    td.unmonitor(tp)
    cbs = ctx._pins.get("complete_exec") or []
    es = ctx.streams[0]
    task = tasks[0]
    # advance the stream's retired count per iteration (restored
    # after): the metrics handler samples on nb_tasks_done % stride,
    # and a FROZEN count makes the probe bimodal — all-sampled when
    # the bench happened to end on a stride point, all-unsampled
    # otherwise.  Walking it measures the production-amortized cost.
    saved_nb = es.nb_tasks_done
    t0 = time.perf_counter()
    for _ in range(k):
        for cb in cbs:
            cb(es, "complete_exec", task)
        es.nb_tasks_done += 1
    dispatch = (time.perf_counter() - t0) / k * 1e6
    es.nb_tasks_done = saved_nb
    return {"construction_us": round(construction, 3),
            "termdet_us": round(termdet, 3),
            "dispatch_us": round(dispatch, 3),
            "progress_us": round(max(0.0, total_us - construction), 3)}


def _bail_snapshot():
    """Current per-reason fast-path bailout counters ({} when the C
    extension is absent) — benches report the DELTA across their timed
    window so a coverage regression (tasks silently popping back to
    Python) shows in the JSON next to the throughput it cost."""
    try:
        from parsec_tpu.native import load_schedext
        se = load_schedext()
        if se is not None and hasattr(se, "bailout_stats"):
            return dict(se.bailout_stats())
    except Exception:
        pass
    return {}


def _bail_delta(before, after):
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] - before.get(k, 0)}


def run_tasks_bench(n: int = 20000):
    """Empty-body task throughput, tasks/s — the DAG-scheduling
    efficiency proxy (insert+wait over n no-op tasks; every runtime
    layer except the body is on the clock).

    ``PARSEC_BENCH_TRACE=1`` runs the same probe with the FULL tracing
    stack installed (binary task profiler + causal tracer: queue-wait
    spans, dep edges) — the premerge tracer-overhead gate compares this
    against the default untraced run (tools/premerge_bench.sh)."""
    from parsec_tpu.core.context import Context
    trace = os.environ.get("PARSEC_BENCH_TRACE", "0") == "1"
    with Context(nb_cores=int(os.environ.get("PARSEC_BENCH_CORES", 4))) \
            as ctx:
        mod = tr = None
        if trace:
            from parsec_tpu.prof.causal import install_causal_tracer
            from parsec_tpu.prof.pins import install_task_profiler
            from parsec_tpu.prof.profiling import Profile
            prof = Profile("bench-tasks")
            mod = install_task_profiler(ctx, prof)
            tr = install_causal_tracer(ctx, prof)
        ctx.add_taskpool(_empty_pool(n // 10))   # warm
        ctx.wait()
        bail0 = _bail_snapshot()
        t0 = time.perf_counter()
        ctx.add_taskpool(_empty_pool(n))
        ctx.wait()
        dt = time.perf_counter() - t0
        bailouts = _bail_delta(bail0, _bail_snapshot())
        budget = _tasks_budget(ctx, dt / n * 1e6)
        if mod is not None:
            mod.uninstall(ctx)
            tr.uninstall(ctx)
        native = {"sched_native":
                  1 if ctx.scheduler.name == "native" else 0}
        doorbell = {"suppressed": ctx._db_suppressed}
    return n / dt, {"native": native, "budget": budget,
                    "doorbell": doorbell, "bailouts": bailouts}


def _chain_pool(nc: int, nb: int):
    """``nc`` independent RW data chains of length ``nb`` — the
    NON-trivial throughput workload: every task carries a real data
    flow (FromDesc binding at k==0, FromTask + local ToTask delivery
    walk inside each chain), so the whole prepare/release/complete
    machinery is on the clock, not just pop+hook."""
    from parsec_tpu.dsl.ptg import DATA, IN, OUT, PTG, Range, TASK
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    A = VectorTwoDimCyclic(1, nc).from_array(np.zeros(nc, np.float32))
    g = PTG("chains", NC=nc, NB=nb)
    g.task("S", c=Range(0, nc - 1), k=Range(0, nb - 1)) \
        .affinity(lambda c, k: A(c)) \
        .flow("T", "RW",
              IN(DATA(lambda c, k: A(c)), when=lambda c, k: k == 0),
              IN(TASK("S", "T", lambda c, k: dict(c=c, k=k - 1)),
                 when=lambda c, k: k > 0),
              OUT(TASK("S", "T", lambda c, k: dict(c=c, k=k + 1)),
                  when=lambda c, k, NB=nb: k < nb - 1)) \
        .body(lambda T, c, k: T.__iadd__(1.0) and None)
    return g.build(), A


def run_ntasks_bench(n: int = 12000):
    """NON-trivial task throughput, tasks/s: independent RW chains
    where every task binds real data and releases a local successor —
    the workload the r17 extended C progress chain (per-class binding
    tables + C-side delivery walk) exists for.  The trivial probe
    (``tasks``) bounds pure scheduling; this probe bounds the full
    dataflow path.  ``bailouts`` in the JSON must stay empty on the
    native path — any non-zero reason means tasks fell back to Python
    and the number no longer measures the C chain."""
    from parsec_tpu.core.context import Context
    nb = int(os.environ.get("PARSEC_BENCH_CHAIN_LEN", 24))
    nc = max(1, n // nb)
    n = nc * nb
    with Context(nb_cores=int(os.environ.get("PARSEC_BENCH_CORES", 4))) \
            as ctx:
        wp, _ = _chain_pool(max(1, nc // 10), nb)   # warm
        ctx.add_taskpool(wp)
        ctx.wait()
        tp, A = _chain_pool(nc, nb)
        bail0 = _bail_snapshot()
        t0 = time.perf_counter()
        ctx.add_taskpool(tp)
        ctx.wait()
        dt = time.perf_counter() - t0
        bailouts = _bail_delta(bail0, _bail_snapshot())
        native = {"sched_native":
                  1 if ctx.scheduler.name == "native" else 0}
        # every chain ran end to end: the throughput number is only
        # valid if the dataflow actually happened
        vals = np.asarray(A(0).resolve().copy_on(0).payload)
        if not np.allclose(vals, float(nb)):
            raise RuntimeError(
                f"ntasks bench: chain results wrong (want {nb}, got "
                f"{vals[:4]}...) — throughput number is invalid")
    return n / dt, {"native": native, "bailouts": bailouts,
                    "chains": {"nc": nc, "nb": nb}, "host": _host_info()}


def _agg_worker(ctx, rank: int, nranks: int, n: int):
    """Per-rank body of the aggregate probe: the trivial headline
    workload with a live RemoteDepEngine attached — every task has
    zero remote successors, so r17 comm-attached fast-complete must
    keep them ALL on the C chain (bailouts delta reports whether it
    did)."""
    ctx.add_taskpool(_empty_pool(max(200, n // 10)))   # warm
    ctx.wait(timeout=120)
    bail0 = _bail_snapshot()
    t0 = time.perf_counter()
    ctx.add_taskpool(_empty_pool(n))
    ctx.wait(timeout=300)
    dt = time.perf_counter() - t0
    return (n / dt, dt, _bail_delta(bail0, _bail_snapshot()),
            1 if ctx.scheduler.name == "native" else 0)


def run_aggregate_bench(n: int = 12000):
    """Multi-rank AGGREGATE task throughput over shm, tasks/s — the
    first whole-host scheduling-capacity number: N same-host ranks
    (self-scaled to the core count, floor 2 so the 1-core CI container
    still exercises the comm-attached path) each run the trivial
    workload with comm attached; the headline is the sum of per-rank
    rates, with per-rank scaling efficiency vs a solo comm-attached
    rank riding along.  On an oversubscribed host efficiency measures
    time-slicing fairness, not speedup — the JSON records the core
    inventory so readers can tell."""
    from parsec_tpu.comm.launch import run_distributed
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count() or 1
    nranks = int(os.environ.get("PARSEC_BENCH_AGG_RANKS",
                                max(2, min(cores, 8))))
    nb_cores = max(1, cores // nranks)
    prior = os.environ.get("PARSEC_MCA_COMM_TRANSPORT")
    os.environ["PARSEC_MCA_COMM_TRANSPORT"] = "shm"
    try:
        solo = run_distributed(_agg_worker, 1, args=(n,),
                               nb_cores=nb_cores, timeout=600)
        res = run_distributed(_agg_worker, nranks, args=(n,),
                              nb_cores=nb_cores, timeout=600)
    finally:
        if prior is None:
            os.environ.pop("PARSEC_MCA_COMM_TRANSPORT", None)
        else:
            os.environ["PARSEC_MCA_COMM_TRANSPORT"] = prior
    rates = [r[0] for r in res]
    # multi-core-only leg: the true scaling curve needs >= 1 core per
    # rank; on a smaller host the probe still runs as an N=2 smoke
    # (the comm-attached C-chain coverage is what it checks there) and
    # the JSON says WHY the scaling number is not a scaling number
    skipped = {}
    if cores < nranks:
        skipped["full_scale"] = (
            f"{cores} core(s) < {nranks} ranks: N=2 smoke only — "
            "ranks time-slice, scaling_efficiency measures fairness, "
            "not speedup")
    # aggregate over the SLOWEST rank's wall time, not a sum of rates:
    # on an oversubscribed host the ranks' windows differ wildly and a
    # rate sum double-counts the slices — this is the number a user
    # sending nranks*n tasks at the host actually experiences
    aggregate = nranks * n / max(r[1] for r in res)
    solo_rate = solo[0][0]
    eff = (aggregate / nranks / solo_rate) if solo_rate else 0.0
    bailouts: dict = {}
    for r in res:
        for k, v in r[2].items():
            bailouts[k] = bailouts.get(k, 0) + v
    return aggregate, {
        "ranks": nranks,
        "nb_cores_per_rank": nb_cores,
        "per_rank_tasks_s": [round(r, 1) for r in rates],
        "solo_tasks_s": round(solo_rate, 1),
        "scaling_efficiency": round(eff, 4),
        "native": {"sched_native": res[0][3]},
        "bailouts": bailouts,
        "host": _host_info(),
        **({"skipped": skipped} if skipped else {}),
    }


def _overhead_probe(knobs, label: str, n: int = 20000):
    """Shared armed-vs-off overhead harness (the telemetry AND journal
    gates): interleaved back-to-back pairs of the null-task probe with
    every knob in ``knobs`` set to 1 (armed) vs 0 (off).

    The reported value is the MINIMUM pair ratio — the clock
    estimator's min-RTT principle applied to an overhead gate:
    host-load noise on a shared CI core spans ~10% run to run (an
    order above the effect measured) and contaminates individual
    pairs in either direction, but a REAL regression shows in every
    pair, so the cleanest pair bounds the true overhead from below
    while staying immune to one loaded window faking a gate failure.
    The ABSOLUTE armed cost in us/task rides along: the gate that
    stays meaningful as the base gets faster (at the r14 ~1us/task
    headline a constant 0.5us plane reads as +50% ratio — the ratio
    stops measuring the code under test)."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.utils.mca import params as _params

    def rate(armed: int) -> float:
        for k in knobs:
            _params.set(k, armed)
        try:
            with Context(nb_cores=int(os.environ.get(
                    "PARSEC_BENCH_CORES", 4))) as ctx:
                ctx.add_taskpool(_empty_pool(n // 10))   # warm
                ctx.wait()
                t0 = time.perf_counter()
                ctx.add_taskpool(_empty_pool(n))
                ctx.wait()
                return n / (time.perf_counter() - t0)
        finally:
            for k in knobs:
                _params.unset(k)

    pairs = []
    us_pairs = []
    off = on = 0.0
    for _ in range(4):
        o, a = rate(0), rate(1)
        off, on = max(off, o), max(on, a)
        if a and o:
            pairs.append(max(0.0, o / a - 1.0))
            us_pairs.append(max(0.0, (1.0 / a - 1.0 / o) * 1e6))
    overhead = min(pairs) if pairs else 1.0
    overhead_us = min(us_pairs) if us_pairs else 10.0
    log(f"{label} overhead: {overhead:+.1%} / {overhead_us:.3f} "
        f"us/task (min of {['%+.1f%%' % (p * 100) for p in pairs]}; "
        f"best off {off:.0f} -> armed {on:.0f} tasks/s)")
    return overhead, {"tasks_off": round(off, 1),
                      "tasks_on": round(on, 1),
                      "overhead_us": round(overhead_us, 3)}


def run_telemetry_bench(n: int = 20000):
    """Always-on telemetry overhead, as a ratio: the tasks probe with
    the metrics registry AND flight recorder armed vs both off — the
    premerge telemetry gate's measurement (bound <= 5%, an order
    cheaper than the causal tracer's 50% gate).  The armed leg
    carries the WHOLE plane: registry + flight recorder + the live
    attribution engine with straggler detection (liveattr rides the
    metrics sampling stride, so arming it is the production
    configuration this gate bounds)."""
    return _overhead_probe(("metrics_enabled", "flightrec_enabled",
                            "liveattr_enable"), "telemetry", n)


def run_journal_bench(n: int = 20000):
    """Control-plane journal overhead on the tasks probe, armed vs
    off — the telemetry-gate discipline (interleaved pairs, min-of-
    pairs, both the ratio and the ABSOLUTE us/task cost reported).
    The journal has NO per-task emit sites by construction (every
    emit is control-plane code: recovery rounds, retirement
    handshakes, barriers, job lifecycle), so the C run_quantum fast
    path never crosses it — this gate PROVES that instead of
    asserting it in prose."""
    return _overhead_probe(("journal_enabled",), "journal", n)


def run_tracer_bench(n: int = 100000):
    """Binary-tracer overhead per traced task, microseconds — the
    sp-perf.c analog done the way sp-perf does it: the reference's
    harness times the PROFILING LAYER in a tight single-threaded loop
    (tests/profiling-standalone/sp-perf.c), not a whole runtime run, so
    the number measures the tracer instead of scheduler noise.  Here the
    loop drives the REAL instrumentation path end to end — es.pins
    dispatch -> task_profiler callbacks -> interval bookkeeping -> the C
    trace sink — over real Task objects, and reports the marginal cost
    of the tracer being installed (dispatch with no subscribers is the
    baseline, as in the runtime's untraced hot path).

    (The r4 form — whole-runtime wall-clock with/without the tracer at
    nb_cores=4 on this 1-core host — subtracted two ~100 ms runs with a
    1.3-1.4x run-to-run spread to resolve a ~1 us effect; its 5 us
    reading was measurement noise + GIL-contention amplification, not
    tracer cost.  Microbenched pieces: raw sink append 0.14 us/event,
    full callback path ~1.3 us/task.)"""
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.task import Task
    from parsec_tpu.prof.pins import install_task_profiler
    from parsec_tpu.prof.profiling import Profile

    with Context(nb_cores=1) as ctx:
        tp = _empty_pool(4)
        ctx.add_taskpool(tp)
        ctx.wait()
        tc = tp.task_classes["E"]
        es = ctx.streams[0]
        tasks = [Task(tc, tp, {"i": k}) for k in range(n)]

        def loop():
            t0 = time.perf_counter()
            for t in tasks:
                es.pins("exec_begin", t)
                es.pins("exec_end", t)
                es.pins("complete_exec", t)
            return time.perf_counter() - t0

        loop()                                   # warm
        base = min(loop() for _ in range(3))
        mod = install_task_profiler(ctx, Profile())
        try:
            loop()                               # warm caches/JIT paths
            traced = min(loop() for _ in range(3))
        finally:
            mod.uninstall(ctx)
    return max(0.0, (traced - base) / n * 1e6)


def run_fabric_bench(n_jobs: int = 0):
    """Many-small-jobs serving throughput through the ServingFabric
    (service/fabric.py): one warm mesh, a stream of independent small
    chain jobs, jobs/s as the value and the p50/p99
    admission->completion latency in the extras — the serving-shape
    metric of the multi-tenant fabric (ISSUE 16).  The run is
    journal-audited: any F1/F2/F3 fabric-invariant violation fails the
    probe rather than reporting a number a broken fabric produced."""
    if not n_jobs:
        n_jobs = int(os.environ.get("PARSEC_BENCH_FABRIC_JOBS", 48))
    nt = int(os.environ.get("PARSEC_BENCH_FABRIC_NT", 8))
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    from parsec_tpu.service.fabric import ServingFabric

    def chain_factory(i):
        def factory():
            A = TwoDimBlockCyclic(mb=4, nb=4, lm=4, ln=4)
            A.data_of(0, 0).copy_on(0).payload[:] = 0.0
            p = PTG(f"fj{i}", NT=nt)
            p.task("S", k=Range(0, nt - 1)) \
                .affinity(lambda k, A=A: A(0, 0)) \
                .flow("T", "RW",
                      IN(DATA(lambda A=A: A(0, 0)),
                         when=lambda k: k == 0),
                      IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                         when=lambda k: k > 0),
                      OUT(TASK("S", "T",
                               lambda k, NT=nt: dict(k=k + 1)),
                          when=lambda k, NT=nt: k < NT - 1),
                      OUT(DATA(lambda A=A: A(0, 0)),
                          when=lambda k, NT=nt: k == NT - 1)) \
                .body(lambda T: T + 1.0)
            return p.build()
        return factory

    log(f"fabric config: jobs={n_jobs} nt={nt}")
    with ServingFabric(nb_cores=4, max_active=8,
                       max_pending=n_jobs + 8) as svc:
        warm = [svc.submit(chain_factory(-1 - i), app="fabwarm")
                for i in range(4)]
        for j in warm:
            j.wait(timeout=60.0)
        t0 = time.perf_counter()
        jobs = [svc.submit(chain_factory(i), app="fabbench")
                for i in range(n_jobs)]
        for j in jobs:
            if not j.wait(timeout=120.0):
                raise RuntimeError(f"fabric bench: {j} never finished")
        dt = time.perf_counter() - t0
        lats = sorted(j.finished_at - j.submitted_at for j in jobs)
        bundle = {0: [svc.context.journal.snapshot()]}
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import journal_audit
    violations = journal_audit.audit(bundle)
    if violations:
        raise RuntimeError(
            f"fabric bench: journal audit found {len(violations)} "
            f"violation(s): {violations[:3]}")
    p50 = lats[len(lats) // 2]
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
    extras = {"fabric": {
        "jobs": n_jobs,
        "p50_latency_s": round(p50, 4),
        "p99_latency_s": round(p99, 4),
        "audit": "clean",
    }}
    return n_jobs / dt, extras


def _call_counts(codes, fn, on_threads=()):
    """Run ``fn()`` and count the calls of each code object in ``codes``
    (name -> code) on every thread: ``sys.monitoring`` local events, so
    only those functions pay for the count.  With ``on_threads`` (thread
    name prefixes) the answer is a pair: the counts on every thread, and
    prefix -> the counts on the threads whose name starts so."""
    mon = sys.monitoring
    tool = mon.PROFILER_ID
    counts = dict.fromkeys(codes, 0)
    on = {p: dict.fromkeys(codes, 0) for p in on_threads}
    names = {code: name for name, code in codes.items()}

    def started(code, offset):
        counts[names[code]] += 1    # under the interpreter lock
        if on:
            thread = threading.current_thread().name
            for p, got in on.items():
                if thread.startswith(p):
                    got[names[code]] += 1

    mon.use_tool_id(tool, "bench-counts")
    try:
        mon.register_callback(tool, mon.events.PY_START, started)
        for code in names:
            mon.set_local_events(tool, code, mon.events.PY_START)
        fn()
    finally:
        for code in names:
            mon.set_local_events(tool, code, 0)
        mon.register_callback(tool, mon.events.PY_START, None)
        mon.free_tool_id(tool)
    return (counts, on) if on_threads else counts


def run_release_bench(nt: int = 32, mb: int = 16):
    """COUNTS a task of the nt = 32 tiled Cholesky DAG (the shape of the
    benchmark's host-paced cells), the PTG beside the DTD front end, on
    one CPU device: calls of a ``dsl/ptg/api._named`` by-name adapter
    (``kwargs`` built by name lookup), calls of its positional form, and
    locked mutations of a data repo.  Not a speed: a PR that puts the
    by-name adapter or a repo hold a task back on the release path is
    seen here without a chip (PERF.md section 6, PR 34: 12.7 by-name
    calls and 4.7 repo mutations a PTG task before it)."""
    from parsec_tpu.apps import potrf
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.datarepo import DataRepo
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.ptg import api
    from parsec_tpu.utils.mca import params

    n = nt * mb
    rng = np.random.default_rng(0)
    m = rng.standard_normal((n, n)).astype(np.float32)
    spd = m @ m.T + n * np.eye(n, dtype=np.float32)
    by_name = api._named(lambda: 0)
    codes = {"by_name": by_name.__code__}
    for i, probe in enumerate((lambda: 0, lambda a: 0, lambda a, b: 0,
                               lambda a, b, c: 0)):
        names = frozenset("abc"[:i])
        codes[f"positional{i}"] = api._positional(
            api._named(probe), names).__code__
    for meth in ("lookup_entry_and_create", "entry_addto_usage_limit",
                 "entry_used_once"):
        codes[meth] = getattr(DataRepo, meth).__code__
    out = {}
    params.set("device_max", 1)
    try:
        with Context(nb_cores=int(os.environ.get("PARSEC_BENCH_CORES",
                                                 4))) as ctx:
            for front, build in (("ptg", potrf.potrf_taskpool),
                                 ("dtd", potrf.potrf_dtd_taskpool)):
                A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n,
                                      ln=n).from_array(spd.copy())
                tp = build(A, device="tpu")

                def job():
                    ctx.add_taskpool(tp)
                    ctx.wait(timeout=600)
                c = _call_counts(codes, job)
                L = np.tril(A.to_array())
                err = np.abs(L @ L.T - spd).max() / np.abs(spd).max()
                if not err < 1e-3:
                    raise RuntimeError(
                        f"release bench: {front} factor wrong ({err})")
                tasks = nt * (nt + 1) * (nt + 2) // 6
                out[front] = {
                    "tasks": tasks,
                    "by_name_calls_per_task":
                        round(c["by_name"] / tasks, 3),
                    "positional_calls_per_task": round(sum(
                        v for k, v in c.items()
                        if k.startswith("positional")) / tasks, 3),
                    "repo_mutations_per_task": round(
                        (c["lookup_entry_and_create"]
                         + c["entry_addto_usage_limit"]
                         + c["entry_used_once"]) / tasks, 3),
                    "release": tp.release_stats.as_dict()}
    finally:
        params.unset("device_max")
    return out["ptg"]["by_name_calls_per_task"], {"release": out,
                                                  "host": _host_info()}


def run_launch_bench(nt: int = 32, mb: int = 16):
    """COUNTS a flow and a task on the managers' launch path (PR 36):
    the nt = 32 tiled Cholesky of the benchmark's host-paced cells with
    their MCA, every lower tile born on one CPU device, the PTG beside
    the DTD front end.  On the manager threads: holds of a datum's lock
    a flow staged (``Data.acquire_on``, ``copy_on``,
    ``transfer_ownership``, ``attach_copy``, ``detach_copy``: 3 before
    PR 36, 1 since), holds of the device's ``_mem_lock`` a flow
    (``_pin_wave``, ``_touch``, ``_account``: 2 before, one a WAVE
    since), and signatures built there a task (1 before, 0 since); on
    every thread, signatures a task (1, at ``submit``).  Where a ready
    task reaches the device: ``DeviceStats.direct_submits`` a
    task (handed in by the thread that released it: all but the pool's
    start-up tasks) and ``XlaDevice.submit`` calls on the workers a
    task (1 before, about 0 since).  Not a speed: a PR that puts a lock
    hold a flow, a signature a candidate or a worker a task back is seen
    here without a chip."""
    import jax
    from parsec_tpu.apps import potrf
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.data import Data
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.devices.xla import XlaDevice, XlaKernel
    from parsec_tpu.utils.mca import params

    n = nt * mb
    rng = np.random.default_rng(0)
    m = rng.standard_normal((n, n)).astype(np.float32)
    spd = m @ m.T + n * np.eye(n, dtype=np.float32)
    datum_lock = ("acquire_on", "copy_on", "transfer_ownership",
                  "attach_copy", "detach_copy")
    mem_lock = ("_pin_wave", "_touch", "_account")
    sigs = ("task_sig", "args_sig")
    codes = {meth: getattr(owner, meth).__code__
             for owner, meths in ((Data, datum_lock),
                                  (XlaDevice, mem_lock + ("submit",)),
                                  (XlaKernel, sigs)) for meth in meths}
    mca = {"device_max": 1, "device_fuse": 8, "device_runahead": 48,
           "device_inflight_depth": 32}
    out = {}
    for k, v in mca.items():
        params.set(k, v)
    try:
        with Context(nb_cores=int(os.environ.get("PARSEC_BENCH_CORES",
                                                 4))) as ctx:
            (dev,) = ctx.device_registry.accelerators
            for front, build in (("ptg", potrf.potrf_taskpool),
                                 ("dtd", potrf.potrf_dtd_taskpool)):
                A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n,
                                      ln=n).from_array(spd.copy())
                for i in range(nt):
                    for j in range(i + 1):
                        A.data_of(i, j).overwrite_on(
                            dev.space, jax.device_put(
                                spd[i * mb:(i + 1) * mb,
                                    j * mb:(j + 1) * mb], dev.jdev))
                tp = build(A, device="tpu")
                st0 = dev.stats.as_dict()

                def job():
                    ctx.add_taskpool(tp)
                    ctx.wait(timeout=600)
                everywhere, on = _call_counts(
                    codes, job, on_threads=("xla-mgr", "parsec-worker"))
                c = on["xla-mgr"]
                st = {k: v - st0[k] for k, v in dev.stats.as_dict().items()}
                L = np.tril(A.to_array())
                err = np.abs(L @ L.T - spd).max() / np.abs(spd).max()
                if not err < 1e-3:
                    raise RuntimeError(
                        f"launch bench: {front} factor wrong ({err})")
                tasks = st["executed_tasks"] + st["held_tasks"]
                flows = st["resident_flows"] + st["staged_flows"]
                out[front] = {
                    "tasks": tasks, "flows": flows,
                    "launches": st["launches"],
                    "resident_flows": st["resident_flows"],
                    "staged_flows": st["staged_flows"],
                    "bytes_in": st["bytes_in"],
                    "datum_lock_holds_per_flow": round(sum(
                        c[k] for k in datum_lock) / flows, 3),
                    "mem_lock_holds_per_flow": round(sum(
                        c[k] for k in mem_lock) / flows, 3),
                    "manager_sig_calls_per_task": round(sum(
                        c[k] for k in sigs) / tasks, 3),
                    "sig_calls_per_task": round(sum(
                        everywhere[k] for k in sigs) / tasks, 3),
                    "direct_submits": st["direct_submits"],
                    "direct_submits_per_task": round(
                        st["direct_submits"] / tasks, 3),
                    "worker_submits_per_task": round(
                        on["parsec-worker"]["submit"] / tasks, 3)}
    finally:
        for k in mca:
            params.unset(k)
    return out["ptg"]["datum_lock_holds_per_flow"], {"launch": out,
                                                     "host": _host_info()}


#: mode -> (runner, metric name, unit, self-declared target, "higher is
#: better").  tools/premerge_bench.sh runs every one but tracer, which
#: tier-1 runs (tests/test_bench_modes.py).
_AUX_MODES = {
    "rtt": (run_rtt_bench, "task_rtt", "us/hop", 1000.0, False),
    "bw": (run_bw_bench, "dataflow_bandwidth", "MB/s", 1000.0, True),
    "tasks": (run_tasks_bench, "task_throughput", "tasks/s", 10000.0, True),
    "ntasks": (run_ntasks_bench, "task_throughput_nontrivial", "tasks/s",
               10000.0, True),
    "aggregate": (run_aggregate_bench, "aggregate_task_throughput",
                  "tasks/s", 20000.0, True),
    "telemetry": (run_telemetry_bench, "telemetry_overhead", "ratio",
                  0.05, False),
    "journal": (run_journal_bench, "journal_overhead", "ratio",
                0.05, False),
    "tracer": (run_tracer_bench, "tracer_overhead", "us/task", 1.0, False),
    "fabric": (run_fabric_bench, "fabric_jobs_per_s", "jobs/s",
               10.0, True),
    "release": (run_release_bench, "ptg_by_name_calls_per_task",
                "calls/task", 1.0, False),
    "launch": (run_launch_bench, "ptg_datum_lock_holds_per_flow",
               "holds/flow", 1.0, False),
}


def main():
    app = os.environ.get("PARSEC_BENCH_APP", "")
    if app not in _AUX_MODES:
        raise SystemExit(
            f"bench.py: PARSEC_BENCH_APP={app!r} names no host canary "
            f"(has: {', '.join(_AUX_MODES)}).  The chip is measured by the "
            f"benchmark: python -m benchmark.run --workload <cell> --seed S "
            f"--seconds 10 (cells in BENCHMARK.json, benchmark/README.md)")
    dev = _device()
    log(f"platform: {dev['platform']}, kind: {dev['kind']}, "
        f"devices: {dev['count']}")
    fn, metric, unit, target, higher = _AUX_MODES[app]
    value = fn()
    extras = {}
    if isinstance(value, tuple):
        value, extras = value
    # lower-is-better ratios cap at 100: a PERFECT reading (the
    # telemetry mode's 0.0 overhead is common) must score best, not
    # divide by zero
    vs = (value / target) if higher \
        else (min(100.0, target / value) if value else 100.0)
    _emit({
        "metric": metric,
        "value": round(value, 3),
        "unit": unit,
        "vs_baseline": round(vs, 4),
        **extras,
    })


if __name__ == "__main__":
    main()
