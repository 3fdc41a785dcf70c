"""Telemetry plane tests (ISSUE 8): registry semantics, exposition
format, the JobServer scrape surface (framed + plain HTTP), and
cross-rank aggregation over TAG_METRICS.

The flight-recorder incident path's end-to-end cases live in
test_fault.py (they ride the chaos/kill machinery)."""

import re
import socket
import time

import pytest

from parsec_tpu.devices.device import DeviceStats
from parsec_tpu.prof.metrics import (BUCKET_BOUNDS, Counter, Family, Gauge,
                                     Histogram, bucket_index,
                                     counter_sample, gauge_sample,
                                     histogram_sample, merge_samples,
                                     render_text)
from parsec_tpu.utils.mca import params


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_bucket_index_le_invariant():
    """Every observation lands in the smallest bucket whose bound is
    >= the value (Prometheus ``le`` semantics) — including exact powers
    of two, the frexp edge case."""
    vals = [1e-9, 1e-6, 2.0 ** -20, 2.0 ** -10, 3e-4, 0.25, 0.5,
            0.500001, 1.0, 2.0, 63.9, 64.0, 100.0, 1e6]
    for v in vals:
        i = bucket_index(v)
        if i < len(BUCKET_BOUNDS):
            assert v <= BUCKET_BOUNDS[i], (v, i)
        if 0 < i <= len(BUCKET_BOUNDS):
            assert v > BUCKET_BOUNDS[i - 1], (v, i)


def test_histogram_counts_sum_quantile():
    h = Histogram(ring=64)
    vals = [1e-5, 1e-5, 2e-3, 0.1, 0.1, 0.1, 5.0]
    for v in vals:
        h.observe(v)
    buckets, s, c = h.snapshot()
    assert c == len(vals)
    assert abs(s - sum(vals)) < 1e-12
    assert sum(buckets) == len(vals)
    # exact per-bucket placement
    for v in set(vals):
        assert buckets[bucket_index(v)] >= 1
    # the recent-window quantile brackets the data
    assert 1e-5 <= h.quantile(0.0) <= 5.0
    assert h.quantile(0.99) == 5.0


def test_counter_gauge_and_family_bounding():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = Gauge()
    g.set(7)
    g.add(-2)
    assert g.value == 5.0
    fam = Family(Counter, ("peer",), max_series=3)
    for r in range(5):
        fam.labels(peer=r).inc(r)
    items = fam.items()
    assert len(items) == 3          # oldest two evicted
    peers = {lab["peer"] for lab, _m in items}
    assert peers == {"2", "3", "4"}


def test_render_text_exposition_format():
    h = Histogram()
    for v in (1e-4, 1e-4, 2.0):
        h.observe(v)
    text = render_text([
        counter_sample("parsec_demo_total", 3),
        gauge_sample("parsec_demo_depth", 2, {"peer": "1"}),
        histogram_sample("parsec_demo_seconds", h),
    ])
    assert "# TYPE parsec_demo_total counter" in text
    assert "parsec_demo_total 3" in text
    assert 'parsec_demo_depth{peer="1"} 2' in text
    # cumulative bucket counts, monotonic, +Inf == count
    counts = [int(m.group(1)) for m in re.finditer(
        r'parsec_demo_seconds_bucket\{le="[^"]+"\} (\d+)', text)]
    assert counts == sorted(counts)
    assert counts[-1] == 3
    assert 'le="+Inf"' in text
    assert "parsec_demo_seconds_count 3" in text
    m = re.search(r"parsec_demo_seconds_sum (\S+)", text)
    assert abs(float(m.group(1)) - 2.0002) < 1e-9


def test_merge_samples_sums_counters_and_labels_gauges():
    h0, h1 = Histogram(), Histogram()
    h0.observe(1e-4)
    h1.observe(1e-4)
    h1.observe(2.0)
    merged = merge_samples({
        0: [counter_sample("parsec_x_total", 5),
            gauge_sample("parsec_x_depth", 2),
            histogram_sample("parsec_x_seconds", h0)],
        1: [counter_sample("parsec_x_total", 7),
            gauge_sample("parsec_x_depth", 9),
            histogram_sample("parsec_x_seconds", h1)],
    })
    by = {(s["n"], tuple(sorted(s["l"].items()))): s for s in merged}
    assert by[("parsec_x_total", ())]["v"] == 12
    assert by[("parsec_x_depth", (("rank", "0"),))]["v"] == 2
    assert by[("parsec_x_depth", (("rank", "1"),))]["v"] == 9
    hs = by[("parsec_x_seconds", ())]
    assert hs["cnt"] == 3 and sum(hs["b"]) == 3


# ---------------------------------------------------------------------------
# the always-on registry on a Context
# ---------------------------------------------------------------------------

def _n_pool(n, name="m"):
    from parsec_tpu.dsl.ptg.api import PTG, Range
    p = PTG(name, N=n)
    p.task("E", i=Range(0, n - 1)).flow("x", "CTL").body(lambda: None)
    return p.build()


def test_runtime_metrics_counts_every_task():
    from parsec_tpu.core.context import Context
    params.set("metrics_sample", 1)
    try:
        with Context(nb_cores=2) as ctx:
            assert ctx.metrics is not None
            assert ctx._ready_stamp     # schedule() stamps ready_at
            ctx.add_taskpool(_n_pool(40))
            ctx.wait(timeout=60)
            text = render_text(ctx.metrics.samples())
    finally:
        params.unset("metrics_sample")
    assert re.search(r"parsec_tasks_retired_total 40\b", text)
    assert re.search(r"parsec_pending_tasks 0\b", text)
    # with stride 1 every task contributes a sojourn-latency sample
    assert re.search(r"parsec_task_latency_seconds_count 40\b", text)


def test_queue_wait_split_is_opt_in():
    """metrics_queue_wait=1 hooks select too, separating queue-wait
    (ready->select) from execution latency (select->complete); the
    default single-hook path keeps the telemetry budget."""
    from parsec_tpu.core.context import Context
    params.set("metrics_sample", 1)
    params.set("metrics_queue_wait", 1)
    try:
        with Context(nb_cores=2) as ctx:
            ctx.add_taskpool(_n_pool(30))
            ctx.wait(timeout=60)
            text = render_text(ctx.metrics.samples())
    finally:
        params.unset("metrics_sample")
        params.unset("metrics_queue_wait")
    assert re.search(r"parsec_task_queue_wait_seconds_count 30\b", text)
    assert re.search(r"parsec_task_latency_seconds_count 30\b", text)


def test_metrics_disabled_removes_every_hook():
    from parsec_tpu.core.context import Context
    params.set("metrics_enabled", 0)
    try:
        with Context(nb_cores=1) as ctx:
            assert ctx.metrics is None
            assert not ctx._ready_stamp
            ctx.add_taskpool(_n_pool(5))
            ctx.wait(timeout=60)
    finally:
        params.unset("metrics_enabled")


def test_causal_tracer_keeps_ready_stamp_without_metrics():
    """The queue-wait stamp survives metrics-off when a causal tracer
    is installed (the pre-existing contract)."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.prof.causal import install_causal_tracer
    from parsec_tpu.prof.profiling import Profile
    params.set("metrics_enabled", 0)
    try:
        with Context(nb_cores=1) as ctx:
            assert not ctx._ready_stamp
            tr = install_causal_tracer(ctx, Profile())
            assert ctx._ready_stamp
            tr.uninstall(ctx)
            assert not ctx._ready_stamp
    finally:
        params.unset("metrics_enabled")


@pytest.mark.parametrize("counter", DeviceStats.__slots__)
def test_every_device_counter_is_exported(counter):
    """A counter of DeviceStats reaches the scrape under its own name
    with the device label, no table to edit: parsec_device_<name>_total,
    but for executed_tasks (parsec_device_tasks_total) and the
    high-water mark replica_bytes_peak, a gauge without the suffix."""
    from parsec_tpu.core.context import Context
    with Context(nb_cores=1) as ctx:
        dev = ctx.device_registry.devices[-1]
        setattr(dev.stats, counter, 7)
        got = [s for s in ctx.metrics._collect_devices()
               if s["l"] == {"device": dev.name} and s["v"] == 7.0]
    if counter == "replica_bytes_peak":
        want = ("parsec_device_replica_bytes_peak", "gauge")
    elif counter == "executed_tasks":
        want = ("parsec_device_tasks_total", "counter")
    else:
        want = (f"parsec_device_{counter}_total", "counter")
    assert [(s["n"], s["t"]) for s in got] == [want]


# ---------------------------------------------------------------------------
# scrape surface: framed op, HTTP GET, CLI client
# ---------------------------------------------------------------------------

def _tiny_job_factory():
    def factory():
        return _n_pool(8, name="job-pool")
    return factory


def test_scrape_over_job_server_framed():
    from parsec_tpu.service.server import request, serve
    params.set("metrics_sample", 1)
    service, server = serve(port=0, nb_cores=2)
    try:
        job = service.submit(_tiny_job_factory(), name="scrapee")
        assert job.wait(timeout=30)
        reply = request(server.host, server.port, {"op": "metrics"})
        assert reply["ok"] and reply["ranks"] == [0]
        text = reply["text"]
        # task + job families are present, and the job SLO histogram
        # has exactly the one completed job with cumulative buckets
        assert "parsec_tasks_retired_total" in text
        assert 'parsec_jobs_done_total{status="done"} 1' in text
        assert re.search(r"parsec_job_duration_seconds_count 1\b", text)
        counts = [int(m.group(1)) for m in re.finditer(
            r'parsec_job_duration_seconds_bucket\{le="[^"]+"\} (\d+)',
            text)]
        assert counts == sorted(counts) and counts[-1] == 1
        # per-job task counters ride the JobGauges window, one series
        # per counter column
        assert re.search(
            r'parsec_job_tasks_total\{job="%d",kind="retired"\} 8\b'
            % job.job_id, text)
    finally:
        params.unset("metrics_sample")
        server.close()
        service.shutdown(timeout=10.0)


def test_scrape_over_http_get():
    """A stock HTTP client (curl, Prometheus) scrapes the SAME port:
    the server sniffs the first four bytes to pick the protocol."""
    from parsec_tpu.service.server import serve
    service, server = serve(port=0, nb_cores=2)
    try:
        with socket.create_connection((server.host, server.port),
                                      timeout=10.0) as s:
            s.sendall(b"GET /metrics HTTP/1.0\r\nHost: x\r\n\r\n")
            data = b""
            while True:
                chunk = s.recv(4096)
                if not chunk:
                    break
                data += chunk
        head, body = data.split(b"\r\n\r\n", 1)
        assert head.startswith(b"HTTP/1.0 200 OK")
        assert b"text/plain" in head
        assert b"parsec_tasks_retired_total" in body
        # and a wrong path 404s instead of hanging the connection
        with socket.create_connection((server.host, server.port),
                                      timeout=10.0) as s:
            s.sendall(b"GET /nope HTTP/1.0\r\n\r\n")
            assert s.recv(4096).startswith(b"HTTP/1.0 404")
    finally:
        server.close()
        service.shutdown(timeout=10.0)


def test_metrics_client_one_shot():
    from tools.metrics_client import scrape
    from parsec_tpu.service.server import serve
    service, server = serve(port=0, nb_cores=2)
    try:
        text = scrape(server.host, server.port)
        assert "parsec_pending_tasks" in text
    finally:
        server.close()
        service.shutdown(timeout=10.0)


# ---------------------------------------------------------------------------
# heartbeat-detector observability (satellite: per-peer rebase)
# ---------------------------------------------------------------------------

def test_starved_checker_rebase_is_per_peer():
    """A starved checker rebases ONLY peers whose silence it cannot
    judge (last heard before the stall); a peer heard DURING the stall
    keeps its real silence clock — and the rebases are counted for the
    metrics plane."""
    from parsec_tpu.comm.engine import CommEngine

    params.set("comm_peer_timeout_s", 0.5)
    try:
        ce = CommEngine(0, 3)
        now = time.monotonic()
        ce._hb_check_at = now - 10.0          # WE were frozen for 10s
        ce._last_heard[1] = now - 10.0        # silent since before stall
        ce._last_heard[2] = now - 0.6         # heard DURING the stall,
        ce.check_peer_timeouts()              # age already past timeout
        # a starved round NEVER declares (unread frames may be parked
        # in the kernel) — but only the stale peer was rebased
        assert not ce.dead_peers
        assert ce.hb_rebase_total == 1
        assert ce.hb_rebases() == {1: 1}
        # peer 2's clock was NOT rebased: the next HEALTHY check
        # declares on its true silence age immediately
        ce.check_peer_timeouts()
        assert 2 in ce.dead_peers
        assert 1 not in ce.dead_peers         # rebased peer got fresh time
        assert ce.peer_debug()[1].get("hb_rebases") == 1
    finally:
        params.unset("comm_peer_timeout_s")


# ---------------------------------------------------------------------------
# cross-rank aggregation over TAG_METRICS (the 2-rank acceptance)
# ---------------------------------------------------------------------------

def _chain_pool(V, nranks, name="chain"):
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
    NT = 6
    p = PTG(name, NT=NT)
    p.task("S", k=Range(0, NT - 1)) \
        .affinity(lambda k, V=V: V(k)) \
        .flow("T", "RW",
              IN(DATA(lambda k, V=V: V(k)), when=lambda k: k == 0),
              IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                 when=lambda k: k > 0),
              OUT(TASK("S", "T", lambda k, NT=NT: dict(k=k + 1)),
                  when=lambda k, NT=NT: k < NT - 1),
              OUT(DATA(lambda k, V=V: V(k)))) \
        .body(lambda T: T + 1.0)
    return p.build()


def _scrape_worker(ctx, rank, nranks):
    """Rank 0 runs a JobService + JobServer over the SHARED 2-rank
    context and scrapes /metrics; the reply must cover the mesh."""
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    V = VectorTwoDimCyclic(mb=4, lm=24, nodes=nranks, myrank=rank)
    for m, _ in V.local_tiles():
        V.data_of(m).copy_on(0).payload[:] = 0.0
    ctx.add_taskpool(_chain_pool(V, nranks))
    ctx.wait(timeout=60)
    local_frames = ctx.comm.stats()["frames_sent"]
    if rank != 0:
        return {"frames": local_frames}
    from parsec_tpu.service.server import JobServer, request
    from parsec_tpu.service.service import JobService
    svc = JobService(context=ctx)
    server = JobServer(svc, port=0)
    try:
        job = svc.submit(_tiny_job_factory(), name="agg")
        assert job.wait(timeout=30)
        reply = request(server.host, server.port,
                        {"op": "metrics", "timeout": 5.0})
    finally:
        server.close()
        svc.shutdown(timeout=10.0)
    return {"frames": local_frames, "reply": reply}


def test_two_rank_scrape_aggregates_over_tag_metrics():
    """The ISSUE acceptance: one scrape on a running JobService sees
    the mesh — task/comm/job families summed across both ranks via
    TAG_METRICS, gauges labeled per rank, and a histogram with correct
    bucket counts."""
    from parsec_tpu.comm.launch import run_distributed
    res = run_distributed(_scrape_worker, 2, timeout=180)
    reply = res[0]["reply"]
    assert reply["ok"]
    assert reply["ranks"] == [0, 1]
    text = reply["text"]
    # comm counters summed across ranks: at least every frame rank 1
    # alone sent (both ranks sent frames during the chain)
    m = re.search(r"parsec_comm_frames_sent_total (\d+)", text)
    assert m is not None
    total = int(m.group(1))
    assert total >= res[0]["frames"] + 1, (total, res)
    assert total >= res[1]["frames"] + 1, (total, res)
    # per-rank gauges carry the rank label
    assert re.search(r'parsec_pending_tasks\{rank="1"\}', text)
    # the clock-probe exchange fed the frame-RTT histogram
    m = re.search(r"parsec_comm_frame_rtt_seconds_count (\d+)", text)
    assert m is not None and int(m.group(1)) >= 1, text[:2000]
    # the job SLO histogram survived the merge with correct buckets
    counts = [int(mm.group(1)) for mm in re.finditer(
        r'parsec_job_duration_seconds_bucket\{le="[^"]+"\} (\d+)',
        text)]
    assert counts and counts == sorted(counts) and counts[-1] == 1
    assert re.search(r"parsec_job_duration_seconds_count 1\b", text)


# ---------------------------------------------------------------------------
# SLO breach wiring (metrics -> flight recorder)
# ---------------------------------------------------------------------------

def test_job_slo_breach_counts_and_triggers_incident(tmp_path):
    from parsec_tpu.service.service import JobService
    params.set("metrics_slo_job_s", 1e-9)   # every job breaches
    params.set("flightrec_enabled", 1)
    params.set("flightrec_dir", str(tmp_path))
    try:
        with JobService(nb_cores=2) as svc:
            job = svc.submit(_tiny_job_factory(), name="slo")
            assert job.wait(timeout=30)
            ctx = svc.context
            # wait() returns at the DONE transition; the job_done PINS
            # emission (breach count + incident dump) follows on the
            # finishing thread a moment later
            deadline = time.monotonic() + 10
            while ctx._flightrec.incidents < 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert ctx._flightrec.incidents >= 1
            text = render_text(ctx.metrics.samples())
            assert re.search(r"parsec_jobs_slo_breached_total [1-9]",
                             text)
            assert (tmp_path / "rank0.ptt").exists()
    finally:
        params.unset("metrics_slo_job_s")
        params.unset("flightrec_enabled")
        params.unset("flightrec_dir")
