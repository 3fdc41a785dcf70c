"""Predictive health plane tests (ISSUE 19): scrape-time score
fusion and the state machine (prof/health.py), pessimistic cross-rank
merge of ``__health__`` sections, the metrics/status export surfaces,
the serving fabric's sustained-below-threshold drain/undrain loop, the
H1 invariant of the offline journal auditor, and the flight-recorder
health snapshot (tools/journal_audit.py, prof/flightrec.py)."""

import json
import os
import re
import sys
import time

import pytest

from parsec_tpu.prof.health import HealthMonitor, merge_health
from parsec_tpu.prof.metrics import render_text
from parsec_tpu.utils.mca import params

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from tools import journal_audit  # noqa: E402


# ---------------------------------------------------------------------------
# merge_health: the cross-rank pessimistic fold
# ---------------------------------------------------------------------------

def _section(rank, scores, folds=0, transitions=0):
    return {"v": 1, "rank": rank, "folds": folds,
            "transitions": transitions,
            "scores": {str(r): {"score": s, "ewma": s, "trend": 0.0,
                                "state": "ok", "since_s": 0.0, "n": 1}
                       for r, s in scores.items()}}


def test_merge_health_counts_sum_exactly():
    doc = merge_health({
        0: _section(0, {0: 1.0}, folds=7, transitions=2),
        1: _section(1, {1: 1.0}, folds=5, transitions=1),
    })
    assert doc["folds"] == 12
    assert doc["transitions"] == 3


def test_merge_health_pessimistic_lowest_view_wins():
    """A wedged rank's rosy self-report must not mask what its peers
    measure: the LOWEST smoothed score any rank observed wins, and the
    observing rank is recorded as ``src``."""
    doc = merge_health({
        0: _section(0, {0: 1.0, 1: 0.4}),    # rank 0 sees peer 1 sick
        1: _section(1, {1: 0.95, 0: 0.99}),  # rank 1 self-reports fine
    })
    assert doc["ranks"][1]["ewma"] == 0.4
    assert doc["ranks"][1]["src"] == 0
    assert doc["ranks"][0]["ewma"] == 0.99
    assert doc["ranks"][0]["src"] == 1


def test_merge_health_tolerates_absent_and_malformed_sections():
    """A mid-pull death or a disabled plane leaves a rank's section
    absent (or empty) — it contributes nothing and kills nothing."""
    doc = merge_health({
        0: _section(0, {0: 0.9}),
        1: None,
        2: {},
        3: {"v": 1, "rank": 3, "scores": {"bogus": {"ewma": "NaNish"}}},
    })
    assert set(doc["ranks"]) == {0}
    assert merge_health(None) == {"ranks": {}, "folds": 0,
                                  "transitions": 0}
    assert merge_health({}) == {"ranks": {}, "folds": 0,
                                "transitions": 0}


# ---------------------------------------------------------------------------
# HealthMonitor: scoring, state machine, transition journal
# ---------------------------------------------------------------------------

class _JournalStub:
    def __init__(self):
        self.events = []

    def emit(self, etype, **fields):
        self.events.append({"e": etype, **fields})


class _CtxStub:
    def __init__(self):
        self.rank = 0
        self.journal = _JournalStub()


class _MetricsStub:
    def __init__(self):
        self.context = _CtxStub()


def _mk_monitor():
    m = _MetricsStub()
    return HealthMonitor(m), m.context.journal


def test_monitor_state_machine_and_transition_journal():
    """Driving declining scores through the fold walks ok ->
    degraded -> critical, each hop journaled as a health_transition
    with the OBSERVED rank in ``peer`` (merge stamps ``rank`` with
    the observer)."""
    hm, jr = _mk_monitor()
    now = time.monotonic()
    with hm._lock:
        for s in (1.0, 0.9, 0.5, 0.3, 0.1, 0.05, 0.02, 0.01):
            hm._observe_locked(1, s, now)
    snap = hm.snapshot()[1]
    assert snap["state"] == "critical"
    assert snap["ewma"] < 0.5
    kinds = [(e["frm"], e["to"]) for e in jr.events
             if e["e"] == "health_transition"]
    assert ("ok", "degraded") in kinds
    assert ("degraded", "critical") in kinds
    assert all(e.get("peer") == 1 for e in jr.events)
    assert hm.transitions == len(kinds)
    # trend over the declining window is negative
    assert snap["trend"] < 0.0


def test_monitor_hysteresis_damps_flapping():
    """Climbing back out of a state needs the threshold PLUS the
    hysteresis margin — a score dithering on the line must not spam
    the transition journal."""
    params.set("health_alpha", 1.0)      # ewma == last score: exact
    try:
        hm, jr = _mk_monitor()
        now = time.monotonic()
        thr_deg = hm._thr_deg
        hyst = hm._hyst
        with hm._lock:
            hm._observe_locked(1, thr_deg - 0.01, now)   # -> degraded
            assert hm._ranks[1].state == "degraded"
            # above the threshold but inside the margin: stays put
            hm._observe_locked(1, thr_deg + hyst / 2, now)
            assert hm._ranks[1].state == "degraded"
            # past the margin: recovers
            hm._observe_locked(1, thr_deg + hyst + 0.01, now)
            assert hm._ranks[1].state == "ok"
        trans = [e for e in jr.events if e["e"] == "health_transition"]
        assert len(trans) == 2          # one down, one up — no flap
    finally:
        params.unset("health_alpha")


def test_monitor_evidence_and_series_shapes():
    hm, _ = _mk_monitor()
    now = time.monotonic()
    with hm._lock:
        for s in (0.8, 0.6, 0.4):
            hm._observe_locked(2, s, now)
    ev = hm.evidence(2, k=2)
    assert len(ev) == 2
    assert [s for _age, s in ev] == [0.6, 0.4]      # newest last
    assert all(age >= 0.0 for age, _s in ev)
    series = hm.series_snapshot()
    assert len(series[2]) == 3
    assert hm.evidence(99) == []                    # unknown rank


def test_monitor_refresh_rate_limit_reuses_last_fold():
    params.set("health_interval_s", 3600.0)
    try:
        hm, _ = _mk_monitor()
        hm.refresh()
        hm.refresh()
        hm.refresh()
        assert hm.folds == 1        # inside the window: one real fold
        hm.refresh(force=True)
        assert hm.folds == 2
        # a context-less self fold scores this rank healthy
        assert hm.snapshot()[0]["ewma"] == 1.0
    finally:
        params.unset("health_interval_s")


# ---------------------------------------------------------------------------
# export surfaces: gauges + __health__ section on a live Context
# ---------------------------------------------------------------------------

def _n_pool(n, name="h"):
    from parsec_tpu.dsl.ptg.api import PTG, Range
    p = PTG(name, N=n)
    p.task("E", i=Range(0, n - 1)).flow("x", "CTL").body(lambda: None)
    return p.build()


def test_health_gauges_and_section_ride_samples():
    from parsec_tpu.core.context import Context
    params.set("health_interval_s", 0.0)
    try:
        with Context(nb_cores=2) as ctx:
            assert ctx.metrics is not None
            assert ctx.metrics.health is not None
            ctx.add_taskpool(_n_pool(10))
            ctx.wait(timeout=60)
            samples = ctx.metrics.samples()
    finally:
        params.unset("health_interval_s")
    text = render_text(samples)
    assert re.search(r'parsec_rank_health\{rank="0"\} 1\b', text)
    assert "parsec_health_folds_total" in text
    sections = [s for s in samples if s.get("n") == "__health__"]
    assert len(sections) == 1
    doc = sections[0]["doc"]
    assert doc["scores"]["0"]["state"] == "ok"
    # the side-channel record itself never renders
    assert "__health__" not in text


def test_health_disarmed_by_knob():
    from parsec_tpu.core.context import Context
    params.set("health_enable", 0)
    try:
        with Context(nb_cores=1) as ctx:
            assert ctx.metrics is not None
            assert ctx.metrics.health is None
            samples = ctx.metrics.samples()
    finally:
        params.unset("health_enable")
    assert not [s for s in samples if s.get("n") == "__health__"]


# ---------------------------------------------------------------------------
# H1: the offline auditor on hand-built journals
# ---------------------------------------------------------------------------

def _bundle(events, rank=0):
    """One rank's snapshot list in the auditor's input shape."""
    evs = [{"seq": i, "inc": 0, **e} for i, e in enumerate(events)]
    return {rank: [{"rank": rank, "inc": 0, "nranks": 2, "clock": {},
                    "events": evs}]}


def _h1(violations):
    return [v for v in violations if v.startswith("H1")]


def test_audit_h1_clean_drain_sequence():
    evs = [
        {"e": "health_transition", "t": 1.0, "peer": 1, "frm": "ok",
         "to": "degraded", "score": 0.7},
        {"e": "health_transition", "t": 2.0, "peer": 1,
         "frm": "degraded", "to": "critical", "score": 0.45},
        {"e": "health_drain", "t": 3.0, "peer": 1, "score": 0.45,
         "thr": 0.5, "sustain_s": 2.0, "evidence": [[0.5, 0.45]]},
        {"e": "fabric_admit", "t": 3.5, "job": 1, "verdict": "admit"},
        {"e": "fabric_place", "t": 4.0, "job": 1, "devices": [],
         "shared": True, "ranks": [0]},
        {"e": "health_undrain", "t": 5.0, "peer": 1, "score": 0.9},
        {"e": "fabric_admit", "t": 5.5, "job": 2, "verdict": "admit"},
        {"e": "fabric_place", "t": 6.0, "job": 2, "devices": [],
         "shared": True, "ranks": [0, 1]},
    ]
    assert journal_audit.audit(_bundle(evs)) == []


def test_audit_h1_drain_without_evidence():
    evs = [{"e": "health_drain", "t": 1.0, "peer": 1, "score": 0.4,
            "thr": 0.5, "evidence": []}]
    v = _h1(journal_audit.audit(_bundle(evs)))
    assert len(v) == 1
    assert "no preceding below-threshold evidence" in v[0]


def test_audit_h1_recovered_evidence_does_not_back_a_drain():
    """A transition back to 'ok' RETIRES the evidence: a later drain
    needs fresh below-threshold observations."""
    evs = [
        {"e": "health_transition", "t": 1.0, "peer": 1, "frm": "ok",
         "to": "degraded", "score": 0.7},
        {"e": "health_transition", "t": 2.0, "peer": 1,
         "frm": "degraded", "to": "ok", "score": 0.9},
        {"e": "health_drain", "t": 3.0, "peer": 1, "score": 0.4,
         "thr": 0.5, "evidence": []},
    ]
    assert len(_h1(journal_audit.audit(_bundle(evs)))) == 1


def test_audit_h1_drain_score_not_below_threshold():
    evs = [
        {"e": "health_transition", "t": 1.0, "peer": 1, "frm": "ok",
         "to": "critical", "score": 0.45},
        {"e": "health_drain", "t": 2.0, "peer": 1, "score": 0.55,
         "thr": 0.5, "evidence": [[0.5, 0.55]]},
    ]
    v = _h1(journal_audit.audit(_bundle(evs)))
    assert len(v) == 1
    assert "not below its threshold" in v[0]


def test_audit_h1_placement_onto_drained_rank():
    evs = [
        {"e": "health_transition", "t": 1.0, "peer": 1, "frm": "ok",
         "to": "critical", "score": 0.4},
        {"e": "health_drain", "t": 2.0, "peer": 1, "score": 0.4,
         "thr": 0.5, "evidence": [[0.5, 0.4]]},
        {"e": "fabric_admit", "t": 2.5, "job": 7, "verdict": "admit"},
        {"e": "fabric_place", "t": 3.0, "job": 7, "devices": [],
         "shared": True, "ranks": [0, 1]},
    ]
    v = _h1(journal_audit.audit(_bundle(evs)))
    assert len(v) == 1
    assert "placement targets drained rank" in v[0]
    assert "job=7" in v[0]


def test_audit_h1_skips_pre_health_placements():
    """Placements without a ``ranks`` gang stamp predate the health
    plane and are not judged."""
    evs = [
        {"e": "health_transition", "t": 1.0, "peer": 1, "frm": "ok",
         "to": "critical", "score": 0.4},
        {"e": "health_drain", "t": 2.0, "peer": 1, "score": 0.4,
         "thr": 0.5, "evidence": [[0.5, 0.4]]},
        {"e": "fabric_admit", "t": 2.5, "job": 7, "verdict": "admit"},
        {"e": "fabric_place", "t": 3.0, "job": 7, "devices": [],
         "shared": True},
    ]
    assert _h1(journal_audit.audit(_bundle(evs))) == []


# ---------------------------------------------------------------------------
# serving fabric: sustained-below-threshold drain, then undrain
# ---------------------------------------------------------------------------

class _FakeMonitor:
    """Stands in for ctx.metrics._health: a scripted peer score the
    fabric's dispatcher tick consumes, journaling the transition the
    way the real monitor does so the decision audits clean."""

    def __init__(self, journal):
        self._journal = journal
        self.ewma = {1: 0.2}
        self._transitioned = set()

    def refresh(self, force=False):
        for r, e in self.ewma.items():
            if e < 0.75 and r not in self._transitioned:
                self._transitioned.add(r)
                self._journal.emit("health_transition", peer=r,
                                   frm="ok", to="critical", score=e)
        return self.snapshot()

    def snapshot(self):
        return {r: {"score": e, "ewma": e, "trend": 0.0, "state": "ok",
                    "since_s": 0.0, "n": 9}
                for r, e in self.ewma.items()}

    def evidence(self, rank, k=8):
        e = self.ewma.get(rank, 1.0)
        return [[0.3, e], [0.1, e]]


def test_fabric_drains_then_undrains_on_scripted_scores():
    from parsec_tpu.service.fabric import ServingFabric
    params.set("fabric_drain_sustain_s", 0.3)
    try:
        with ServingFabric(nb_cores=2, max_active=4) as svc:
            fake = _FakeMonitor(svc.context.journal)
            svc.context.metrics._health = fake
            assert svc._health_monitor() is fake
            # min smoothed score across the (undrained) gang
            deadline = time.monotonic() + 10.0
            while svc.drains < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert svc.drains == 1
            assert 1 in svc._health_drained
            st = svc.stats()["fabric"]
            assert st["drained_ranks"] == [1]
            drains = [e for e in svc.context.journal.tail(4096)
                      if e.get("e") == "health_drain"]
            assert len(drains) == 1
            assert drains[0]["peer"] == 1
            assert drains[0]["score"] < drains[0]["thr"]
            assert drains[0]["evidence"]        # decision carries proof
            # a drained rank stops taxing quotes
            assert svc._gang_health() == 1.0
            # recovery past the undrain threshold lifts it
            fake.ewma[1] = 0.95
            while svc._health_drained and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not svc._health_drained
            undrains = [e for e in svc.context.journal.tail(4096)
                        if e.get("e") == "health_undrain"]
            assert len(undrains) == 1 and undrains[0]["peer"] == 1
            snap = svc.context.journal.snapshot()
        assert journal_audit.audit({0: [snap]}) == []
    finally:
        params.unset("fabric_drain_sustain_s")


def test_fabric_one_bad_fold_does_not_drain():
    """The sustain window is the whole point: a single below-threshold
    observation must not shed a rank."""
    from parsec_tpu.service.fabric import ServingFabric
    params.set("fabric_drain_sustain_s", 30.0)
    try:
        with ServingFabric(nb_cores=2, max_active=4) as svc:
            fake = _FakeMonitor(svc.context.journal)
            svc.context.metrics._health = fake
            time.sleep(0.6)     # several dispatcher ticks
            assert svc.drains == 0
            assert 1 in svc._below_since        # stopwatch is running
            # recovery above the threshold resets the stopwatch
            fake.ewma[1] = 0.9
            deadline = time.monotonic() + 5.0
            while 1 in svc._below_since \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert 1 not in svc._below_since
    finally:
        params.unset("fabric_drain_sustain_s")


def test_fabric_gang_health_floor_and_disarm():
    from parsec_tpu.service.fabric import ServingFabric
    with ServingFabric(nb_cores=2, max_active=4) as svc:
        fake = _FakeMonitor(svc.context.journal)
        fake.ewma = {0: 1.0, 1: 0.4}
        svc.context.metrics._health = fake
        assert svc._gang_health() == 0.4
        svc._health_enable = False
        assert svc._gang_health() == 1.0


# ---------------------------------------------------------------------------
# flight recorder: the health snapshot in incident bundles
# ---------------------------------------------------------------------------

def test_flightrec_bundle_carries_health_and_comm_delta(tmp_path):
    from parsec_tpu.core.context import Context
    params.set("flightrec_enabled", 1)
    params.set("flightrec_dir", str(tmp_path))
    params.set("flightrec_min_interval_s", 0.0)
    params.set("health_interval_s", 0.0)
    try:
        with Context(nb_cores=2) as ctx:
            ctx.add_taskpool(_n_pool(10))
            ctx.wait(timeout=60)
            ctx.metrics.health.refresh(force=True)
            bundle = ctx.telemetry_incident("unit-test incident")
    finally:
        for k in ("flightrec_enabled", "flightrec_dir",
                  "flightrec_min_interval_s", "health_interval_s"):
            params.unset(k)
    assert bundle is not None
    # the dump runs on its own thread and writes incidents.jsonl last:
    # reading before that finds health-rank0.json created but empty
    manifest = os.path.join(bundle, "incidents.jsonl")
    deadline = time.monotonic() + 30.0
    while not os.path.exists(manifest) and time.monotonic() < deadline:
        time.sleep(0.02)
    path = os.path.join(bundle, "health-rank0.json")
    assert os.path.exists(path)
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["reason"] == "unit-test incident"
    assert doc["health"]["0"]["ewma"] == pytest.approx(1.0)
    assert doc["health_series"]["0"]        # bounded score series
    assert "comm_delta" in doc and "comm_window_s" in doc
