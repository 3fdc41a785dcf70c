"""Recovery-plane tests (ISSUE 10: contain -> RECOVER -> rejoin).

Unit layers: the lineage planner's minimal re-execution set on
hand-built DAGs, the per-collection rank translation, the termdet
rewind, the run_epoch task fence, incarnation-epoch frame fencing, the
degraded-checkpoint fail-fast, and the service's degraded -> recovering
-> healthy bookkeeping.

End to end: 2-rank kill_rank plans (PTG potrf and DTD chain) that END
IN COMPLETED, NUMERICALLY VALIDATED jobs on the survivor; recovery
disabled reproducing PR 5's containment; a killed-then-restarted rank
rejoining over TAG_REJOIN and serving its partition again; and the
slow 3-rank mid-run-kill acceptance run with the makespan bound.
"""

import os
import sys
import time

import numpy as np
import pytest

from parsec_tpu.core.errors import (CheckpointDegradedError,
                                    PeerFailedError)
from parsec_tpu.core.recovery import (LineageRecord, RecoveryUnsupported,
                                      dtd_skip_prefix, lineage_plan,
                                      minimal_plan)
from parsec_tpu.utils.mca import params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _run_distributed_with_env(fn, nranks, env, timeout=120,
                              tolerate_ranks=()):
    from parsec_tpu.comm.launch import run_distributed
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        return run_distributed(fn, nranks, timeout=timeout,
                               tolerate_ranks=tolerate_ranks)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------------------
# lineage planner: minimal re-execution set on hand-built DAGs
# ---------------------------------------------------------------------------

def test_lineage_plan_minimal_set():
    """Diamond DAG over tiles a/b/c/d; only d's final version is lost
    and b's intermediate survives -> re-execute exactly the producers
    on the lost path, not the whole log."""
    log = [
        LineageRecord("T1", reads=[("a", 0)], writes=[("b", 1)]),
        LineageRecord("T2", reads=[("a", 0)], writes=[("c", 1)]),
        LineageRecord("T3", reads=[("b", 1), ("c", 1)],
                      writes=[("d", 1)]),
        LineageRecord("T4", reads=[("d", 1)], writes=[("d", 2)]),
    ]
    surviving = {"a": 0, "b": 1, "c": 1}       # d died with its rank
    tasks, base = lineage_plan(log, surviving, {"d": 2})
    assert tasks == ["T3", "T4"]               # T1/T2 outputs survive
    assert base == {"b": 1, "c": 1}


def test_lineage_plan_walks_back_to_source():
    """Nothing of the lost chain survives: the walk reaches the version-0
    source (the registration snapshot / init_fn base)."""
    log = [
        LineageRecord("P0", reads=[("x", 0)], writes=[("x", 1)]),
        LineageRecord("P1", reads=[("x", 1)], writes=[("x", 2)]),
    ]
    tasks, base = lineage_plan(log, {"x": 0}, {"x": 2})
    assert tasks == ["P0", "P1"]
    assert base == {"x": 0}


def test_lineage_plan_broken_lineage_raises():
    with pytest.raises(RecoveryUnsupported):
        lineage_plan([], {}, {"ghost": 3})


# ---------------------------------------------------------------------------
# minimal_plan: the RECORDED-lineage replay set on hand-built DAGs
# (recorded plan == analytic plan; checkpoint-bounded cut; ring-evicted
# fallback)
# ---------------------------------------------------------------------------

def _chain_records(sent_to_dead=("T0",)):
    """Three-step in-place chain over tile a (v0 -> v3) plus an
    independent tile b task; T0's activations reached rank 1."""
    return [
        LineageRecord("T0", rmap={"C": ("a", 0)}, wmap={"C": ("a", 1)},
                      reads=[("a", 0)], writes=[("a", 1)],
                      dests={1} if "T0" in sent_to_dead else ()),
        LineageRecord("T1", rmap={"C": ("a", 1)}, wmap={"C": ("a", 2)},
                      reads=[("a", 1)], writes=[("a", 2)]),
        LineageRecord("T2", rmap={"C": ("a", 2)}, wmap={"C": ("a", 3)},
                      reads=[("a", 2)], writes=[("a", 3)]),
        LineageRecord("U0", rmap={"B": ("b", 0)}, wmap={"B": ("b", 1)},
                      reads=[("b", 0)], writes=[("b", 1)]),
    ]


_CHAIN_EDGES = {
    "T0": [("desc", "a", 0)],
    "T1": [("task", "T0", "C", "C", "local", False)],
    "T2": [("task", "T1", "C", "C", "local", False)],
    "U0": [("desc", "b", 0)],
}


def test_minimal_plan_matches_analytic_set():
    """Recorded plan == analytic plan: T0 fed the dead rank, so the
    whole a-chain re-runs (re-running T0 regresses tile a below its
    live version — every recorded later writer rejoins); the untouched
    b task stays OUT of the plan."""
    plan = minimal_plan(_chain_records(), dead_set={1},
                        live={"a": 3, "b": 1},
                        materializable={"a": {0}, "b": {0}},
                        edges=lambda k: _CHAIN_EDGES.get(k, ()))
    assert plan.tasks == {"T0", "T1", "T2"}     # analytic closure
    assert plan.base == {"a": 0}                # desc cut at snapshot
    assert not plan.needs and not plan.synth


def test_minimal_plan_synthesizes_materialized_edges():
    """A pending consumer of a SKIPPED producer gets its delivery
    synthesized from the live-intact version instead of re-running the
    producer."""
    edges = dict(_CHAIN_EDGES)
    edges["P0"] = [("task", "U0", "B", "X", "local", False)]
    plan = minimal_plan(_chain_records(), dead_set={1}, pending=["P0"],
                        live={"a": 3, "b": 1},
                        materializable={"a": {0}, "b": {0}},
                        edges=lambda k: edges.get(k, ()))
    assert "U0" not in plan.tasks and "P0" in plan.tasks
    assert ("P0", "X", "b", 1, "U0") in plan.synth


def test_minimal_plan_checkpoint_bounds_replay_depth():
    """Checkpoint-bounded cut: with tile a's v2 captured by the
    incremental checkpoint store, a consumer needing v2 synthesizes
    from the capture — the walk stops there instead of rewinding to
    the snapshot and re-running the whole chain."""
    edges = dict(_CHAIN_EDGES)
    edges["P1"] = [("task", "T1", "C", "X", "local", False)]
    # without the checkpoint: T1 must re-run, dragging T0 and T2 in
    deep = minimal_plan(_chain_records(sent_to_dead=()), dead_set={1},
                        pending=["P1"], live={"a": 3, "b": 1},
                        materializable={"a": {0}, "b": {0}},
                        edges=lambda k: edges.get(k, ()))
    assert {"T0", "T1", "T2"} <= deep.tasks
    # with (a, 2) checkpointed the plan is ONE pending task + a synth
    shallow = minimal_plan(_chain_records(sent_to_dead=()),
                           dead_set={1}, pending=["P1"],
                           live={"a": 3, "b": 1},
                           materializable={"a": {0, 2}, "b": {0}},
                           edges=lambda k: edges.get(k, ()))
    assert shallow.tasks == {"P1"}
    assert ("P1", "X", "a", 2, "T1") in shallow.synth


def test_minimal_plan_ring_evicted_falls_back():
    """A producer whose record the ring evicted cannot be planned
    around: RecoveryUnsupported — the caller takes the full
    restore-point replay (counted in full_replays)."""
    recs = _chain_records()[1:]    # T0's record evicted
    with pytest.raises(RecoveryUnsupported):
        minimal_plan(recs, dead_set={1}, pending=["P2"],
                     live={"a": 3, "b": 1},
                     materializable={"a": {0}, "b": {0}},
                     edges=lambda k:
                     {"P2": [("task", "T0", "C", "X", "local",
                              False)]}.get(k, ()))


def test_minimal_plan_unrecorded_later_writer_falls_back():
    """Rewinding a tile whose LIVE version has no recorded writer
    (the ring rolled past it) is unsound — the plan refuses."""
    recs = _chain_records()
    with pytest.raises(RecoveryUnsupported):
        minimal_plan(recs, dead_set={1}, live={"a": 9, "b": 1},
                     materializable={"a": {0}, "b": {0}},
                     edges=lambda k: _CHAIN_EDGES.get(k, ()))


def test_minimal_plan_remote_edges_become_needs():
    """A task-fed input produced on a LIVE survivor is a negotiation
    need, never a silent assumption."""
    edges = dict(_CHAIN_EDGES)
    edges["P3"] = [("task", "Q", "C", "Y", ("peer", 2), False)]
    plan = minimal_plan(_chain_records(sent_to_dead=()), dead_set={1},
                        pending=["P3"], live={"a": 3, "b": 1},
                        materializable={"a": {0}, "b": {0}},
                        edges=lambda k: edges.get(k, ()))
    assert (2, "P3", "Y") in plan.needs


def test_minimal_plan_synth_drops_when_producer_joins():
    """An edge that first chose synthesis must lose its synth twin if
    the producer later joins the plan (the natural re-delivery would
    otherwise double-arrive)."""
    recs = _chain_records(sent_to_dead=())
    recs.append(LineageRecord("D0", rmap={"B": ("b", 1)},
                              wmap={}, reads=[("b", 1)], dests={1}))
    edges = dict(_CHAIN_EDGES)
    edges["D0"] = [("task", "U0", "B", "X", "local", False)]
    # P4 needs b@0 which is NOT materializable as a synth-only story:
    # force U0 to rejoin via a desc rewind of b
    edges["P4"] = [("task", "U0", "B", "X", "local", False),
                   ("desc", "b", 0)]
    plan = minimal_plan(recs, dead_set={1}, pending=["P4"],
                        live={"a": 3, "b": 1},
                        materializable={"a": {0}, "b": {0}},
                        edges=lambda k: edges.get(k, ()))
    # rewinding b to 0 pulls writer U0 in; every synth against U0 is
    # dropped in favor of the natural delivery
    assert "U0" in plan.tasks
    assert not any(s[4] == "U0" for s in plan.synth)


# ---------------------------------------------------------------------------
# DTD insert-stream skip agreement: the pure prefix planner on
# hand-built write ladders (r15)
# ---------------------------------------------------------------------------

#: a 10-insert single-tile chain: insert i writes version i+1
_LADDER = [(i, "t") for i in range(10)]


def test_dtd_skip_prefix_full_prefix():
    """Every survivor's frontier covers the whole stream and someone
    holds the final version: the whole prefix skips."""
    k, holders, vcut = dtd_skip_prefix(
        {0: 10, 2: 10}, {0: {"t": 10}, 2: {"t": 4}}, _LADDER)
    assert k == 10 and holders == {"t": 0} and vcut == {"t": 10}


def test_dtd_skip_prefix_cuts_to_held_version():
    """Frontiers split inside a window (the mid-insert kill shape):
    the agreed prefix is the largest K where some survivor HOLDS the
    cut version — not just the min frontier."""
    # min frontier 8, but the best-landed survivor holds only v6: the
    # scan walks down to the materializable cut
    k, holders, vcut = dtd_skip_prefix(
        {0: 8, 2: 40}, {0: {"t": 6}, 2: {"t": 3}}, _LADDER)
    assert k == 6 and holders == {"t": 0} and vcut == {"t": 6}
    # the lower-landed survivor's version also works when it is the
    # only consistent cut
    k, holders, _ = dtd_skip_prefix(
        {0: 8, 2: 40}, {0: {"t": 0}, 2: {"t": 3}}, _LADDER)
    assert k == 3 and holders == {"t": 2}


def test_dtd_skip_prefix_no_holder_falls_back():
    """Nobody holds any cut version (the dead rank's payloads never
    landed): no common prefix — the gang takes the full replay."""
    k, holders, vcut = dtd_skip_prefix(
        {0: 10, 2: 10}, {0: {}, 2: {}}, _LADDER)
    assert k == 0 and not holders and not vcut


def test_dtd_skip_prefix_unwritten_tiles_need_no_holder():
    """A tile the prefix never writes (vcut 0) restores from the
    pool-attach snapshot instead of needing a holder."""
    writes = [(0, "a"), (1, "a")]
    k, holders, vcut = dtd_skip_prefix(
        {0: 5, 1: 5}, {0: {"a": 2, "b": 7}, 1: {}}, writes)
    assert k == 5
    assert holders == {"a": 0} and vcut == {"a": 2}


def test_dtd_skip_prefix_multi_tile_intersection():
    """Two tiles: the agreed K must satisfy BOTH materializable cuts
    simultaneously."""
    writes = [(0, "a"), (1, "b"), (2, "a"), (3, "b")]
    landed = {0: {"a": 2, "b": 1}, 1: {"a": 1, "b": 2}}
    k, holders, vcut = dtd_skip_prefix({0: 4, 1: 4}, landed, writes)
    assert k == 4
    assert vcut == {"a": 2, "b": 2}
    assert holders == {"a": 0, "b": 1}
    # rank 1's b-ladder stops at v1: K drops to where both cuts hold
    k, _h, vcut = dtd_skip_prefix(
        {0: 4, 1: 4}, {0: {"a": 2, "b": 1}, 1: {"a": 1, "b": 1}},
        writes)
    assert k == 3 and vcut == {"a": 2, "b": 1}


# ---------------------------------------------------------------------------
# DTD skip machinery: pool-level replay (ghost prefix, holder seeding,
# tid-gated filter) and the pool-side full votes
# ---------------------------------------------------------------------------

def _dtd_chain_pool(ctx, steps=10):
    import numpy as np
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    from parsec_tpu.dsl.dtd import INOUT, DTDTaskpool
    V = VectorTwoDimCyclic(mb=4, lm=4, nodes=1, myrank=0, name="Vsk")
    V.data_of(0).copy_on(0).payload[:] = 0.0
    tp = DTDTaskpool("skiptest")
    tp.recovery_collections = [V]
    ctx.add_taskpool(tp)
    ctx.start()
    t = tp.tile_of(V, 0)

    def step(T):
        return T + 1.0
    for _ in range(steps):
        tp.insert_task(step, (t, INOUT))
    tp.wait(timeout=30)
    return V, tp, t, step


def test_dtd_skip_replay_ghosts_prefix_and_seeds_holder():
    """Single-pool replay mechanics, deterministically: arm a skip at
    K=6 with this rank the holder of the seeded v6 cut — the replay
    ghost-tracks 6 inserts (versions advance, no body runs), the
    finalize seeds the cut payload, and exactly the 4 post-prefix
    bodies re-run to the exact final value."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.taskpool import TaskpoolState
    from parsec_tpu.core.termdet import TermdetState
    from parsec_tpu.dsl.dtd import INOUT
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        V, tp, t, step = _dtd_chain_pool(ctx, steps=10)
        assert tp._lineage is not None
        rep = tp.dtd_skip_report()
        assert rep.get("full") is None and rep["frontier"] == 10
        wire = t.wire_key
        assert rep["landed"] == {wire: 10}
        # drive the restart shape _restart_pool uses
        tp.state = TaskpoolState.ATTACHED
        tp.run_epoch += 1
        assert tp.termdet.taskpool_reset(tp, force_terminated=True) \
            == TermdetState.TERMINATED
        with ctx._lock:
            ctx._active_taskpools += 1
        tp._done_event.clear()
        tp.termdet.taskpool_addto_runtime_actions(tp, 1)
        tp.recovery_reset()
        tp.dtd_arm_skip(6, {wire: 0},
                        {wire: np.full(4, 6.0, np.float32)}, {wire: 6})
        t2 = tp.tile_of(V, 0)
        for _ in range(10):
            tp.insert_task(step, (t2, INOUT))
        tp.dtd_skip_finish()
        tp.ready()
        assert tp.wait_local(30)
        val = np.asarray(V.data_of(0).pull_to_host().payload)
        np.testing.assert_allclose(val, 10.0)
        assert sorted(tp._pos_done) == [6, 7, 8, 9]  # prefix ghosted
        # one skip per generation: the next death votes full
        assert tp.dtd_skip_report().get("full")
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


def test_dtd_skip_report_votes_full_on_unskippable_pools():
    """Region lanes and tile_new wire keys latch the pool unskippable
    (the report votes full instead of planning from partial
    evidence)."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.dsl.dtd import (INOUT, INPUT, DTDTaskpool, Region)
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        from parsec_tpu.data.matrix import VectorTwoDimCyclic
        V = VectorTwoDimCyclic(mb=4, lm=4, nodes=1, myrank=0,
                               name="Vrl")
        tp = DTDTaskpool("regions")
        tp.recovery_collections = [V]
        ctx.add_taskpool(tp)
        ctx.start()
        t = tp.tile_of(V, 0)
        tp.insert_task(lambda T: None,
                       (t, INPUT | Region("u", (slice(0, 2),))))
        tp.wait(timeout=30)
        assert tp.dtd_skip_report()["full"] == "region lanes"

        tp2 = DTDTaskpool("news")
        tp2.recovery_collections = [V]
        ctx.add_taskpool(tp2)
        tn = tp2.tile_new((4,))
        tp2.insert_task(lambda T: T + 1.0, (tn, INOUT))
        tp2.wait(timeout=30)
        assert "tile_new" in tp2.dtd_skip_report()["full"]
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


def _stub_rde(rank, peers, sent):
    import types
    ce = types.SimpleNamespace(
        rank=rank, nranks=max([rank] + list(peers)) + 1,
        dead_peers=set(),
        send_am=lambda tag, dst, payload: sent.append((dst, payload)))
    return types.SimpleNamespace(
        ce=ce, _live_peers=lambda: list(peers),
        recovery_coordinator=lambda: min([rank] + list(peers)))


def test_dtd_skip_round_coordinator_cuts_and_broadcasts():
    """Coordinator side of the skip round: a pre-delivered peer report
    (divergent frontier) cuts the prefix; a report from a FOREIGN rank
    (one that rejoined mid-round — not in the round's peer snapshot)
    is ignored."""
    from parsec_tpu.core.context import Context
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        V, tp, t, _step = _dtd_chain_pool(ctx, steps=10)
        rec = ctx.recovery
        sent = []
        rec._rde = _stub_rde(0, [2], sent)
        wire = t.wire_key
        with rec._ctl_cond:
            rec._skip_reports[(tp.taskpool_id, 2)] = \
                (0, {"frontier": 6, "landed": {wire: 4}})
            # rank 3 rejoined mid-round: its unsolicited report must
            # not join the quorum (it is not in the peer snapshot)
            rec._skip_reports[(tp.taskpool_id, 3)] = \
                (0, {"frontier": 1, "landed": {}})
        spec = {"tp": tp, "collections": tp.recovery_collections,
                "replay": lambda tp: None}
        skip = rec._plan_dtd_skip(tp, spec, {1})
        # K honors rank 2's held v4 cut, not its frontier of 6 (this
        # rank holds v10, which no K <= 6 can use)
        assert skip["prefix"] == 4
        assert skip["holders"] == {wire: 2}
        assert skip["seeds"] == {}          # rank 2 holds the cut
        # the agreed prefix was broadcast to the round's peers only
        assert [d for d, _m in sent] == [2]
        assert sent[0][1]["k"] == "skipset" \
            and sent[0][1]["prefix"] == 4
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


def test_dtd_skip_round_peer_full_vote_converges_gang():
    """A survivor whose lineage ring evicted votes full: the
    coordinator broadcasts prefix 0 (everyone falls back FAST instead
    of timing out) and takes the full replay itself."""
    from parsec_tpu.core.context import Context
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        _V, tp, _t, _step = _dtd_chain_pool(ctx, steps=10)
        rec = ctx.recovery
        sent = []
        rec._rde = _stub_rde(0, [2], sent)
        with rec._ctl_cond:
            rec._skip_reports[(tp.taskpool_id, 2)] = \
                (0, {"full": "evicted ring"})
        spec = {"tp": tp, "collections": tp.recovery_collections,
                "replay": lambda tp: None}
        with pytest.raises(RecoveryUnsupported, match="voted full"):
            rec._plan_dtd_skip(tp, spec, {1})
        assert sent and sent[0][1]["prefix"] == 0
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


def test_dtd_skip_round_participant_timeout_falls_back():
    """Participant side with no coordinator broadcast (a coordinator
    that died — or was displaced by a rejoin — mid-round): the bounded
    wait expires into the full-replay fallback instead of a hang."""
    from parsec_tpu.core.context import Context
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        _V, tp, _t, _step = _dtd_chain_pool(ctx, steps=10)
        rec = ctx.recovery
        sent = []
        # this rank is NOT the coordinator: ce.rank 2, coordinator 0
        rde = _stub_rde(2, [0], sent)
        rec._rde = rde
        rec.agree_timeout = 0.2
        spec = {"tp": tp, "collections": tp.recovery_collections,
                "replay": lambda tp: None}
        t0 = time.monotonic()
        with pytest.raises(RecoveryUnsupported, match="never arrived"):
            rec._plan_dtd_skip(tp, spec, {1})
        assert time.monotonic() - t0 < 2.0
        # the report reached the coordinator before the wait
        assert sent and sent[0][0] == 0 and sent[0][1]["k"] == "skipf"
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


# ---------------------------------------------------------------------------
# multi-round need negotiation (r15): a widened closure re-negotiates
# against frozen plans instead of falling back
# ---------------------------------------------------------------------------

def _need_round_harness(ctx, cap):
    """A RecoveryCoordinator wired for _plan_minimal control-flow
    tests: _compute_minimal is a recorded stub that simulates a peer's
    re-feed seed landing MID-WINDOW (the merged closure then widens
    the remote needs — the exact r12 fallback shape)."""
    rec = ctx.recovery
    rec.need_rounds_cap = cap
    rec.agree_window = 0.01
    rec._rde = _stub_rde(0, [2], [])

    from parsec_tpu.core.recovery import ReplayPlan
    calls = {"negotiated": []}

    def compute(tp, spec, dead_set, extra):
        plan = ReplayPlan()
        plan.tasks = {"A"} | set(extra)
        if not extra:
            # a peer's need lands inside the pre-freeze window: the
            # freeze pops it and the recompute widens the needs
            with rec._ctl_cond:
                rec._extra_seeds[tp.taskpool_id] = {"B"}
        else:
            # the merged seed closure reaches a producer on rank 2
            plan.needs = [(2, "W", "F")]
        return plan

    def negotiate(tp, needs):
        calls["negotiated"].append(list(needs))
        return True

    rec._compute_minimal = compute
    rec._negotiate_needs = negotiate
    return rec, calls


def test_plan_minimal_second_round_recovers_widened_needs():
    """The r12 fallback shape — merged re-feed seeds widen the remote
    needs after the freeze — now negotiates a SECOND round and stays
    minimal, counter-proven (widened + acked move, exhausted does
    not)."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.taskpool import Taskpool
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        rec, calls = _need_round_harness(ctx, cap=2)
        tp = Taskpool("nr")
        before = dict(rec.need_round_counts)
        plan = rec._plan_minimal(tp, {"tp": tp}, {1})
        assert "B" in plan.tasks
        assert calls["negotiated"] == [[(2, "W", "F")]]
        after = rec.need_round_counts
        assert after["widened"] == before["widened"] + 1
        assert after["acked"] == before["acked"] + 1
        assert after["exhausted"] == before["exhausted"]
        # the frozen replay set is published for peers' second rounds
        with rec._ctl_cond:
            assert rec._frozen_tasks[tp.taskpool_id] == plan.tasks
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


def test_plan_minimal_round_cap_exhausts_to_full():
    """recovery_need_rounds=0 restores the r12 single-shot behavior:
    a widened closure falls back, counted as exhausted."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.taskpool import Taskpool
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        rec, calls = _need_round_harness(ctx, cap=0)
        tp = Taskpool("nr0")
        with pytest.raises(RecoveryUnsupported,
                           match="recovery_need_rounds"):
            rec._plan_minimal(tp, {"tp": tp}, {1})
        assert rec.need_round_counts["exhausted"] == 1
        assert not calls["negotiated"]
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


def test_handle_need_acks_frozen_plan_when_covered():
    """A second-round need against a FROZEN plan acks iff the resolved
    producers are already in the frozen replay set (the r12
    unconditional nack forced full replays the plan satisfied
    anyway)."""
    from parsec_tpu.core.context import Context
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        rec = ctx.recovery
        sent = []
        rec._rde = _stub_rde(0, [2], sent)
        tp, tc = _frozen_need_pool(ctx)
        tpid = tp.taskpool_id
        with rec._lock:
            rec._active.add(tpid)
        with rec._ctl_cond:
            rec._plan_state[tpid] = "frozen"
            rec._frozen_tasks[tpid] = {("W", 0), ("W", 1)}
        rec._handle_need(2, {"tp": tpid,
                             "needs": [[("W", 1), "P"]]})
        assert sent[-1][1] == {"k": "need_ack", "tp": tpid, "ok": True}
        # a need whose producer the frozen plan does NOT re-run nacks
        with rec._ctl_cond:
            rec._frozen_tasks[tpid] = {("W", 5)}
        rec._handle_need(2, {"tp": tpid,
                             "needs": [[("W", 1), "P"]]})
        assert sent[-1][1]["ok"] is False
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


def _frozen_need_pool(ctx):
    """A 2-task chain pool whose need edges _resolve_need can invert:
    W(i) reads P from W(i-1)."""
    from parsec_tpu.core.task import (Dep, FromDesc, FromTask, READ,
                                      RW, TaskClass, ToDesc, ToTask)
    from parsec_tpu.core.taskpool import ParameterizedTaskpool
    from parsec_tpu.data.matrix import VectorTwoDimCyclic
    V = VectorTwoDimCyclic(mb=2, lm=8, nodes=1, myrank=0, name="Vfn")
    V.set_init(lambda m, n=0: np.zeros(2, np.float32))
    tc = TaskClass(
        "W", params=[("i", lambda g, l: range(4))],
        affinity=lambda loc, V=V: V(loc["i"]),
        flows=[READ("P",
                    inputs=[Dep(FromTask("W", "T",
                                         lambda loc:
                                         {"i": loc["i"] - 1}),
                                guard=lambda loc: loc["i"] > 0)]),
               RW("T",
                  inputs=[Dep(FromDesc(lambda loc, V=V: V(loc["i"])))],
                  outputs=[Dep(ToTask("W", "P",
                                      lambda loc: {"i": loc["i"] + 1}),
                               guard=lambda loc: loc["i"] < 3),
                           Dep(ToDesc(lambda loc, V=V: V(loc["i"])))])],
        incarnations=[("cpu", lambda es, task: None)])
    tp = ParameterizedTaskpool("fn")
    tp.add_task_class(tc)
    tp.recovery_collections = [V]
    ctx.add_taskpool(tp)
    ctx.wait(timeout=30)
    return tp, tc


# ---------------------------------------------------------------------------
# completed-pool retirement handshake (r15): coordinator confirms
# global quiescence before a pool leaves restartable state
# ---------------------------------------------------------------------------

def test_retirement_handshake_coordinator_quorum():
    """Coordinator side: local completion alone keeps the pool
    restartable; once EVERY live rank reported, the pool retires, the
    confirmation broadcasts, and the counter moves."""
    from parsec_tpu.comm.engine import SocketCE
    from parsec_tpu.comm.launch import _probe_port_base
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.taskpool import TaskpoolState
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    params.set("recovery_enable", 1)
    ce = SocketCE(0, 2, _probe_port_base(2))
    ctx = Context(nb_cores=1, rank=0, nranks=2)
    rde = RemoteDepEngine(ce, ctx)
    sent = []
    ce.send_am = lambda tag, dst, payload: sent.append((dst, payload))
    try:
        from parsec_tpu.core.taskpool import Taskpool
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=8, ln=8, nodes=2,
                              myrank=0, name="Aret")
        tp = Taskpool("ret")
        tp.recovery_collections = [A]
        ctx.add_taskpool(tp)
        rec = ctx.recovery
        tp.state = TaskpoolState.DONE          # locally complete
        rec._pool_done(tp)
        assert not tp.retired                  # rank 1 outstanding
        with rec._lock:
            assert rec._specs[tp.taskpool_id]["completed_at"] \
                is not None
        rec._on_recover_msg(1, {"k": "retire", "tp": tp.taskpool_id})
        assert tp.retired and rec.retirements == 1
        assert any(p.get("k") == "retired" for _d, p in sent)
        # retired pools are never recovery candidates again
        handled, leave = rec.on_peer_dead(
            1, PeerFailedError(1, "x", detector="close"), [])
        assert handled and leave == []
        with rec._lock:
            assert tp.taskpool_id not in rec._active
        tp.cancel()
    finally:
        ce._stop = True
        rde.fini()
        ctx.fini()
        params.set("recovery_enable", 0)


def test_retirement_broadcast_applies_on_peer():
    """Non-coordinator side: the coordinator's confirmed ``retired``
    broadcast retires a locally-complete pool; a pool mid-restart
    ignores a stale confirmation."""
    from parsec_tpu.comm.engine import SocketCE
    from parsec_tpu.comm.launch import _probe_port_base
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.taskpool import Taskpool, TaskpoolState
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    params.set("recovery_enable", 1)
    ce = SocketCE(0, 2, _probe_port_base(2))
    ctx = Context(nb_cores=1, rank=0, nranks=2)
    rde = RemoteDepEngine(ce, ctx)
    ce.send_am = lambda tag, dst, payload: None
    try:
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=8, ln=8, nodes=2,
                              myrank=0, name="Bret")
        tp = Taskpool("ret1")
        tp.recovery_collections = [A]
        ctx.add_taskpool(tp)
        rec = ctx.recovery
        tp.state = TaskpoolState.DONE
        # a restart owns the pool: the stale confirmation is ignored
        with rec._lock:
            rec._active.add(tp.taskpool_id)
        rec._on_recover_msg(0, {"k": "retired", "tp": tp.taskpool_id})
        assert not tp.retired
        with rec._lock:
            rec._active.discard(tp.taskpool_id)
        rec._on_recover_msg(0, {"k": "retired", "tp": tp.taskpool_id})
        assert tp.retired
        tp.cancel()
    finally:
        ce._stop = True
        rde.fini()
        ctx.fini()
        params.set("recovery_enable", 0)


def test_single_rank_pool_retires_at_completion():
    """No peers: local completion IS global quiescence — the pool
    leaves restartable state immediately instead of dangling through
    the 30 s grace window."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        from parsec_tpu.apps.potrf import potrf_taskpool
        n, mb = 32, 16
        rng = np.random.default_rng(2)
        a = rng.standard_normal((n, n)).astype(np.float32)
        spd = (a @ a.T + n * np.eye(n)).astype(np.float32)
        A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n,
                              name="Aret1").from_array(spd.copy())
        tp = potrf_taskpool(A, device="cpu")
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
        assert tp.retired
        assert ctx.recovery.retirements >= 1
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


def test_retirement_succession_on_coordinator_death():
    """Coordinator succession (r17): the handshake coordinator dying
    with the collected reports must NOT degrade retirement to the
    grace window — survivors re-report to the new lowest live rank,
    which re-collects quorum over the shrunken live set."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.taskpool import Taskpool, TaskpoolState
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    params.set("recovery_enable", 1)
    ctx = Context(nb_cores=1)
    try:
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=8, ln=8, nodes=3,
                              myrank=1, name="Asucc")
        tp = Taskpool("succ")
        tp.recovery_collections = [A]
        ctx.add_taskpool(tp)
        rec = ctx.recovery
        sent = []
        rec._rde = _stub_rde(1, [0, 2], sent)   # we are rank 1 of 3
        tp.state = TaskpoolState.DONE
        rec._pool_done(tp)
        # report went to the original coordinator (rank 0), who now
        # dies with it — the pool must still be restartable
        assert sent and sent[-1] == (0, {"k": "retire",
                                         "tp": tp.taskpool_id})
        assert not tp.retired
        rec._rde = _stub_rde(1, [2], sent)      # rank 0 died
        rec._rde.ce.dead_peers.add(0)
        rec._succeed_retirements(0)
        # this rank became coordinator and re-recorded its own report;
        # quorum over the live set {1, 2} still waits on rank 2
        assert not tp.retired
        evs = [ev for ev in ctx.journal.tail(256)
               if ev.get("e") == "retire_succession"]
        assert evs and evs[-1]["pool"] == tp.taskpool_id \
            and evs[-1]["coord"] == 1
        # rank 2's succession re-report completes quorum -> retired
        rec._on_recover_msg(2, {"k": "retire", "tp": tp.taskpool_id})
        assert tp.retired and rec.retirements == 1
        assert (2, {"k": "retired", "tp": tp.taskpool_id}) in sent
        tp.cancel()
    finally:
        ctx.fini()
        params.set("recovery_enable", 0)


def test_refired_completion_emits_exactly_one_job_done():
    """Service seam: a recovery restart re-firing a completed pool's
    termination callbacks is absorbed below the service — exactly ONE
    terminal job_done per job (SLO histograms and waiters would
    otherwise double-observe)."""
    from parsec_tpu.service.service import JobService
    from parsec_tpu.core.taskpool import Taskpool
    svc = JobService(max_active=1, nb_cores=1)
    try:
        events = []
        svc.context.pins_register(
            "job_done", lambda es, ev, job: events.append(job.job_id))
        job = svc.submit(lambda: Taskpool("j1"), name="j1")
        assert job.wait(10)
        deadline = time.monotonic() + 5
        while not events and time.monotonic() < deadline:
            time.sleep(0.01)
        assert events == [job.job_id]
        # the recovery restart re-fires the pool's completion path
        svc._finish(job)
        svc._finish(job)
        time.sleep(0.05)
        assert events == [job.job_id]
    finally:
        svc.shutdown(timeout=10)


# ---------------------------------------------------------------------------
# partition re-mapping: per-collection rank translation
# ---------------------------------------------------------------------------

def test_rank_translation_adopts_partition():
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    A = TwoDimBlockCyclic(mb=4, nb=4, lm=16, ln=16, nodes=2, myrank=0,
                          name="A")
    mine = set(A.local_tiles())
    assert all(A.rank_of(m, n) == 0 for m, n in mine)
    A.set_rank_translation({1: 0})
    try:
        # rank_of stays the pure distribution; owner_of routes around
        assert any(A.rank_of(m, n) == 1
                   for m in range(A.mt) for n in range(A.nt))
        assert all(A.owner_of(m, n) == 0
                   for m in range(A.mt) for n in range(A.nt))
        adopted = set(A.local_tiles()) - mine
        assert adopted, "dead rank's tiles must appear local"
        m, n = sorted(adopted)[0]
        assert A.data_of(m, n) is not None    # materializes, no raise
    finally:
        A.set_rank_translation(None)
    assert set(A.local_tiles()) == mine


def test_rank_translation_is_per_collection():
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    A = TwoDimBlockCyclic(mb=4, nb=4, lm=8, ln=8, nodes=2, myrank=0,
                          name="A")
    B = TwoDimBlockCyclic(mb=4, nb=4, lm=8, ln=8, nodes=2, myrank=0,
                          name="B")
    A.set_rank_translation({1: 0})
    try:
        assert len(A.local_tiles()) == 4
        assert len(B.local_tiles()) == 2      # B untouched
    finally:
        A.set_rank_translation(None)


def test_taskclass_rank_of_translates():
    from parsec_tpu.core.task import TaskClass
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    A = TwoDimBlockCyclic(mb=4, nb=4, lm=16, ln=16, nodes=2, myrank=0,
                          name="A")
    tc = TaskClass("T", params=[("m", lambda g, l: range(4))],
                   affinity=lambda loc, A=A: A(0, loc["m"]))
    ranks = {m: tc.rank_of({"m": m}) for m in range(4)}
    assert 1 in ranks.values()
    A.set_rank_translation({1: 0})
    try:
        assert all(tc.rank_of({"m": m}) == 0 for m in range(4))
    finally:
        A.set_rank_translation(None)


# ---------------------------------------------------------------------------
# termdet rewind + run_epoch fence
# ---------------------------------------------------------------------------

def test_termdet_reset_rewinds_without_firing():
    from parsec_tpu.core.taskpool import Taskpool
    from parsec_tpu.core.termdet import LocalTermdet
    td = LocalTermdet()
    tp = Taskpool("t")
    fired = []
    td.monitor(tp, lambda: fired.append(1))
    td.taskpool_addto_runtime_actions(tp, 1)
    td.taskpool_ready(tp)
    td.taskpool_addto_nb_tasks(tp, 5)
    from parsec_tpu.core.termdet import TermdetState
    assert td.taskpool_reset(tp) == TermdetState.BUSY
    assert tp.nb_tasks == 0 and tp.nb_pending_actions == 0
    assert not fired
    # the rewound pool re-runs the attach->ready lifecycle and
    # terminates on the NEW generation's counts only
    td.taskpool_addto_runtime_actions(tp, 1)
    td.taskpool_addto_nb_tasks(tp, 2)
    td.taskpool_ready(tp)
    td.taskpool_addto_runtime_actions(tp, -1)
    td.taskpool_addto_nb_tasks(tp, -2)
    assert fired == [1]
    # a TERMINATED pool refuses the plain rewind (completed
    # concurrently)...
    assert td.taskpool_reset(tp) is None
    # ...but force_terminated rewinds it — local completion is not
    # global completion, and the caller re-arms the released
    # bookkeeping on the returned TERMINATED
    assert td.taskpool_reset(tp, force_terminated=True) \
        == TermdetState.TERMINATED


def test_run_epoch_fence_discards_stale_tasks():
    """A task scheduled before a restart must neither execute nor touch
    the re-counted termdet when it surfaces after the epoch bump."""
    from parsec_tpu.core import scheduling
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.task import Task, TaskClass
    from parsec_tpu.core.taskpool import Taskpool
    ctx = Context(nb_cores=1)
    try:
        tp = Taskpool("fence")
        ran = []
        tc = TaskClass("X", body=lambda: ran.append(1))
        tp.add_task_class(tc)
        ctx.add_taskpool(tp)
        stale = Task(tc, tp, {})
        tp.run_epoch += 1                  # restart happened
        before = tp.nb_tasks
        scheduling.task_progress(ctx.streams[0], stale)
        assert not ran
        assert tp.nb_tasks == before       # no decrement
        scheduling.complete_execution(ctx.streams[0], stale)
        assert tp.nb_tasks == before
        tp.cancel()
        ctx.wait(timeout=10)
    finally:
        ctx.fini()


def test_recovery_busy_blocks_quiescence_idle():
    """A queued/active recovery restart must hold global quiescence
    open: _local_idle stays False and the sole-survivor short-circuit
    waits — otherwise Context.wait hands tiles to the application
    while the restore rewinds them (the completed-pool-grace race)."""
    from parsec_tpu.comm.engine import SocketCE
    from parsec_tpu.comm.launch import _probe_port_base
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.core.context import Context
    params.set("recovery_enable", 1)
    ce = SocketCE(0, 2, _probe_port_base(2))
    ctx = Context(nb_cores=1, rank=0, nranks=2)
    rde = RemoteDepEngine(ce, ctx)
    try:
        rec = ctx.recovery
        assert not rec.busy() and rde._local_idle()
        with rec._lock:
            rec._pending_dead.add(1)     # death accepted, not processed
        assert rec.busy()
        assert not rde._local_idle()     # quiescence must not pass
        with pytest.raises(TimeoutError):
            rde._wait_recovery_idle(time.monotonic() + 0.1)
        with rec._lock:
            rec._pending_dead.clear()
        assert rde._local_idle()
    finally:
        ce._stop = True
        rde.fini()
        ctx.fini()
        params.set("recovery_enable", 0)


def test_stale_body_discard_taints_tile_versions():
    """A stale-generation body that RAN may have mutated its write-flow
    tiles in place without a version bump (complete_write is skipped by
    the discard).  The epoch-fence discard must advance those version
    clocks, or minimal replay would synthesize from a 'live-intact'
    payload that is neither — the silent-corruption class the chaos
    smoke caught under load."""
    from parsec_tpu.core import scheduling
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.task import RW, Task, TaskClass
    from parsec_tpu.core.taskpool import Taskpool
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    ctx = Context(nb_cores=1)
    try:
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=4, ln=4, name="At")
        d = A.data_of(0, 0)
        tp = Taskpool("taint")
        tc = TaskClass("X", flows=[RW("T")], body=lambda T: None)
        tp.add_task_class(tc)
        ctx.add_taskpool(tp)
        stale = Task(tc, tp, {})
        stale.data["T"] = d.copy_on(0)
        before = d.newest_version()
        tp.run_epoch += 1            # a restart fenced the generation
        scheduling.complete_execution(ctx.streams[0], stale)
        assert d.newest_version() > before   # the mutation is visible
        tp.cancel()
        ctx.wait(timeout=10)
    finally:
        ctx.fini()


# ---------------------------------------------------------------------------
# incarnation-epoch frame fencing + Safra reconcile
# ---------------------------------------------------------------------------

def test_epoch_fence_drops_stale_incarnation_frames():
    from parsec_tpu.comm.engine import SocketCE
    from parsec_tpu.comm.launch import _probe_port_base
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.core.context import Context
    ce = SocketCE(0, 2, _probe_port_base(2))
    ctx = Context(nb_cores=1, rank=0, nranks=2)
    rde = RemoteDepEngine(ce, ctx)
    try:
        with rde._term_lock:
            rde._sent_to[1] = 3
            rde._recv_from[1] = 2
            rde._app_sent += 3
            rde._app_recv += 2
        rde.recovery_reconcile(1)
        assert rde._balance() == 0         # dead contribution removed
        # a pre-death straggler (no _ep) is fenced WITHOUT a credit
        rde._activate_cb(1, {"tp": 999, "_fid": (1, 7)})
        assert rde._balance() == 0
        with rde._dlock:
            assert not rde._delayed        # not even parked
        # the rejoined incarnation (epoch 1) passes the fence
        rde.note_peer_epoch(1, 1)
        rde._activate_cb(1, {"tp": 999, "_ep": 1, "_fid": (1, 1 << 48)})
        with rde._term_lock:
            assert rde._app_recv == 1      # credited
        with rde._dlock:
            assert rde._delayed            # parked for the unknown pool
            rde._delayed.clear()           # stop the retry timer chain
    finally:
        ce._stop = True
        rde.fini()
        ctx.fini()


def test_pool_epoch_gate_drops_and_parks_activations():
    from parsec_tpu.comm.engine import SocketCE
    from parsec_tpu.comm.launch import _probe_port_base
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.core.context import Context
    from parsec_tpu.core.taskpool import Taskpool
    ce = SocketCE(0, 2, _probe_port_base(2))
    ctx = Context(nb_cores=1, rank=0, nranks=2)
    rde = RemoteDepEngine(ce, ctx)
    try:
        tp = Taskpool("gate")
        ctx.add_taskpool(tp, start=True)
        tp.run_epoch = 2
        base = {"tp": tp.taskpool_id, "root": 1, "ranks": [0],
                "deliveries": {}, "data": None}
        rde._try_activation(1, {**base, "pe": 1})   # torn generation
        with rde._dlock:
            assert not rde._delayed                 # dropped outright
        rde._try_activation(1, {**base, "pe": 3})   # future generation
        with rde._dlock:
            assert len(rde._delayed) == 1           # parked, not lost
        # once the local restart catches up, the parked frame delivers
        tp.run_epoch = 3
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            rde.retry_delayed()
            with rde._dlock:
                if not rde._delayed:
                    break
            time.sleep(0.02)
        with rde._dlock:
            assert not rde._delayed
        tp.cancel()
    finally:
        ce._stop = True
        rde.fini()
        ctx.fini()


# ---------------------------------------------------------------------------
# checkpoint under a degraded context (satellite bugfix)
# ---------------------------------------------------------------------------

def test_checkpoint_degraded_fails_fast(tmp_path):
    import types
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.utils.checkpoint import checkpoint, restore
    ctx = Context(nb_cores=1)
    try:
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=8, ln=8, name="A")
        A.data_of(0, 0)
        path = str(tmp_path / "ck")
        # healthy single-rank checkpoint still works
        checkpoint(ctx, [A], path)
        # a dead, UNEXCUSED peer fails fast with the structured error
        # instead of wedging in the collective barrier
        ctx.comm = types.SimpleNamespace(
            ce=types.SimpleNamespace(dead_peers={1}, excused_peers=set()))
        with pytest.raises(CheckpointDegradedError) as ei:
            checkpoint(ctx, [A], str(tmp_path / "ck2"))
        assert ei.value.ranks == [1]
        with pytest.raises(CheckpointDegradedError):
            restore(ctx, [A], path)
        # an EXCUSED death proceeds (the barrier narrowed to survivors;
        # nranks=1 here so no wire traffic) and records the marker
        ctx.comm = None
        restore(ctx, [A], path)
    finally:
        ctx.comm = None
        ctx.fini()


def test_checkpoint_records_excused_marker(tmp_path):
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.utils.checkpoint import checkpoint
    import types
    ctx = Context(nb_cores=1)
    try:
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=8, ln=8, name="A")
        A.data_of(0, 0)

        class _BarrierCE:
            dead_peers = {1}
            excused_peers = {1}

            def barrier(self, timeout=60.0):
                pass
        ctx.comm = types.SimpleNamespace(ce=_BarrierCE())
        out = checkpoint(ctx, [A], str(tmp_path / "ck"))
        with np.load(out) as zf:
            assert list(zf["__excused__"]) == [1]
    finally:
        ctx.comm = None
        ctx.fini()


# ---------------------------------------------------------------------------
# service bookkeeping: degraded -> recovering -> healthy (satellite)
# ---------------------------------------------------------------------------

def test_service_recovery_state_transitions():
    from parsec_tpu.service.service import JobService
    svc = JobService(max_active=1, nb_cores=1)
    try:
        assert svc.stats()["recovering"] is False
        svc.note_recovery("start", 1)
        st = svc.stats()
        assert st["degraded"] and st["degraded_ranks"] == [1]
        assert st["recovering"] and st["recovering_ranks"] == [1]
        svc.note_recovery("done", 1)
        st = svc.stats()
        assert not st["degraded"] and not st["recovering"]
        # a failed recovery leaves the degradation standing
        svc.note_recovery("start", 2)
        svc.note_recovery("failed", 2)
        st = svc.stats()
        assert st["degraded_ranks"] == [2] and not st["recovering"]
        # ...until the rank rejoins
        svc.note_recovery("rejoin", 2)
        assert svc.stats()["degraded"] is False
    finally:
        svc.shutdown(timeout=10)


# ---------------------------------------------------------------------------
# end to end: kill -> recover -> COMPLETED with correct numerics
# ---------------------------------------------------------------------------

def test_kill_close_recovers_potrf():
    """The acceptance shape: a 2-rank potrf whose peer hard-dies
    mid-run COMPLETES on the survivor with validated numbers (adopted
    tiles included — local_tiles routes through the translation)."""
    import chaos
    res = _run_distributed_with_env(
        chaos.potrf_recover_workload, 2,
        {"PARSEC_MCA_FAULT_PLAN":
         "seed=11;kill_rank=1@t+1.0s,mode=close;"
         "delay_frame=tag:ACT,p=1,ms=150",
         "PARSEC_MCA_RECOVERY_ENABLE": "1",
         "PARSEC_CHAOS_WAIT_S": "45"},
        timeout=90, tolerate_ranks=(1,))
    assert res[0] == "ok" and res[1] is None   # victim actually died


def test_kill_close_recovers_dtd_chain():
    """DTD lineage replay: the insert stream re-runs on the survivor
    against the snapshot-restored tile — EXACT final value."""
    import chaos
    res = _run_distributed_with_env(
        chaos.dtd_chain_recover_workload, 2,
        {"PARSEC_MCA_FAULT_PLAN":
         "seed=7;kill_rank=1@t+1.2s,mode=close;"
         "delay_frame=tag:DTD,p=1,ms=60",
         "PARSEC_MCA_RECOVERY_ENABLE": "1",
         "PARSEC_CHAOS_WAIT_S": "30"},
        timeout=90, tolerate_ranks=(1,))
    assert res[0] == "ok" and res[1] is None


def test_kill_rank_zero_recovers_on_new_root():
    """Killing rank 0 exercises the generalized ring/barrier root: the
    surviving rank 1 becomes coordinator, initiator, AND barrier root,
    adopts rank 0's partition, and completes with validated numbers."""
    import chaos
    res = _run_distributed_with_env(
        chaos.potrf_recover_workload, 2,
        {"PARSEC_MCA_FAULT_PLAN":
         "seed=13;kill_rank=0@t+1.0s,mode=close;"
         "delay_frame=tag:ACT,p=1,ms=150",
         "PARSEC_MCA_RECOVERY_ENABLE": "1",
         "PARSEC_CHAOS_WAIT_S": "45"},
        timeout=90, tolerate_ranks=(0,))
    assert res[1] == "ok" and res[0] is None


def test_recovery_disabled_reproduces_containment():
    """PARSEC_MCA_RECOVERY_ENABLE=0 (the default): the same kill plan
    fails the pool with the PR 5 structured PeerFailedError — recovery
    never engages implicitly."""
    import chaos
    with pytest.raises(RuntimeError) as ei:
        _run_distributed_with_env(
            chaos.potrf_recover_workload, 2,
            {"PARSEC_MCA_FAULT_PLAN":
             "seed=11;kill_rank=1@t+1.0s,mode=close;"
             "delay_frame=tag:ACT,p=1,ms=150",
             "PARSEC_MCA_RECOVERY_ENABLE": "0",
             "PARSEC_CHAOS_WAIT_S": "30"},
            timeout=90)
    assert "PeerFailedError" in str(ei.value)


# ---------------------------------------------------------------------------
# elastic rejoin: killed -> restarted -> serving its partition again.
# Parametrized over transports: shm exercises the ring RE-CREATION in
# the TAG_REJOIN handshake (previously the one transport that could
# not rejoin — the receiver's unlink left no ring to come back to).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport", ["evloop", "shm"])
def test_killed_rank_rejoins_and_serves(transport):
    import chaos
    ok, detail = chaos.rejoin_scenario(transport, timeout=150.0)
    assert ok, detail


# ---------------------------------------------------------------------------
# lineage recording + the incremental checkpoint plane
# ---------------------------------------------------------------------------

def test_lineage_log_records_completed_tasks():
    """With recovery armed, every completed task of a registered pool
    lands in the ring with flow-keyed, version-stamped reads/writes —
    and the write versions march the datum version clock upward (the
    chain the minimal planner walks)."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    params.set("recovery_enable", 1)
    try:
        ctx = Context(nb_cores=1)
        try:
            from parsec_tpu.apps.potrf import potrf_taskpool
            n, mb = 32, 16
            rng = np.random.default_rng(2)
            a = rng.standard_normal((n, n)).astype(np.float32)
            spd = (a @ a.T + n * np.eye(n)).astype(np.float32)
            A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n,
                                  name="Alin").from_array(spd.copy())
            tp = potrf_taskpool(A, device="cpu")
            ctx.add_taskpool(tp)
            assert tp._lineage is not None     # armed at registration
            ctx.wait(timeout=30)
            lin = tp._lineage
            assert not lin.overflow
            assert len(lin.records) == len(lin.completed) > 0
            by_key = {r.key: r for r in lin.records}
            # every task class completed and recorded tile writes
            names = {k[0] for k in by_key}
            assert {"POTRF", "TRSM", "SYRK", "POTRFL"} <= names
            # the diagonal chain: SYRK(1, 0)'s T write supersedes its
            # T read of the same tile (in-place version discipline)
            rec = by_key[("SYRK", 1, 0)]
            rt, rv = rec.rmap["T"]
            wt, wv = rec.wmap["T"]
            assert rt == wt == ("Alin", 1, 1)
            assert wv > rv
        finally:
            ctx.fini()
    finally:
        params.set("recovery_enable", 0)


def test_tile_checkpoint_store_interval_and_keep():
    from parsec_tpu.utils.checkpoint import TileCheckpointStore
    st = TileCheckpointStore(3600.0, keep=2)    # huge interval
    st.note_write(("a", 0, 0), 1, np.ones(4))
    st.note_write(("a", 0, 0), 2, np.full(4, 2.0))   # inside interval
    assert st.versions(("a", 0, 0)) == (1,)          # rate-bounded
    st2 = TileCheckpointStore(0.0, keep=2)      # capture every write
    for v in (1, 2, 3):
        st2.note_write(("a", 0, 0), v, np.full(4, float(v)))
    assert st2.versions(("a", 0, 0)) == (2, 3)  # keep bound evicts v1
    np.testing.assert_allclose(st2.get(("a", 0, 0), 3), 3.0)
    assert st2.get(("a", 0, 0), 1) is None


def test_lineage_hook_feeds_checkpoint_store():
    """recovery_checkpoint_interval_s > 0 arms the capture plane: the
    complete_execution lineage hook snapshots version-stamped dirty
    tiles into the store (the replay cut of long version chains)."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    params.set("recovery_enable", 1)
    params.set("recovery_checkpoint_interval_s", 0.0001)
    try:
        ctx = Context(nb_cores=1)
        try:
            from parsec_tpu.apps.potrf import potrf_taskpool
            n, mb = 32, 16
            rng = np.random.default_rng(2)
            a = rng.standard_normal((n, n)).astype(np.float32)
            spd = (a @ a.T + n * np.eye(n)).astype(np.float32)
            A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n,
                                  name="Ack").from_array(spd.copy())
            ctx.add_taskpool(potrf_taskpool(A, device="cpu"))
            ctx.wait(timeout=30)
            st = ctx.recovery.ckpt
            assert st is not None and st.captures > 0
            # a captured version is retrievable at its exact stamp;
            # keys scope by COLLECTION IDENTITY so a later job's
            # same-named tiles can never read this job's bytes
            key = (id(A), ("Ack", 0, 0))
            vs = st.versions(key)
            assert vs
            assert st.get(key, vs[-1]) is not None
            # spec retirement evicts the captures with it
            st.drop_owner(id(A))
            assert st.versions(key) == ()
        finally:
            ctx.fini()
    finally:
        params.set("recovery_checkpoint_interval_s", 0.0)
        params.set("recovery_enable", 0)


def test_checkpoint_shards_carry_version_stamps(tmp_path):
    """Format-2 collective shards stamp each tile's version — the
    replay-cut metadata shard_versions reads back."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.utils.checkpoint import (checkpoint, restore,
                                             shard_versions)
    ctx = Context(nb_cores=1)
    try:
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=8, ln=8, name="Avz")
        d = A.data_of(0, 0)
        d.overwrite_host(np.ones((4, 4), np.float32))
        A.data_of(1, 1)
        path = str(tmp_path / "ck")
        checkpoint(ctx, [A], path)
        vs = shard_versions(path, 0)
        assert vs["Avz:0:0"] == d.newest_version()
        assert "Avz:1:1" in vs
        # and the stamped shard still restores
        d.overwrite_host(np.zeros((4, 4), np.float32))
        restore(ctx, [A], path)
        np.testing.assert_allclose(
            np.asarray(A.data_of(0, 0).pull_to_host().payload), 1.0)
    finally:
        ctx.fini()


# ---------------------------------------------------------------------------
# end to end: minimal replay, dyn-hold recovery, multi-death agreement
# ---------------------------------------------------------------------------

def test_minimal_replay_reexecutes_strictly_fewer():
    """The headline A/B: on the SAME mid-run kill, recorded-lineage
    minimal replay re-executes strictly fewer tasks than
    replay-from-restore-point, and each leg provably took its path
    (minimal_replays / full_replays counters)."""
    import chaos
    ab = chaos.run_ab_pair(timeout=120.0)
    assert ab["minimal"]["minimal"] >= 1 and ab["minimal"]["full"] == 0
    assert ab["full"]["full"] >= 1
    assert ab["minimal"]["reexec"] < ab["full"]["reexec"], ab


def test_kill_dtd_chain_skip_minimal_sole_survivor():
    """2-rank DTD chain kill: the sole survivor SHORT-CIRCUITS the
    skip agreement to its local view (no wire round), ghost-replays
    the completed prefix, and ends with the exact final value — the
    counters prove the minimal path (full_replays stays 0); the wired
    multi-survivor round is the chaos kill-dtd-minimal 3-rank case."""
    import chaos
    res = _run_distributed_with_env(
        chaos.dtd_ab_chain_workload, 2,
        {"PARSEC_MCA_FAULT_PLAN":
         "seed=5;kill_rank=1@t+2.0s,mode=close;"
         "delay_dispatch=key~_dtd_chain_step,ms=100",
         "PARSEC_MCA_RECOVERY_ENABLE": "1",
         "PARSEC_CHAOS_WAIT_S": "45"},
        timeout=120, tolerate_ranks=(1,))
    surv = res[0]
    assert surv is not None and surv[0] == "ok" and res[1] is None
    assert surv[2] >= 1 and surv[3] == 0    # minimal, never full
    assert surv[4] >= 1                     # skip agreement concluded


def test_kill_recovers_dynamic_taskpool_with_hold():
    """A DynamicTaskpool killed while its distributed termination hold
    is outstanding restarts on the survivor with the hold RE-ARMED
    (previously stranded) and finishes with exact values."""
    import chaos
    res = _run_distributed_with_env(
        chaos.dyn_chain_recover_workload, 2,
        {"PARSEC_MCA_FAULT_PLAN":
         "seed=1;kill_rank=1@t+0.8s,mode=close;"
         "delay_frame=tag:ACT,p=1,ms=150;delay_frame=tag:BATCH,p=1,ms=150",
         "PARSEC_MCA_RECOVERY_ENABLE": "1",
         "PARSEC_CHAOS_WAIT_S": "40"},
        timeout=90, tolerate_ranks=(1,))
    assert res[0] == "ok" and res[1] is None


def test_multi_death_agreement_converges_survivors():
    """Two near-simultaneous deaths on a 4-rank gang: the TAG_RECOVER
    agreement round lands both survivors on the SAME confirmed dead
    set and the run completes with validated numerics."""
    import chaos
    res = _run_distributed_with_env(
        chaos.potrf_recover_workload, 4,
        {"PARSEC_MCA_FAULT_PLAN":
         "seed=2;kill_rank=2@t+1.0s,mode=close;"
         "kill_rank=3@t+1.05s,mode=close;"
         "delay_frame=tag:ACT,p=1,ms=120;delay_frame=tag:BATCH,p=1,ms=120",
         "PARSEC_MCA_RECOVERY_ENABLE": "1",
         "PARSEC_MCA_RECOVERY_MAX_ATTEMPTS": "3",
         "PARSEC_CHAOS_WAIT_S": "60"},
        timeout=120, tolerate_ranks=(2, 3))
    assert res[0] == "ok" and res[1] == "ok"
    assert res[2] is None and res[3] is None   # both kills fired


# ---------------------------------------------------------------------------
# observability: metrics families + flight-recorder hook
# ---------------------------------------------------------------------------

def test_recovery_metrics_families_scrape():
    from parsec_tpu.core.context import Context
    params.set("recovery_enable", 1)
    try:
        ctx = Context(nb_cores=1)
        try:
            assert ctx.recovery is not None
            names = {s["n"] for s in ctx.metrics.samples()}
            assert "parsec_recoveries_total" in names
            assert "parsec_tasks_reexecuted_total" in names
            assert "parsec_rank_rejoins_total" in names
            assert "parsec_recovery_duration_seconds" in names
            assert "parsec_recovery_minimal_replays_total" in names
            assert "parsec_recovery_full_replays_total" in names
            stages = {s["l"].get("stage")
                      for s in ctx.metrics.samples()
                      if s["n"] == "parsec_recoveries_total"}
            assert {"started", "completed", "failed"} <= stages
        finally:
            ctx.fini()
    finally:
        params.set("recovery_enable", 0)


# ---------------------------------------------------------------------------
# acceptance (slow): 3-rank mid-run kill, multi-survivor re-execution
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_three_rank_potrf_survives_midrun_kill():
    """Two survivors recover a third's mid-run death TOGETHER: the dead
    partition re-maps onto one adopter, both re-enumerate, cross-rank
    activations of the new generation flow, numerics validate, and the
    killed run stays within ~2x the no-fault makespan (the ISSUE
    bound; the loose assert guards the invariant under host noise)."""
    import chaos
    env = {"PARSEC_MCA_RECOVERY_ENABLE": "1",
           "PARSEC_CHAOS_WAIT_S": "60"}
    t0 = time.monotonic()
    res = _run_distributed_with_env(
        chaos.potrf_recover_workload, 3,
        {**env, "PARSEC_MCA_FAULT_PLAN":
         "seed=4;delay_frame=tag:ACT,p=1,ms=120"},
        timeout=120)
    base_s = time.monotonic() - t0
    assert res == ["ok", "ok", "ok"]
    t0 = time.monotonic()
    res = _run_distributed_with_env(
        chaos.potrf_recover_workload, 3,
        {**env, "PARSEC_MCA_FAULT_PLAN":
         "seed=4;kill_rank=2@t+1.0s,mode=close;"
         "delay_frame=tag:ACT,p=1,ms=120"},
        timeout=180, tolerate_ranks=(2,))
    kill_s = time.monotonic() - t0
    assert res[0] == "ok" and res[1] == "ok"
    ratio = kill_s / max(base_s, 1e-9)
    print(f"3-rank mid-run kill: baseline {base_s:.1f}s, "
          f"killed {kill_s:.1f}s, ratio {ratio:.2f}x")
    assert ratio < 3.0, (base_s, kill_s)


@pytest.mark.slow
def test_chaos_recover_catalog():
    """The full recovery catalog (close/hang x evloop/shm/threads +
    DTD + minimal replay + the DTD skip agreement + dyn holds +
    multi-death agreement + survivor exhaustion, plus the shm
    kill->restart->rejoin leg) through the chaos harness."""
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos.py"),
         "--recover", "--seeds", "12", "--timeout", "120"],
        capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
