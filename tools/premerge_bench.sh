#!/bin/sh
# Pre-merge bench smoke: run the CPU-only host-side probes and hold each
# to its ABSOLUTE gate.  (Until PR 21 every probe line was also diffed
# with tools/bench_guard.py against the newest BENCH_r*.json in the
# repo; those artifacts held one GEMM metric that no probe shares, so
# the diff compared nothing, and they were deleted with the rest of the
# remote-chip records.  bench_guard.py itself stays: --prev FILE diffs
# any two bench lines.)
#
# These probes time the Python+TCP runtime layers (no accelerator), so
# they run anywhere in ~3 minutes and catch scheduler/transport
# regressions — including the r6 protocol-mix guards (frames_sent,
# syscalls_per_mb, and act_eager coverage under the bw/rtt "protocol"
# key; wakeups/partial_writes are recorded but not gated — they track
# OS scheduling timing, not the code under test) — before a change
# merges.  Documented in BENCH.md ("Pre-merge guard").
#
# r7 added the TRACER-OVERHEAD gate: the tasks probe runs a second
# time with the full tracing stack installed (PARSEC_BENCH_TRACE=1:
# binary task profiler + causal tracer's queue-wait spans and dep
# edges).  Since r14 the gate bounds the ABSOLUTE per-task tracing
# cost ($trace_bound_us, default 8 us/task; measured ~2.3 on the
# 1-core container, down from r7's ~5.4) instead of a ratio — see the
# usage note.
#
# r8 adds the CHAOS smoke: a seeded subset of tools/chaos.py fault
# plans (delayed v0 DTD payload, hard rank kill, transient task faults
# with retry) asserting the no-hang invariant — every run completes
# correctly or fails with a structured error within its deadline.  The
# full catalog is `python tools/chaos.py --seeds 12`.
#
# r10 added the TELEMETRY-OVERHEAD gate: the always-on metrics
# registry plus an ARMED flight recorder and the live attribution
# engine with straggler detection (prof/liveattr.py).  Since r14 the
# gate bounds the ABSOLUTE armed-plane cost ($telemetry_bound_us,
# default 0.5 us/task — the same magnitude the old 5%-of-7us contract
# allowed, but stable under base speedups).  The measurement is
# bench.py's telemetry mode (four back-to-back off/on pairs in one
# process, gating on the MINIMUM pair reading — host-load noise
# contaminates single pairs in either direction but a real regression
# shows in all of them; the JSON records both the ratio and
# overhead_us).
#
# r16 adds the JOURNAL-OVERHEAD gate (control-plane black box,
# prof/journal.py): the tasks probe armed vs off through bench.py's
# journal mode, bounded ABSOLUTE at $journal_bound (default 0.3
# us/task).  The journal has no per-task emit sites by construction —
# this leg proves the C run_quantum fast path never crosses it.  The
# chaos smoke below additionally runs --audit-journal (per-case
# journal bundles through tools/journal_audit.py's invariant auditor).
#
# Usage:  sh tools/premerge_bench.sh [threshold] [trace_bound_us] \
#             [telemetry_bound_us] [native_margin] [journal_bound_us]
#         threshold:   unused since PR 21 (was bench_guard's relative
#             regression bound); kept so the positions of the other
#             arguments do not move
#         trace_bound_us: max ABSOLUTE tracing cost in us/task
#             (default 8.0).  r14 changed this gate from a ratio to an
#             absolute bound: at the 482k+/s headline (~2 us/task) the
#             old 50% ratio tripped on a tracing cost that had in fact
#             DROPPED from r7's ~5.4 to ~2.3 us/task — a faster base
#             must not turn a constant overhead into a regression.
#         telemetry_bound_us: max ABSOLUTE armed-plane cost in us/task
#             (default 0.5; same rationale — the old <=5% ratio bound
#             was 5% of a 7 us base = 0.35 us, so the absolute bound
#             preserves the old contract's magnitude while surviving
#             base speedups; bench telemetry mode reports both)
#         native_margin: min native/fallback tasks ratio (default 1.05)
#         ntasks_margin (arg 6): min native/fallback ratio on the
#             NON-trivial (data-carrying chain) probe (default 1.3) —
#             the r17 extended-chain gate; the same leg fails on ANY
#             native-path bailout (coverage, not just speed)
# r11 adds the NATIVE-vs-PYTHON pairing: the tasks probe (which runs
# with the native scheduler hot path by default) is re-run with
# PARSEC_MCA_SCHED_NATIVE=0 — the native line must (a) actually have
# the native path active in its JSON (sched_native=1 — a
# silently-degraded build is a no-op native path) and (b) beat the
# fallback by >= $native_margin (default 5%).  The shm transport gets
# its own rtt probe (it must run to a result).
#
# r17 adds the FABRIC smoke (multi-tenant serving fabric,
# service/fabric.py): the bench fabric probe (many small jobs/s with
# p50/p99 admission->completion latency, self-auditing its journal)
# must run clean, and a carved-subset smoke
# runs 3 concurrent tenants on disjoint exclusive device subsets of an
# 8-device CPU mesh plus one temporal-sharing job, then replays the
# journal through tools/journal_audit.py's F1/F2/F3 fabric invariants
# (disjoint subsets always, one placement outcome per admission,
# preemptions resolve).
#
# r9 prepends the PARSECLINT gate: the project static analyzer
# (tools/parseclint — lock discipline, event-loop blocking calls,
# device_put aliasing, MCA knob drift, containment exception hygiene,
# -O assert hazards) must be clean against its baseline BEFORE any
# bench cycle is spent; a violation fails the premerge outright.
set -e
repo="$(cd "$(dirname "$0")/.." && pwd)"
trace_bound="${2:-8.0}"
telemetry_bound="${3:-0.5}"
rc=0
tasks_off=""
echo "== premerge gate: parseclint (static analysis) =="
if ! (cd "$repo" && python -m tools.parseclint parsec_tpu); then
    echo "premerge: parseclint found violations (fix, waive with a"
    echo "          'lint:' comment, or baseline in tools/parseclint/)"
    exit 1
fi
echo "== premerge gate: native build-from-source =="
# r14: every native source (core.cpp + the pinsext/schedext/commext
# CPython extensions) must compile from a clean tree into a scratch
# directory — the .so artifacts are built on demand (gitignored), so
# a source that no longer compiles is a SILENT fleet-wide degradation:
# every fresh container would fall back to the Python twins with one
# rate-limited warning nobody reads.  (No mtime drift check: the
# runtime's _stale() rebuild-on-load already guarantees the probes
# below never measure an old build of an edited source.)
scratch="$(mktemp -d)"
if ! make -s -C "$repo/parsec_tpu/native" OUT="$scratch" all; then
    echo "premerge: native build-from-source FAILED (compile error)"
    rm -rf "$scratch"
    exit 1
fi
rm -rf "$scratch"
for mode in tasks rtt bw; do
    echo "== premerge probe: $mode =="
    out="${TMPDIR:-/tmp}/premerge_${mode}_$$.json"
    if ! JAX_PLATFORMS=cpu PARSEC_BENCH_APP=$mode \
         python "$repo/bench.py" > "$out" 2>/dev/null; then
        echo "premerge: $mode probe FAILED to run"
        rc=1
        continue
    fi
    if [ "$mode" = tasks ]; then
        tasks_off="$out"     # kept for the tracer-overhead comparison
    else
        rm -f "$out"
    fi
done
echo "== premerge probe: tracer overhead (tasks, tracing on) =="
on="${TMPDIR:-/tmp}/premerge_tasks_on_$$.json"
if [ -n "$tasks_off" ] && JAX_PLATFORMS=cpu PARSEC_BENCH_APP=tasks \
     PARSEC_BENCH_TRACE=1 python "$repo/bench.py" > "$on" 2>/dev/null; then
    if ! python - "$tasks_off" "$on" "$trace_bound" <<'EOF'
import json, sys
def last_json(path):
    for line in reversed(open(path).read().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit(f"premerge: no JSON in {path}")
off = last_json(sys.argv[1])["value"]
on = last_json(sys.argv[2])["value"]
bound = float(sys.argv[3])
cost_us = (1e6 / on - 1e6 / off) if on and off else float("inf")
print(f"premerge: tracer cost {cost_us:+.2f} us/task "
      f"(bound {bound} us; off {off:.0f} -> on {on:.0f} tasks/s)")
sys.exit(1 if cost_us > bound else 0)
EOF
    then
        rc=1
    fi
else
    echo "premerge: traced tasks probe FAILED to run"
    rc=1
fi
echo "== premerge probe: native-vs-python A/B (tasks) =="
native_margin="${4:-1.05}"
fb="${TMPDIR:-/tmp}/premerge_tasks_fb_$$.json"
if [ -n "$tasks_off" ] && JAX_PLATFORMS=cpu PARSEC_BENCH_APP=tasks \
     PARSEC_MCA_SCHED_NATIVE=0 python "$repo/bench.py" > "$fb" 2>/dev/null; then
    if ! python - "$tasks_off" "$fb" "$native_margin" <<'EOF'
import json, sys
def last_json(path):
    for line in reversed(open(path).read().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit(f"premerge: no JSON in {path}")
nat, fb = last_json(sys.argv[1]), last_json(sys.argv[2])
margin = float(sys.argv[3])
active = (nat.get("native") or {}).get("sched_native")
ratio = nat["value"] / fb["value"] if fb["value"] else float("inf")
print(f"premerge: sched native A/B {fb['value']:.0f} -> "
      f"{nat['value']:.0f} tasks/s (x{ratio:.2f}, need >= x{margin}; "
      f"native active: {active})")
if active != 1:
    print("premerge: NATIVE PATH INACTIVE in the default tasks probe "
          "(build degraded?) — a no-op native path fails pre-merge")
    sys.exit(1)
sys.exit(0 if ratio >= margin else 1)
EOF
    then
        rc=1
    fi
else
    echo "premerge: fallback tasks probe FAILED to run"
    rc=1
fi
rm -f "$fb"
rm -f "$tasks_off" "$on"
echo "== premerge probe: native-vs-python A/B (ntasks, data-carrying chains) =="
# r17: the EXTENDED C progress chain (per-class binding tables +
# C-side local delivery walk) gets its own paired A/B on the
# non-trivial probe — native must beat the fallback by
# >= $ntasks_margin (default 1.3) AND report ZERO bailouts (any
# non-empty reason means data tasks silently popped back to Python
# and the number no longer measures the chain).
ntasks_margin="${6:-1.3}"
nt_nat="${TMPDIR:-/tmp}/premerge_ntasks_$$.json"
nt_fb="${TMPDIR:-/tmp}/premerge_ntasks_fb_$$.json"
if JAX_PLATFORMS=cpu PARSEC_BENCH_APP=ntasks \
     python "$repo/bench.py" > "$nt_nat" 2>/dev/null \
   && JAX_PLATFORMS=cpu PARSEC_BENCH_APP=ntasks \
     PARSEC_MCA_SCHED_NATIVE=0 python "$repo/bench.py" > "$nt_fb" \
     2>/dev/null; then
    if ! python - "$nt_nat" "$nt_fb" "$ntasks_margin" <<'EOF'
import json, sys
def last_json(path):
    for line in reversed(open(path).read().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit(f"premerge: no JSON in {path}")
nat, fb = last_json(sys.argv[1]), last_json(sys.argv[2])
margin = float(sys.argv[3])
active = (nat.get("native") or {}).get("sched_native")
bail = nat.get("bailouts") or {}
ratio = nat["value"] / fb["value"] if fb["value"] else float("inf")
print(f"premerge: non-trivial chain A/B {fb['value']:.0f} -> "
      f"{nat['value']:.0f} tasks/s (x{ratio:.2f}, need >= x{margin}; "
      f"native active: {active}; bailouts: {bail or 'none'})")
if active != 1:
    print("premerge: NATIVE PATH INACTIVE in the ntasks probe "
          "(build degraded?) — a no-op extended chain fails pre-merge")
    sys.exit(1)
if bail:
    print("premerge: UNEXPECTED BAILOUTS on the native ntasks probe — "
          "data tasks fell back to Python; the extended chain lost "
          "coverage")
    sys.exit(1)
sys.exit(0 if ratio >= margin else 1)
EOF
    then
        rc=1
    fi
else
    echo "premerge: ntasks probe FAILED to run"
    rc=1
fi
rm -f "$nt_nat" "$nt_fb"
echo "== premerge probe: aggregate multi-rank throughput (shm) =="
# r17: N same-host ranks over shm, each with a live RemoteDepEngine —
# comm-attached fast-complete must keep every (purely local) task on
# the C chain: zero comm_buffered bailouts.  Self-scales N to the core count
# (N=2 smoke on a 1-core host, with the skip reason in the JSON).
agg="${TMPDIR:-/tmp}/premerge_aggregate_$$.json"
if JAX_PLATFORMS=cpu PARSEC_BENCH_APP=aggregate \
     python "$repo/bench.py" > "$agg" 2>/dev/null; then
    if ! python - "$agg" <<'EOF'
import json, sys
def last_json(path):
    for line in reversed(open(path).read().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit(f"premerge: no JSON in {path}")
obj = last_json(sys.argv[1])
bail = obj.get("bailouts") or {}
skip = obj.get("skipped") or {}
print(f"premerge: aggregate {obj['value']:.0f} tasks/s over "
      f"{obj.get('ranks')} ranks (eff {obj.get('scaling_efficiency')}; "
      f"bailouts: {bail or 'none'}"
      + (f"; skipped: {skip}" if skip else "") + ")")
if bail.get("comm_buffered"):
    print("premerge: comm_buffered bailouts in the aggregate probe — "
          "comm-attached fast-complete regressed (local tasks left "
          "the C chain because a comm engine was attached)")
    sys.exit(1)
sys.exit(0)
EOF
    then
        rc=1
    fi
else
    echo "premerge: aggregate probe FAILED to run"
    rc=1
fi
rm -f "$agg"
echo "== premerge probe: shm transport rtt =="
shmout="${TMPDIR:-/tmp}/premerge_shm_rtt_$$.json"
if ! JAX_PLATFORMS=cpu PARSEC_BENCH_APP=rtt PARSEC_MCA_COMM_TRANSPORT=shm \
     python "$repo/bench.py" > "$shmout" 2>/dev/null; then
    echo "premerge: shm rtt probe FAILED to run"
    rc=1
fi
rm -f "$shmout"
echo "== premerge probe: telemetry overhead (metrics + flight recorder + liveattr armed) =="
tel="${TMPDIR:-/tmp}/premerge_telemetry_$$.json"
if JAX_PLATFORMS=cpu PARSEC_BENCH_APP=telemetry \
     python "$repo/bench.py" > "$tel" 2>/dev/null; then
    if ! python - "$tel" "$telemetry_bound" <<'EOF'
import json, sys
def last_json(path):
    for line in reversed(open(path).read().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit(f"premerge: no JSON in {path}")
obj = last_json(sys.argv[1])
cost_us = obj.get("overhead_us")
bound = float(sys.argv[2])
if cost_us is None:   # pre-r14 bench build: fall back to the ratio
    cost_us = obj["value"] * 7.0   # vs the old 7 us/task base
print(f"premerge: telemetry cost {cost_us:.3f} us/task "
      f"(bound {bound} us; ratio {obj['value']:+.1%}; off "
      f"{obj.get('tasks_off')} -> armed {obj.get('tasks_on')} tasks/s)")
sys.exit(1 if cost_us > bound else 0)
EOF
    then
        rc=1
    fi
else
    echo "premerge: telemetry probe FAILED to run"
    rc=1
fi
rm -f "$tel"
echo "== premerge probe: journal overhead (control-plane black box armed) =="
# r16: the control-plane journal is always-on; its emit sites are
# control-plane only (recovery rounds, retirement handshakes,
# barriers, job lifecycle — NO per-task emits), so the tasks probe
# armed-vs-off must read ~0 us/task.  The absolute bound proves the C
# run_quantum fast path never crosses the journal.
journal_bound="${5:-0.3}"
jnl="${TMPDIR:-/tmp}/premerge_journal_$$.json"
if JAX_PLATFORMS=cpu PARSEC_BENCH_APP=journal \
     python "$repo/bench.py" > "$jnl" 2>/dev/null; then
    if ! python - "$jnl" "$journal_bound" <<'EOF'
import json, sys
def last_json(path):
    for line in reversed(open(path).read().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit(f"premerge: no JSON in {path}")
obj = last_json(sys.argv[1])
cost_us = obj.get("overhead_us")
bound = float(sys.argv[2])
if cost_us is None:
    print("premerge: journal probe JSON carries no overhead_us "
          "(every pair skipped?)")
    sys.exit(1)
print(f"premerge: journal cost {cost_us:.3f} us/task "
      f"(bound {bound} us; ratio {obj['value']:+.1%}; off "
      f"{obj.get('tasks_off')} -> armed {obj.get('tasks_on')} tasks/s)")
sys.exit(1 if cost_us > bound else 0)
EOF
    then
        rc=1
    fi
else
    echo "premerge: journal probe FAILED to run"
    rc=1
fi
rm -f "$jnl"
echo "== premerge probe: fabric serving (jobs/s + latency, self-audited) =="
fab="${TMPDIR:-/tmp}/premerge_fabric_$$.json"
if ! JAX_PLATFORMS=cpu PARSEC_BENCH_APP=fabric \
     python "$repo/bench.py" > "$fab" 2>/dev/null; then
    echo "premerge: fabric probe FAILED to run"
    rc=1
fi
rm -f "$fab"
echo "== premerge probe: fabric carved-subset smoke (3 tenants, audited) =="
# three concurrent tenants on disjoint exclusive 2-device subsets of an
# 8-device CPU mesh plus one temporal-sharing job; every placement is
# journaled and the bundle must pass journal_audit's F1/F2/F3 fabric
# invariants.  Concurrency is asserted from the journal itself: the
# third exclusive placement lands before any of the three releases.
if ! JAX_PLATFORMS=cpu \
     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     REPO="$repo" python - <<'EOF'
import os, sys, time
repo = os.environ["REPO"]
sys.path.insert(0, repo)
sys.path.insert(0, os.path.join(repo, "tools"))
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range, TASK
from parsec_tpu.service.fabric import ServingFabric
import journal_audit

NT = 12

def chain_factory(i):
    def factory():
        A = TwoDimBlockCyclic(mb=4, nb=4, lm=4, ln=4)
        A.data_of(0, 0).copy_on(0).payload[:] = 0.0
        p = PTG(f"smoke{i}", NT=NT)
        p.task("S", k=Range(0, NT - 1)) \
            .affinity(lambda k, A=A: A(0, 0)) \
            .flow("T", "RW",
                  IN(DATA(lambda A=A: A(0, 0)), when=lambda k: k == 0),
                  IN(TASK("S", "T", lambda k: dict(k=k - 1)),
                     when=lambda k: k > 0),
                  OUT(TASK("S", "T", lambda k: dict(k=k + 1)),
                      when=lambda k: k < NT - 1),
                  OUT(DATA(lambda A=A: A(0, 0)),
                      when=lambda k: k == NT - 1)) \
            .body(lambda T: (time.sleep(0.02), T + 1.0)[1])
        return p.build()
    return factory

with ServingFabric(nb_cores=4, max_active=8) as svc:
    mesh = len(svc.context.accelerator_spaces())
    if mesh < 7:
        raise SystemExit(
            f"premerge: fabric smoke wants an 8-device mesh, got {mesh}")
    excl = [svc.submit(chain_factory(i), devices=2, name=f"excl{i}")
            for i in range(3)]
    shared = svc.submit(chain_factory(9), name="shared")
    for j in excl + [shared]:
        if not j.wait(timeout=120.0):
            raise SystemExit(f"premerge: fabric smoke job {j} hung")
    bundle = {0: [svc.context.journal.snapshot()]}

evs = bundle[0][0]["events"]
excl_ids = {j.job_id for j in excl}
placed = {}          # job -> index of its first exclusive placement
released = []        # indices of releases of the three tenants
for idx, ev in enumerate(evs):
    if (ev.get("e") == "fabric_place" and not ev.get("shared")
            and ev.get("job") in excl_ids):
        placed.setdefault(ev["job"], idx)
        if len(ev.get("devices") or ()) != 2:
            raise SystemExit(f"premerge: tenant {ev['job']} placed on "
                             f"{ev.get('devices')} (wanted 2 devices)")
    elif ev.get("e") == "fabric_release" and ev.get("job") in excl_ids:
        released.append(idx)
if len(placed) != 3:
    raise SystemExit(f"premerge: {len(placed)}/3 tenants placed "
                     "exclusively")
if not any(ev.get("e") == "fabric_place" and ev.get("shared")
           and ev.get("job") == shared.job_id for ev in evs):
    raise SystemExit("premerge: temporal-sharing job never placed")
if released and max(placed.values()) > min(released):
    raise SystemExit("premerge: tenants never held their subsets "
                     "concurrently (3rd placement after 1st release)")
violations = journal_audit.audit(bundle)
if violations:
    raise SystemExit("premerge: fabric journal audit FAILED: "
                     + "; ".join(violations[:3]))
print(f"premerge: fabric smoke 3 exclusive tenants + 1 shared on "
      f"{mesh}-device mesh, concurrent placements, audit clean")
EOF
then
    echo "premerge: fabric carved-subset smoke FAILED"
    rc=1
fi
echo "== premerge probe: chaos (seeded fault plans, no-hang invariant) =="
# 8 seeds = one pass over the quick catalog, which now includes the
# shm-transport kill, the recv-reorder legs, AND the r12 recovery
# cases (kill-close-recover / kill-dtd-recover: kill_rank plans that
# must end in COMPLETED jobs with validated numbers on the survivor).
# r16 arms --audit-journal: every smoke case runs with the
# control-plane journal on and tools/journal_audit.py's invariant
# auditor over the per-case bundle afterwards — a protocol-invariant
# violation fails premerge even when the workload outcome matched.
if ! JAX_PLATFORMS=cpu python "$repo/tools/chaos.py" --seeds 8 --quick \
     --audit-journal; then
    rc=1
fi
echo "== premerge probe: recovery minimal-vs-full replay A/B =="
# r13: the recorded-lineage minimal replay must re-execute STRICTLY
# FEWER tasks than replay-from-restore-point on the acceptance kill,
# with each leg provably taking its intended path (a silent fallback
# to full replay fails the gate).  r15 adds a SECOND A/B line to the
# same gate: the 3-rank DTD chain down the cross-rank skip-agreement
# path (insert-stream prefix agreed over the wire between two
# survivors) vs the forced full insert-stream replay.
if ! JAX_PLATFORMS=cpu python "$repo/tools/chaos.py" --ab-minimal; then
    rc=1
fi
echo "== premerge probe: chaos soak (random recover schedules) =="
# r15: N=4 randomly seeded schedules drawn from the recover catalog,
# each with the full per-run invariant checks (validated numerics,
# no hang, recovery observed); the master seed is printed so any
# failure replays exactly (PARSEC_CHAOS_SOAK_SEED=<seed> --soak 4)
if ! JAX_PLATFORMS=cpu python "$repo/tools/chaos.py" --soak 4; then
    rc=1
fi
echo "== premerge probe: chaos degrade (drain-before-death, audited) =="
# r19: a seeded ramped degradation of rank 1 (frame delay incl.
# heartbeats + task-body jitter, tools/chaos.py --degrade) on a
# 2-rank gang.  The health plane (prof/health.py) must score the
# rank down from its heartbeat gap/jitter inflation, the serving
# fabric must journal an evidence-carrying pre-emptive drain and
# stop placing on the rank STRICTLY BEFORE the heartbeat detector
# declares it dead (comm_peer_timeout_s is never approached), and
# the journal must pass the auditor clean — including the r19 H1
# health invariant (drains evidence-backed, drained ranks never
# placement-targeted).
if ! JAX_PLATFORMS=cpu python "$repo/tools/chaos.py" --degrade; then
    rc=1
fi
exit $rc
