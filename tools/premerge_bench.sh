#!/bin/sh
# Pre-merge gate: the static analyzer, a from-source native build, the
# CPU canaries of bench.py, and the chaos smokes, each held to an
# ABSOLUTE bound or to a paired A/B taken in the same session.  Nothing
# here needs or measures a chip (that is BENCHMARK.json + benchmark/,
# recorded in PERF.md); the probes time Python, the native scheduler and
# the transports of whatever container runs them (~3 minutes), so no
# reading is compared with a stored one: run-to-run host noise is ~10%,
# a faster task core turns any constant cost into a larger ratio, and a
# regression in behaviour shows as a gate below tripping, not as a rate.
# Counts and canaries, not statements about speed.  Run one at a time:
# concurrent runs corrupt each other's clocks on a small host.
#
# What each gate bounds:
#   parseclint     tools/parseclint clean against its baseline (lock
#                  discipline, event-loop blocking calls, device_put
#                  aliasing, MCA knob drift, exception hygiene)
#   native build   every native source compiles from a clean tree (the
#                  .so files build on demand: a source that stopped
#                  compiling would silently degrade every fresh host to
#                  the Python twins)
#   tasks rtt bw   each probe runs to a result (bw/rtt lines carry the
#                  protocol mix: frames, syscalls per MB, eager/rdv)
#   tracer         tasks with the full tracing stack on
#                  (PARSEC_BENCH_TRACE=1) costs <= $trace_bound us/task
#                  more than tasks without
#   native A/B     tasks and ntasks with the native scheduler against
#                  PARSEC_MCA_SCHED_NATIVE=0: the native path is active
#                  (sched_native=1), beats the fallback by
#                  >= $native_margin / $ntasks_margin, and (ntasks) no
#                  task bailed out of the C chain
#   aggregate      N ranks over shm: no comm_buffered bailout (local
#                  tasks stay on the C chain with a comm engine attached)
#   shm rtt        the shm transport runs to a result
#   telemetry      metrics + flight recorder + liveattr armed cost
#                  <= $telemetry_bound us/task (min of four off/on pairs)
#   journal        the control-plane journal armed costs
#                  <= $journal_bound us/task: no per-task emit site
#   fabric         the serving probe runs and its journal audits clean;
#                  3 exclusive tenants + 1 shared on an 8-device CPU mesh
#                  hold disjoint subsets concurrently (F1/F2/F3)
#   chaos          seeded fault plans end correct or in a structured
#                  error (no hang), journals audit clean; minimal replay
#                  re-executes fewer tasks than full replay; 4 random
#                  recover schedules; drain-before-death
#
# Usage:  sh tools/premerge_bench.sh [trace_bound_us] [telemetry_bound_us] \
#             [native_margin] [journal_bound_us] [ntasks_margin]
#         trace_bound_us      default 8.0 (the 1-core container reads ~2.3)
#         telemetry_bound_us  default 0.5
#         native_margin       min native/fallback tasks ratio, default 1.05
#         journal_bound_us    default 0.3
#         ntasks_margin       min native/fallback ratio on the
#                             data-carrying chains, default 1.3
set -e
repo="$(cd "$(dirname "$0")/.." && pwd)"
trace_bound="${1:-8.0}"
telemetry_bound="${2:-0.5}"
rc=0
tasks_off=""
echo "== premerge gate: parseclint (static analysis) =="
if ! (cd "$repo" && python -m tools.parseclint parsec_tpu); then
    echo "premerge: parseclint found violations (fix, waive with a"
    echo "          'lint:' comment, or baseline in tools/parseclint/)"
    exit 1
fi
echo "== premerge gate: native build-from-source =="
# core.cpp and the pinsext/schedext/commext extensions, into a scratch
# directory.  (No mtime drift check: the runtime's _stale()
# rebuild-on-load already guarantees the probes below never measure an
# old build of an edited source.)
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
native_margin="${3:-1.05}"
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
# the extended C progress chain (per-class binding tables + C-side
# local delivery walk): any bailout reason means data tasks popped back
# to Python and the number no longer measures the chain
ntasks_margin="${5:-1.3}"
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
# each rank has a live RemoteDepEngine; N self-scales to the core count
# (N=2 smoke on a 1-core host, with the skip reason in the JSON)
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
if cost_us is None:
    print("premerge: telemetry probe JSON carries no overhead_us "
          "(every pair skipped?)")
    sys.exit(1)
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
# the journal's emit sites are control-plane only (recovery rounds,
# retirement handshakes, barriers, job lifecycle), so armed-vs-off must
# read ~0 us/task: the C run_quantum fast path never crosses it
journal_bound="${4:-0.3}"
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
# 8 seeds = one pass over the quick catalog (delayed payloads, rank
# kills over TCP and shm, recv reorder, transient task faults, the
# kill-and-recover cases); the full one is `tools/chaos.py --seeds 12`.
# --audit-journal: tools/journal_audit.py over every case's bundle, so a
# protocol-invariant violation fails even when the outcome matched.
if ! JAX_PLATFORMS=cpu python "$repo/tools/chaos.py" --seeds 8 --quick \
     --audit-journal; then
    rc=1
fi
echo "== premerge probe: recovery minimal-vs-full replay A/B =="
# the recorded-lineage minimal replay must re-execute STRICTLY FEWER
# tasks than replay-from-restore-point on the acceptance kill, each leg
# provably taking its intended path (a silent fallback to full replay
# fails); a second line does the same for the 3-rank DTD chain down the
# cross-rank skip-agreement path.
if ! JAX_PLATFORMS=cpu python "$repo/tools/chaos.py" --ab-minimal; then
    rc=1
fi
echo "== premerge probe: chaos soak (random recover schedules) =="
# 4 randomly seeded schedules drawn from the recover catalog,
# each with the full per-run invariant checks (validated numerics,
# no hang, recovery observed); the master seed is printed so any
# failure replays exactly (PARSEC_CHAOS_SOAK_SEED=<seed> --soak 4)
if ! JAX_PLATFORMS=cpu python "$repo/tools/chaos.py" --soak 4; then
    rc=1
fi
echo "== premerge probe: chaos degrade (drain-before-death, audited) =="
# a seeded ramped degradation of rank 1 (frame delay incl.
# heartbeats + task-body jitter, tools/chaos.py --degrade) on a
# 2-rank gang.  The health plane (prof/health.py) must score the
# rank down from its heartbeat gap/jitter inflation, the serving
# fabric must journal an evidence-carrying pre-emptive drain and
# stop placing on the rank STRICTLY BEFORE the heartbeat detector
# declares it dead (comm_peer_timeout_s is never approached), and
# the journal must pass the auditor clean — including the H1
# health invariant (drains evidence-backed, drained ranks never
# placement-targeted).
if ! JAX_PLATFORMS=cpu python "$repo/tools/chaos.py" --degrade; then
    rc=1
fi
exit $rc
