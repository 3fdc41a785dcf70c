#!/usr/bin/env python
"""Pre-merge bench regression guard.

Diffs a fresh ``bench.py`` JSON line against the previous round's
driver artifact (``BENCH_r*.json``, highest round number wins) and
exits non-zero when any shared recorded metric regressed by more than
the threshold (default 15%).  Direction-aware: ``us``/latency-class
metrics regress UP, throughput metrics regress DOWN.

Intended as the CPU-only pre-merge smoke over the host-side probes:

    PARSEC_BENCH_APP=tasks  python bench.py > /tmp/tasks.json
    python tools/bench_guard.py /tmp/tasks.json
    PARSEC_BENCH_APP=rtt    python bench.py > /tmp/rtt.json
    python tools/bench_guard.py /tmp/rtt.json
    PARSEC_BENCH_APP=tracer python bench.py > /tmp/tracer.json
    python tools/bench_guard.py /tmp/tracer.json

Usage:
    bench_guard.py NEW.json [--repo DIR] [--threshold 0.15]
                   [--prev FILE]

``NEW.json`` may be either a raw bench line ({"metric": ...}) or a
driver artifact ({"parsed": {...}}); ``-`` reads stdin.  A metric
that only exists on one side is reported but never fails the guard
(new metrics appear, modes differ per round).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

#: ratio-valued metrics compared by ABSOLUTE delta, smaller is better:
#: their normal baseline is 0.0 (the telemetry gate's min-of-pairs
#: clamps there), where relative change is undefined and any ratio or
#: cap scheme turns noise into a discontinuity.  Their derived
#: vs_baseline is skipped for the same reason — the value IS the gate.
ABSOLUTE_DELTA = ("telemetry_overhead", "journal_overhead",
                  "overhead_us")

#: metrics where SMALLER is better (everything else: bigger is better)
LOWER_IS_BETTER = ("task_rtt", "tracer_overhead", "telemetry_overhead",
                   "journal_overhead",
                   "backward_error", "recovery_makespan_ratio",
                   "factorization_residual",
                   # bw/rtt protocol-mix guards (the r6 event-loop
                   # transport): more wire frames or more syscalls per
                   # MB moved for the same probe is a transport
                   # regression even when the headline number hides in
                   # host noise.  act_eager stays higher-is-better by
                   # default: the probes declare eager coverage, so
                   # eroding it IS a regression
                   "frames_sent", "syscalls_per_mb")

#: keys that are configuration/metadata or noise diagnostics, never
#: compared.  rep_band/best are extreme order statistics of a protocol
#: with documented ~20% run-to-run variance — only the median
#: headline gates; the refinement LADDERS (per-step residual histories)
#: legitimately move by orders of magnitude and are accuracy evidence,
#: not rate metrics.
SKIP_KEYS = {"metric", "unit", "storage", "note", "ib", "device",
             "fuse_panel", "potrf_protocol", "potrf_storage",
             "potrf_fuse_panel", "rep_band_gflops", "best_gflops",
             "potrf_rep_band_gflops", "potrf_best_gflops",
             "ir_residuals", "potrf_ir_residuals", "ls_refine_errors",
             # partial_writes depends on transient kernel send-buffer
             # state and wakeups on OS thread-scheduling timing — not
             # on the code under test; act_rdv/act_inline/coalesced are
             # direction-less mix descriptors (act_eager alone gates:
             # eager coverage eroding is the regression)
             "partial_writes", "wakeups", "act_rdv", "act_inline",
             "coalesced_msgs", "transport",
             # critical-path attribution (PARSEC_BENCH_TRACE=1) — and
             # its r14 online twin + the per-bucket agreement — is
             # informational: the buckets reshuffle with host load and
             # have no regression direction; the tracer-overhead gate
             # is the off-vs-on tasks comparison in premerge_bench.sh
             "attribution", "attribution_online",
             "attribution_agreement_pp",
             # host core inventory on bw/rtt lines (where the number
             # was measured, not what was measured) and the telemetry
             # mode's raw side readings (the gated value is the ratio)
             "host", "tasks_off", "tasks_on",
             # r14 tasks-probe diagnostics: the staged per-task budget
             # breakdown localizes a headline regression (the gated
             # value is task_throughput itself) and the suppressed-
             # doorbell count tracks scheduling burst shape, not the
             # code under test
             "budget", "doorbell",
             # r17 fast-path coverage + multi-rank diagnostics: the
             # bailout histogram is gated EXACTLY (zero expected) by
             # the premerge ntasks/aggregate legs, not by relative
             # diff; rank topology and per-rank/solo side readings say
             # where the aggregate headline was measured; the
             # oversubscribed-host scaling_efficiency measures
             # time-slicing fairness (the headline value gates);
             # "skipped" records why a multi-core-only leg did not run
             "bailouts", "chains", "ranks", "nb_cores_per_rank",
             "per_rank_tasks_s", "solo_tasks_s", "scaling_efficiency",
             "skipped",
             # recovery A/B side readings (r13; r15 adds the nested
             # "dtd" leg — insert-stream skip-agreement re-execution
             # counts + makespan ratios): host-load-sensitive
             # makespans and exact re-execution counts are evidence,
             # not rate metrics — the gated value is the headline
             # minimal-makespan ratio (lower-is-better), and the
             # minimal<full invariant on BOTH DAGs is asserted by
             # chaos --ab-minimal in premerge
             "recovery"}


def _load(path: str) -> dict:
    if path == "-":
        raw = sys.stdin.read()
    else:
        with open(path) as f:
            raw = f.read()
    # accept a whole driver artifact, a bare JSON object, or the last
    # JSON line of a bench run's stdout
    try:
        obj = json.loads(raw)
    except ValueError:
        obj = None
        for line in reversed(raw.splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
                break
            except ValueError:
                continue
        if obj is None:
            raise SystemExit(f"bench_guard: no JSON object in {path}")
    if isinstance(obj, dict) and isinstance(obj.get("parsed"), dict):
        obj = obj["parsed"]
    return obj


def _previous(repo: str) -> str:
    arts = []
    for p in glob.glob(os.path.join(repo, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        if m:
            arts.append((int(m.group(1)), p))
    if not arts:
        raise SystemExit(f"bench_guard: no BENCH_r*.json under {repo}")
    return max(arts)[1]


def _flatten(obj: dict, prefix: str = "") -> dict:
    """Numeric leaves by dotted path; lists index by position."""
    out = {}
    for k, v in obj.items():
        if k in SKIP_KEYS:
            continue
        path = f"{prefix}{k}"
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[path] = float(v)
        elif isinstance(v, dict):
            out.update(_flatten(v, path + "."))
        elif isinstance(v, list) and v and \
                all(isinstance(x, (int, float)) for x in v):
            for i, x in enumerate(v):
                out[f"{path}[{i}]"] = float(x)
    return out


def _lower_is_better(path: str) -> bool:
    # vs_baseline ratios are normalized "higher is better" for EVERY
    # metric (bench.py inverts latency-class targets itself)
    if path.endswith("vs_baseline"):
        return False
    segs = [s.split("[")[0] for s in path.split(".")]
    leaf = segs[-1]
    # leaf-scoped: the counter's own direction wherever it appears
    # (protocol breakdown keys, error leaves incl. prefixed forms like
    # potrf_backward_error)
    if any(tag in leaf for tag in LOWER_IS_BETTER):
        return True
    # metric-scoped: ONLY the namespaced headline (metric.value)
    # inherits the metric's direction from its prefix — a protocol leaf
    # under task_rtt.* must not inherit "lower is better" (that
    # inverted act_eager gating for the rtt probe)
    if leaf == "value":
        return any(tag in seg for seg in segs[:-1]
                   for tag in LOWER_IS_BETTER)
    return False


def _namespaced(obj: dict) -> dict:
    """Flatten, prefixing the mode-generic keys (value, vs_baseline,
    band...) with the metric name so two artifacts from different bench
    modes never compare a GEMM rate against a tasks/s number."""
    flat = _flatten(obj)
    metric = obj.get("metric")
    if not metric:
        return flat
    return {(f"{metric}.{k}" if not k.startswith(("tiled_", "potrf_",
                                                  "task_", "dataflow_",
                                                  "stencil_", "tracer_",
                                                  "dag_"))
             else k): v for k, v in flat.items()}


def compare(new: dict, prev: dict, threshold: float):
    """Returns (regressions, report_lines).  Only keys present on BOTH
    sides are compared; vs_baseline-style ratios compare like their
    underlying value."""
    new_f = _namespaced(new)
    prev_f = _namespaced(prev)
    regressions = []
    lines = []
    for path in sorted(set(new_f) & set(prev_f)):
        a, b = prev_f[path], new_f[path]
        if any(tag in path for tag in ABSOLUTE_DELTA):
            if path.endswith("vs_baseline"):
                continue
            delta = b - a
            bad = delta > threshold
            mark = "REGRESSION" if bad else "ok"
            lines.append(f"  {path}: {a:g} -> {b:g} "
                         f"({delta:+.3f} abs) {mark}")
            if bad:
                regressions.append((path, a, b, delta))
            continue
        if a == 0:
            continue
        change = (b - a) / abs(a)
        bad = change > threshold if _lower_is_better(path) \
            else change < -threshold
        mark = "REGRESSION" if bad else "ok"
        lines.append(f"  {path}: {a:g} -> {b:g} ({change:+.1%}) {mark}")
        if bad:
            regressions.append((path, a, b, change))
    for path in sorted(set(new_f) - set(prev_f)):
        lines.append(f"  {path}: (new) {new_f[path]:g}")
    for path in sorted(set(prev_f) - set(new_f)):
        lines.append(f"  {path}: (gone; was {prev_f[path]:g})")
    return regressions, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("new", help="fresh bench JSON ('-' = stdin)")
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="repo dir holding BENCH_r*.json artifacts")
    ap.add_argument("--prev", default=None,
                    help="explicit previous JSON (overrides --repo scan)")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="relative regression that fails (default 0.15)")
    args = ap.parse_args(argv)

    new = _load(args.new)
    prev_path = args.prev or _previous(args.repo)
    prev = _load(prev_path)
    if new.get("metric") and prev.get("metric") and \
            new["metric"] != prev["metric"]:
        # different modes: compare only the overlap (e.g. a potrf run
        # against a gemm+potrf merged artifact still shares the
        # tiled_potrf_* keys when present)
        print(f"bench_guard: metric {new['metric']!r} vs previous "
              f"{prev['metric']!r} — comparing shared keys only")
    regs, lines = compare(new, prev, args.threshold)
    print(f"bench_guard: {args.new} vs {prev_path} "
          f"(threshold {args.threshold:.0%})")
    for ln in lines:
        print(ln)
    if regs:
        print(f"bench_guard: {len(regs)} metric(s) regressed >"
              f"{args.threshold:.0%}:")
        for path, a, b, change in regs:
            print(f"  {path}: {a:g} -> {b:g} ({change:+.1%})")
        return 1
    print("bench_guard: no regression beyond threshold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
