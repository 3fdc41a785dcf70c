"""bench.py is the host canaries and nothing else: each runs on the CPU
and names the device in its line; any other mode name — a typo, the
accelerator modes this file used to carry, or none — is an error that
points at the benchmark; and the pre-merge script asks for no mode the
file does not have."""

import json
import os
import re

import pytest

import bench


@pytest.mark.parametrize("app", ["gemn", "gemm", "potrf", "geqrf",
                                 "stencil", "eff", None])
def test_unknown_mode_is_an_error(app, monkeypatch, capsys):
    if app is None:
        monkeypatch.delenv("PARSEC_BENCH_APP", raising=False)
    else:
        monkeypatch.setenv("PARSEC_BENCH_APP", app)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    said = str(exc.value.code)
    assert "benchmark.run --workload" in said
    assert "benchmark/README.md" in said and "\n" not in said
    assert capsys.readouterr().out == ""          # no result line


#: the other modes spawn ranks or run four off/on pairs:
#: tools/premerge_bench.sh runs those
@pytest.mark.parametrize("app, metric", [
    ("tracer", "tracer_overhead"), ("tasks", "task_throughput"),
    ("ntasks", "task_throughput_nontrivial"),
    ("journal", "journal_overhead")])
def test_host_canary_runs_on_cpu_and_names_it(app, metric, monkeypatch,
                                              capsys):
    monkeypatch.setenv("PARSEC_BENCH_APP", app)
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1                          # exactly one line
    line = json.loads(out[0])
    assert line["metric"] == metric and line["value"] >= 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["kind"] and dev["count"] >= 1


def test_release_canary_counts_a_task_of_the_cell_3_dag(monkeypatch, capsys):
    """Counts, not a speed (PR 34): no by-name adapter call and no locked
    repo mutation is left on a task's release path, PTG or DTD, and the
    PTG's deliveries are the DAG's 16 368 a job, none through a general
    arm."""
    monkeypatch.setenv("PARSEC_BENCH_APP", "release")
    bench.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "ptg_by_name_calls_per_task"
    assert line["device"]["platform"] == "cpu"
    ptg, dtd = line["release"]["ptg"], line["release"]["dtd"]
    assert ptg["tasks"] == dtd["tasks"] == 5984
    assert ptg["by_name_calls_per_task"] == dtd["by_name_calls_per_task"] == 0
    assert ptg["repo_mutations_per_task"] == dtd["repo_mutations_per_task"] \
        == 0
    assert 10 < ptg["positional_calls_per_task"] < 14
    assert ptg["release"] == {"deliveries": 16368, "general_deliveries": 0,
                              "repo_holds": 0}
    assert dtd["release"]["deliveries"] == 0


def test_launch_canary_counts_a_flow_of_the_cell_3_dag(monkeypatch, capsys):
    """Counts, not a speed (PR 36): a manager takes a datum's lock once
    a flow it stages (the parent took it three times) and the device's
    memory lock once a WAVE (twice a flow), builds no signature itself
    (one a task, at ``submit``; a chain link has none), and every
    operand of the job is resident but its nt - 1 NEW-arena ``W``
    panels, PTG and DTD alike.  A ready task reaches the device
    handed in by the thread that released it, all but the PTG
    pool's one start-up task, and no worker submits (1 a task before)."""
    monkeypatch.setenv("PARSEC_BENCH_APP", "launch")
    bench.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "ptg_datum_lock_holds_per_flow"
    assert line["device"]["platform"] == "cpu"
    for front in ("ptg", "dtd"):
        got = line["launch"][front]
        assert got["tasks"] == 5984
        # POTRF T W, POTRFL T, TRSM W C, SYRK T R, GEMM C L R
        assert got["flows"] == 2 * 31 + 1 + 2 * 496 * 2 + 3 * 4960 == 16927
        assert got["staged_flows"] == 31 and got["bytes_in"] == 0
        assert got["resident_flows"] == got["flows"] - 31
        # one hold a resident flow; a NEW panel's copy is made and
        # handed over under a few more
        assert 1.0 <= got["datum_lock_holds_per_flow"] < 1.02
        # one hold a wave (5-7 tasks of 2-3 flows), one more a panel
        assert got["mem_lock_holds_per_flow"] < 0.2
        assert got["manager_sig_calls_per_task"] == 0
        assert got["sig_calls_per_task"] == round(5952 / 5984, 3)
        roots = 1 if front == "ptg" else 0   # POTRF(0) from the start-up
        assert got["direct_submits"] == 5984 - roots
        assert got["worker_submits_per_task"] == round(roots / 5984, 3)


def test_premerge_names_only_modes_that_exist():
    script = os.path.join(os.path.dirname(os.path.abspath(bench.__file__)),
                          "tools", "premerge_bench.sh")
    with open(script) as f:
        text = f.read()
    asked = set(re.findall(r"PARSEC_BENCH_APP=(\$?\w+)", text))
    # the first loop names its modes through $mode
    loop = re.search(r"for mode in ([\w ]+); do", text)
    assert "$mode" in asked and loop
    asked = (asked - {"$mode"}) | set(loop.group(1).split())
    assert asked and asked <= set(bench._AUX_MODES), \
        asked - set(bench._AUX_MODES)
