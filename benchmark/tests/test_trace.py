"""The trace reduction on a trace recorded on the chip (PR 24: two jobs
of potrf.n98304_mb6144, ``trace.load``'s output with the HLO text of
each operation cut to its name)."""

import gzip
import json
import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def chip_trace():
    with gzip.open(os.path.join(HERE, "data",
                                "trace_potrf_nt16_chip.json.gz"), "rt") as f:
        return json.load(f)


def test_union_of_intervals():
    assert trace.union_ns([]) == 0
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert trace.union_ns([(20, 30), (0, 10), (10, 12)]) == 22


def test_busy_and_idle_account_for_the_whole_window(chip_trace):
    lo, hi = trace.window(chip_trace)
    assert (lo, hi) == (0, 4664446423)           # the bench:window span
    busy = trace.busy(chip_trace)["/device:TPU:0"]
    assert busy == pytest.approx(4.387292766)
    gaps = dict(trace.idle_gaps(chip_trace))
    assert busy + sum(gaps.values()) == pytest.approx((hi - lo) / 1e9)
    # the gaps fall where the host was staging or waiting, hardly elsewhere
    assert gaps["stage"] + gaps["wait"] > 0.99 * sum(gaps.values())


def test_own_programs_are_left_out_of_launches_and_runtime_busy(chip_trace):
    mods = chip_trace["devices"]["/device:TPU:0"]["modules"]
    own = [m for m in mods if trace.is_own(m[0])]
    assert len(own) == 274 and all("bench_stage_tile" in m[0] for m in own)
    assert trace.launches(chip_trace) == len(mods) - len(own) == 476
    all_busy = trace.busy(chip_trace)["/device:TPU:0"]
    runtime = trace.busy(chip_trace, runtime_only=True)["/device:TPU:0"]
    staged = sum(m[2] for m in own) / 1e9
    # but for the stage events the window clips (the device clock runs
    # a third of a millisecond ahead of the host span)
    assert runtime == pytest.approx(all_busy - staged, rel=1e-3)


def test_programs_and_operations_by_name(chip_trace):
    top = trace.programs(chip_trace, top=3)
    assert top[0][0].startswith("jit_target(") and top[0][2] == 113
    assert top[0][1] > top[1][1] > top[2][1]
    ops = trace.device_ops(chip_trace)
    assert len(ops) == 10
    assert ops[0][0] == "jit_target(14857792302800472602)/%fusion.2"
    # every operation lies inside an execution of some program
    assert not any(name.startswith("?/") for name, _s in ops)


def test_gap_is_given_to_the_span_open_when_it_began():
    t = {"devices": {"/device:TPU:0": {"modules": [
            ["jit_fn(1)", 100, 100], ["jit_bench_stage_tile(2)", 300, 50],
            ["jit_fn(1)", 600, 300]], "ops": []}},
         "host": [["bench:window", 0, 1000], ["bench:stage", 0, 150],
                  ["bench:wait", 150, 800]]}
    assert dict(trace.idle_gaps(t)) == pytest.approx(
        {"stage": 100e-9, "wait": (100 + 250 + 100) * 1e-9})
    assert trace.launches(t) == 2
    assert trace.busy(t)["/device:TPU:0"] == pytest.approx(450e-9)
    assert trace.busy(t, True)["/device:TPU:0"] == pytest.approx(400e-9)
