"""The four-chip cell's own pieces (PR 27): its three readers on
hand-made runs, its configuration and traffic files through
``harness.load_cell``, and the tiny four-chip potrf sound and with the
replica path broken underneath."""

import json
import os

import pytest

from benchmark import harness, runtime_spans as rs
from benchmark.metrics import (ici_gib_per_job, ici_host_us_per_transfer,
                               replica_peak_gib)
from benchmark.tests.test_harness import tiny

CELL = "potrf4.n147456_mb6144"
GIB = 2 ** 30


def run_of(**over):
    run = {"jobs": [(0.0, 1.0), (1.0, 2.0)], "ici": {}, "devices": [],
           "trace": None}
    run.update(over)
    return run


def test_ici_gib_per_job_sums_the_three_transports_over_the_jobs():
    run = run_of(ici={"puts": 4, "put_bytes": GIB, "bcasts": 9,
                      "bcast_bytes": 6 * GIB, "permutes": 1,
                      "permute_edges": 2, "permute_bytes": GIB})
    assert ici_gib_per_job.read(run) == 4.0


@pytest.mark.parametrize("over", [
    {"ici": {}},                                  # one chip: no ICI engine
    {"jobs": [], "ici": {"put_bytes": GIB}}])     # no job ended
def test_ici_gib_per_job_finds_nothing(over):
    assert ici_gib_per_job.read(run_of(**over)) is None


def test_replica_peak_gib_is_the_fullest_chips():
    devs = [{"stats": {"replica_bytes_peak": b}}
            for b in (GIB, 3 * GIB, 0, 2 * GIB)]
    assert replica_peak_gib.read(run_of(devices=devs)) == 3.0


@pytest.mark.parametrize("devs", [
    [{"stats": {"launches": 7}}],                 # the parent: no counter
    [{"stats": {"replica_bytes_peak": 0}}],       # nothing adopted
    []])
def test_replica_peak_gib_finds_nothing(devs):
    assert replica_peak_gib.read(run_of(devices=devs)) is None


def _spans(*evs):
    """One thread line: the window 0..10 ms and the given spans."""
    return {"devices": {}, "done": [], "threads": [
        [["bench:window", 0, 10_000_000, {}]] + [list(e) for e in evs]]}


def test_ici_host_us_per_transfer_is_the_mean_span_in_the_window(monkeypatch):
    data = _spans(("parsec:ici.bcast", 1_000_000, 300_000, {"bytes": 8}),
                  ("parsec:ici.put", 2_000_000, 100_000, {"bytes": 8}),
                  ("parsec:ici.permute", 3_000_000, 200_000, {}),
                  ("parsec:fin.release", 900_000, 900_000, {}),
                  ("parsec:ici.put", 11_000_000, 900_000, {}))   # after it
    monkeypatch.setattr(rs, "load", lambda path=None: data)
    assert ici_host_us_per_transfer.read(run_of(trace={})) == 200.0


def test_ici_host_us_per_transfer_finds_nothing(monkeypatch):
    bare = _spans(("parsec:fin.release", 900_000, 900_000, {}))
    monkeypatch.setattr(rs, "load", lambda path=None: bare)
    assert ici_host_us_per_transfer.read(run_of(trace={})) is None
    assert ici_host_us_per_transfer.read(run_of()) is None     # untraced

    def gone(path=None):
        raise FileNotFoundError("no .xplane.pb")
    monkeypatch.setattr(rs, "load", gone)
    assert ici_host_us_per_transfer.read(run_of(trace={})) is None


def test_the_cell_loads_with_its_configuration_and_traffic():
    spec, cell, config, traffic = harness.load_cell(CELL)
    assert cell["chips"] == 4 and cell["config"] == "dplasma_potrf_bf16_4chip"
    assert config["app"] == "potrf" and config["distribute"] is True
    assert config["grid"] == "2x2" and config["limits"] == \
        {"offdiag_resid": 0.02}
    assert config["mca"].get("device_mem_mb", 0) == 0     # exact, no budget
    assert (traffic["n"], traffic["mb"]) == (147456, 6144)
    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           "dplasma_potrf_bf16.json")) as f:
        one_chip = json.load(f)
    for key in ("storage", "accumulate", "diag_over_sqrt_n", "warm_jobs",
                "guarantee", "limits"):
        assert config[key] == one_chip[key]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["chips"]
    mine = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"tflops_per_chip.host_paced", "ici_gib_per_job",
            "ici_host_us_per_transfer", "replica_peak_gib"} <= mine
    assert not {m for m in mine if "roofline" in m or "idle" in m}
    for name in ("ici_gib_per_job", "ici_host_us_per_transfer",
                 "replica_peak_gib"):
        m = next(m for m in spec["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["layer"] == "ICI transport"
        assert m["moves"] == "tflops_per_chip.host_paced"


def test_tiny_four_chip_potrf_is_correct_on_the_grid_and_reads_its_metrics():
    r = tiny("potrf", 4, trace=True, name=CELL)
    assert r["correct"] is True and r["failed"] == 0
    got = r["metrics"]
    # 15 factor tiles of 64 x 64 float32 fan out a job, to two or three
    # chips each
    assert 0 < got["ici_gib_per_job"]["value"] < 60 * 64 * 64 * 4 / GIB
    assert 0 < got["replica_peak_gib"]["value"] <= 15 * 64 * 64 * 4 / GIB
    assert got["ici_host_us_per_transfer"]["value"] > 0
    assert got["compiles_in_window.host_paced"]["value"] >= 0
    assert not [k for k in got if "roofline" in k or "idle" in k]


def _replicas_arrive_empty(monkeypatch):
    """Every tile ICI moves, by put or by broadcast, lands as zeros."""
    import jax.numpy as jnp
    from parsec_tpu.comm.ici import IciEngine
    put, bcast = IciEngine.put, IciEngine.bcast
    monkeypatch.setattr(IciEngine, "put", lambda self, p, dst:
                        jnp.zeros_like(put(self, p, dst)))
    monkeypatch.setattr(IciEngine, "bcast", lambda self, p, dsts: {
        s: jnp.zeros_like(v) for s, v in bcast(self, p, dsts).items()})


def _release_takes_the_owners_copy_too(monkeypatch):
    """The release drops the tile wherever it lies, the producer's chip
    included: the factor tile is gone once its first replica leaves."""
    from parsec_tpu.data.data import Coherency
    from parsec_tpu.devices.xla import XlaDevice

    def release(self, datum):
        for sp, c in list(datum.copies().items()):
            if sp != 0:
                datum.detach_copy(sp)
                c.payload, c.coherency = None, Coherency.INVALID
    monkeypatch.setattr(XlaDevice, "release_replica", release)


@pytest.mark.parametrize("fault", [_replicas_arrive_empty,
                                   _release_takes_the_owners_copy_too])
def test_broken_replica_path_comes_out_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    try:
        r = tiny("potrf", 4)
    except RuntimeError:
        return          # a warm job failed outright: no result line at all
    assert r["correct"] is False


def test_a_replica_dropped_one_consumer_early_costs_a_stage_in_not_the_answer(
        monkeypatch):
    """Counting one reader short releases every replica before its last
    consumer: that one finds no copy on its chip and stages in as any
    late consumer does.  The mechanism may be early; it cannot be wrong."""
    from parsec_tpu.comm.ici import IciEngine
    expect = IciEngine.expect
    monkeypatch.setattr(
        IciEngine, "expect", lambda self, tp, copy, readers: expect(
            self, tp, copy, {s: max(n - 1, 1) for s, n in readers.items()}))
    r = tiny("potrf", 4)
    assert r["correct"] is True and r["failed"] == 0
