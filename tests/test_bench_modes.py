"""bench.py's split between the accelerator modes and the host-only
canaries: on the CPU the first fail (they never shrink to a toy size),
the second run and name the device in their line; a device without a
recorded peak is an error, not a default."""

import json

import pytest

import bench


@pytest.mark.parametrize("app", ["gemm", "potrf", "geqrf", "stencil", "eff"])
def test_accelerator_modes_fail_without_a_tpu(app, monkeypatch, capsys):
    monkeypatch.setenv("PARSEC_BENCH_APP", app)
    monkeypatch.delenv("PARSEC_EFF_CHILD", raising=False)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "found no TPU" in str(exc.value.code)
    assert f"'{app}'" in str(exc.value.code)
    assert capsys.readouterr().out == ""          # no result line


def test_unknown_mode_is_an_error(monkeypatch):
    monkeypatch.setenv("PARSEC_BENCH_APP", "gemn")
    with pytest.raises(SystemExit, match="unknown PARSEC_BENCH_APP"):
        bench.main()


def test_host_canary_runs_on_cpu_and_names_it(monkeypatch, capsys):
    monkeypatch.setenv("PARSEC_BENCH_APP", "tracer")
    bench.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "tracer_overhead"
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["kind"] and dev["count"] >= 1


def test_peak_table_is_keyed_by_device_kind():
    assert bench._peak_gflops({"kind": "TPU v5 lite"}) == 197_000.0
    with pytest.raises(SystemExit, match="no peak rate on record"):
        bench._peak_gflops({"kind": "cpu"})
    with pytest.raises(SystemExit, match="TPU v9"):
        bench._peak_gflops({"kind": "TPU v9"})


def test_eff_measured_names_missing_points(monkeypatch):
    """A virtual-mesh child that fails or prints nothing is reported by
    name with its reason, never skipped in silence."""
    import subprocess

    def fake_run(cmd, env=None, **kw):
        nd = int(env["PARSEC_EFF_CHILD"])
        assert env["JAX_PLATFORMS"] == "cpu"      # never reaches for the chip
        assert f"device_count={nd}" in env["XLA_FLAGS"]
        if nd == 2:
            return subprocess.CompletedProcess(cmd, 3, "", "boom")
        if nd == 4:
            return subprocess.CompletedProcess(cmd, 0, "no json here\n", "")
        if nd == 8:
            raise subprocess.TimeoutExpired(cmd, 900)
        return subprocess.CompletedProcess(
            cmd, 0, json.dumps({"t": 1.5, "ndev": nd}) + "\n", "")
    monkeypatch.setattr(subprocess, "run", fake_run)
    times, payloads, missing = bench._eff_measured()
    assert times == {1: 1.5} and list(payloads) == [1]
    assert set(missing) == {2, 4, 8}
    assert "exit 3" in missing[2] and "boom" in missing[2]
    assert "no result line" in missing[4]
    assert "timed out" in missing[8]


def test_require_clean_devices_refuses_failed_widths_and_faults():
    class Stats:
        faults = 0

    class Dev:
        name = "tpu:0"
        fuse_failures = {}
        stats = Stats()

    class Reg:
        accelerators = [Dev()]

    class Ctx:
        device_registry = Reg()

    bench._require_clean_devices(Ctx())
    Dev.fuse_failures = {("potrf.fn", 8): "XlaRuntimeError: RESOURCE_EXHAUSTED"}
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        bench._require_clean_devices(Ctx())
    Dev.fuse_failures = {}
    Stats.faults = 2
    with pytest.raises(RuntimeError, match="2 device faults"):
        bench._require_clean_devices(Ctx())
