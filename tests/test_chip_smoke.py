"""CPU rehearsal of chip_smoke.py and of what it leans on.

The script itself passes only on a TPU; its phases are plain functions
of their sizes, run here tiny on one and on four virtual CPU devices
(f32 storage: XLA's CPU backend has no bf16 x bf16 -> f32 dot), sound
and with a fault planted under them.  Beside them: where the compile
cache is placed, a broken backend raising out of init_devices, a failed
fused width keeping its reason, and tiles born on the device that owns
them.
"""

import json
import os

import numpy as np
import pytest

import chip_smoke as cs
from parsec_tpu.utils.mca import params


@pytest.fixture
def devices():
    """Confine the phase to the first n virtual devices."""
    def confine(n):
        params.set("device_max", n)
    yield confine
    params.unset("device_max")


def _one(out):
    assert len(out["devices"]) == 1
    d = out["devices"][0]
    assert d["stats"]["executed_tasks"] > 0 and d["stats"]["faults"] == 0
    assert d["fuse_failures"] == {}
    json.dumps(out)            # every phase line must serialize
    return d


def _limits(config):
    return cs._config(config)["limits"]


def test_gemm_phase_one_device(devices):
    devices(1)
    out = cs.run_gemm(mb=64, mt=3, nt=3, kt=4, seed=3, storage="float32")
    d = _one(out)
    assert d["stats"]["executed_tasks"] == 2 * 3 * 3 * 4   # two passes
    # the benchmark's number, held to the benchmark's limit
    assert set(out["compared"]) == {"c_rel_err"}
    err = out["compared"]["c_rel_err"]
    assert err["limit"] == _limits("dplasma_gemm_bf16")["c_rel_err"]
    assert err["value"] <= 1e-6 and len(out["run_s"]) == 1


def test_potrf_phase_one_device(devices):
    devices(1)
    out = cs.run_potrf(mb=32, nt=6, seed=3, storage="float32")
    _one(out)
    resid = out["compared"]["offdiag_resid"]
    assert resid["limit"] == _limits("dplasma_potrf_bf16")["offdiag_resid"]
    assert resid["value"] < 1e-5                 # f32 storage
    assert len(out["run_s"]) == 2                # one warm pass, two runs
    assert out["mca"] == cs._config("dplasma_potrf_bf16")["mca"]


# -- faults planted under the phases: each must come out a SmokeFailure ------

def _diagonal_alone(monkeypatch):
    """Every kernel keeps the diagonal's square root and nothing else:
    L = sqrt(diag A) I (sqrt(n) I on the operand the phase used to
    stage, which its backward error let through: PERF.md section 7)."""
    import jax.numpy as jnp
    from parsec_tpu.apps import potrf

    def root(T):
        return jnp.diag(jnp.sqrt(jnp.diag(T.astype(jnp.float32))))
    monkeypatch.setitem(
        potrf._kernels, ("potrf", None),
        lambda T, W: {"T": root(T).astype(T.dtype),
                      "W": jnp.diag(1.0 / jnp.diag(root(T)))})
    monkeypatch.setitem(potrf._kernels, ("potrf_last", None),
                        lambda T: root(T).astype(T.dtype))
    monkeypatch.setitem(potrf._kernels, ("trsm", None),
                        lambda W, C: jnp.zeros_like(C))
    monkeypatch.setitem(potrf._kernels, ("syrk", None), lambda T, R: T)
    monkeypatch.setitem(potrf._kernels, ("gemm", None), lambda C, L, R: C)


def _tile_zeroed(monkeypatch):
    """One off-diagonal tile of the factor is lost after the last pass."""
    import jax.numpy as jnp
    from benchmark import tiles
    real = cs._run_passes

    def run_passes(ctx, passes, t0, stage, pool, A):
        timed = real(ctx, passes, t0, stage, pool, A)
        datum = A.data_of(3, 1)
        space = next(sp for sp, c in datum.copies().items()
                     if c.version == datum.newest_version()
                     and c.payload is not None)
        datum.overwrite_on(space, jnp.zeros_like(tiles.newest(A, 3, 1)))
        return timed
    monkeypatch.setattr(cs, "_run_passes", run_passes)


def _trsm_altered(monkeypatch):
    import jax.numpy as jnp
    from parsec_tpu.apps import potrf
    monkeypatch.setitem(
        potrf._kernels, ("trsm", None),
        lambda W, C: (1.1 * jnp.matmul(C, W.T)).astype(C.dtype))


def _updates_left_out(monkeypatch):
    from parsec_tpu.apps import potrf
    monkeypatch.setitem(potrf._kernels, ("gemm", None), lambda C, L, R: C)


@pytest.mark.parametrize("fault", [_diagonal_alone, _tile_zeroed,
                                   _trsm_altered, _updates_left_out])
def test_potrf_phase_sees_a_wrong_factor(devices, monkeypatch, fault):
    devices(1)
    fault(monkeypatch)
    with pytest.raises(cs.SmokeFailure, match="offdiag_resid"):
        cs.run_potrf(mb=32, nt=6, seed=3, storage="float32", passes=1)


def test_gemm_phase_sees_half_a_product(devices, monkeypatch):
    import jax.numpy as jnp
    from parsec_tpu.apps import gemm
    devices(1)
    monkeypatch.setitem(gemm._kernels, (1.0, None),
                        lambda Ai, Bi, Ci: Ci + 0.5 * jnp.matmul(Ai, Bi))
    with pytest.raises(cs.SmokeFailure, match="c_rel_err"):
        cs.run_gemm(mb=64, mt=3, nt=3, kt=4, seed=3, storage="float32",
                    passes=1)


@pytest.mark.parametrize("nt", [3, 8])
def test_geqrf_phase_one_device(devices, nt):
    """The geqrf phase as main() runs it, the benchmark's job through
    the default path (chain fusion on), at the uncut nt = 8 too: two
    chain programs whatever nt is."""
    devices(1)
    out = cs.run_geqrf(mb=64, nt=nt, ib=16, seed=3, storage="float32")
    d = _one(out)
    assert out["phase"] == "geqrf" and out["n"] == nt * 64
    assert out["ib"] == 16
    assert out["compared"]["factor_resid"]["value"] < 1e-5
    assert out["compared"]["below_diag_max"]["value"] == 0.0
    assert d["stats"]["chained_launches"] > 0
    assert d["stats"]["chain_programs"] <= 2


def test_geqrf_phase_sees_a_wrong_factor(devices, monkeypatch):
    """TSMQR left out: the trailing matrix is never updated, and the
    phase must fail on the configuration's own limit."""
    from parsec_tpu.apps import qr
    devices(1)
    monkeypatch.setitem(qr._kernels, "tsmqr",
                        lambda Q, C1, C2: {"C1": C1, "C2": C2})
    with pytest.raises(cs.SmokeFailure, match="factor_resid"):
        cs.run_geqrf(mb=64, nt=4, ib=16, seed=3, storage="float32",
                     passes=1)


def test_geqrf_phase_refuses_a_clamped_ib(devices):
    """ib that does not block the panel is clamped to 0 by the engine:
    the phase must say so, not report an unblocked run as ib=24."""
    devices(1)
    with pytest.raises(cs.SmokeFailure, match="ib=24"):
        cs.run_geqrf(mb=64, nt=2, ib=24, seed=3, storage="float32",
                     passes=1)


def test_multichip_phase_four_devices(devices):
    devices(4)
    lines = []
    out = cs.run_multichip({"mb": 32, "nt": 8, "storage": "float32"},
                           {"mb": 64, "mt": 3, "nt": 3, "kt": 4,
                            "storage": "float32"},
                           seed=3, emit=lines.append)
    assert out["potrf_tiles_rel_diff"] <= cs.POTRF_AGREE_TOL
    assert out["gemm_tiles_rel_diff"] <= 1e-4
    # the four-chip run took the four-chip configuration
    assert out["potrf_offdiag_resid"]["many"] < 1e-5
    assert out["ici_ring"]["permutes"] == 1 and out["ici_ring"]["bcasts"] == 1
    by = {(ln["phase"], ln.get("scope")): ln for ln in lines}
    assert set(by) == {("ici_ring", None), ("potrf", "many"),
                       ("gemm", "many"), ("potrf", "one"), ("gemm", "one")}
    for phase in ("potrf", "gemm"):
        many, one = by[(phase, "many")], by[(phase, "one")]
        # every one of the four devices worked and holds tiles, and the
        # ICI engine moved data between them
        assert len(many["devices"]) == 4 and len(one["devices"]) == 1
        for d in many["devices"]:
            assert d["stats"]["executed_tasks"] > 0 and d["tiles_held"] > 0
        moved = many["ici"]
        assert moved["bcasts"] + moved["puts"] + moved["permutes"] > 0
        assert one["ici"] == {}
        json.dumps(many)
    # on the 2 x 2 grid a GEMM panel has one other chip to reach: a put
    moved = by[("gemm", "many")]["ici"]
    assert moved["bcasts"] + moved["puts"] + moved["permutes"] > 0


def test_idle_device_fails_the_distributed_check():
    devs = [{"name": "tpu:0", "fuse_failures": {}, "tiles_held": 3,
             "stats": {"executed_tasks": 5, "faults": 0}},
            {"name": "tpu:1", "fuse_failures": {}, "tiles_held": 0,
             "stats": {"executed_tasks": 0, "faults": 0}}]
    cs._require_healthy("potrf", devs, every_device=False)
    with pytest.raises(cs.SmokeFailure, match="tpu:1 sat idle"):
        cs._require_healthy("potrf", devs, every_device=True)
    devs[0]["stats"]["faults"] = 1
    with pytest.raises(cs.SmokeFailure, match="faults"):
        cs._require_healthy("potrf", devs, every_device=False)


def test_main_fails_without_a_tpu(capsys):
    """No option lets the script pass on a CPU: non-zero, says why, and
    prints no result line."""
    assert cs.main([]) != 0
    assert cs.main(["--chips", "4"]) != 0
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "found no TPU" in cap.err


def test_native_extensions_all_load():
    """Same toolchain here as on the chip's machine: all four build."""
    assert cs.native_extensions() == {
        "libparsec_tpu": True, "schedext": True, "pinsext": True,
        "commext": True}


# -- compile cache placement -------------------------------------------------

@pytest.fixture
def cache_config():
    import jax
    was = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_env_set_code_sets_nothing(monkeypatch, cache_config, tmp_path):
    from parsec_tpu.devices import configure_compile_cache
    cache_config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert cache_config.jax_compilation_cache_dir is None


def test_cache_unset_goes_to_fixed_dir_in_checkout(monkeypatch, cache_config):
    from parsec_tpu.devices import (COMPILE_CACHE_DIR,
                                    configure_compile_cache, init_devices)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_config.update("jax_compilation_cache_dir", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert configure_compile_cache() == COMPILE_CACHE_DIR
    assert cache_config.jax_compilation_cache_dir == COMPILE_CACHE_DIR
    # and every entry point passes through it: init_devices places it
    cache_config.update("jax_compilation_cache_dir", None)
    init_devices(None)
    assert cache_config.jax_compilation_cache_dir == COMPILE_CACHE_DIR


# -- no fallback that hides the device ---------------------------------------

def test_init_devices_raises_when_the_backend_does(monkeypatch):
    import jax
    from parsec_tpu.devices import init_devices

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu': boom")
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        init_devices(None)
    # device_enabled=0 stays the way to ASK for a host-only runtime
    params.set("device_enabled", 0)
    try:
        assert init_devices(None).accelerators == []
    finally:
        params.unset("device_enabled")


def test_failed_fused_width_keeps_its_reason(monkeypatch, devices, capfd):
    """A fused width whose compile fails still runs (as singles) — but
    the device says which width failed and why, once."""
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.devices.xla import XlaKernel, wait_fuse_warm
    from parsec_tpu.dsl.ptg.api import DATA, IN, OUT, PTG, Range
    import time

    class Refused:
        def lower(self, *a):
            raise ValueError("Mosaic failed to compile: Bad lhs type")

    def mul_kernel(T):
        time.sleep(0.05)     # trace-time stall: the wave queues behind it
        return T * 2.0

    real = XlaKernel.jitted_fused
    monkeypatch.setattr(
        XlaKernel, "jitted_fused",
        lambda self, donate, n: Refused() if self.fn is mul_kernel
        else real(self, donate, n))
    MT, mb = 16, 8
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=MT * mb)
    for _m, n in A.local_tiles():
        A.data_of(0, n).copy_on(0).payload[:] = float(n)
    devices(1)
    params.set("device_fuse", 8)
    try:
        with Context(nb_cores=2) as ctx:
            p = PTG("wave", MT=MT)
            tb = p.task("MUL", n=Range(0, MT - 1)) \
                .affinity(lambda n, A=A: A(0, n)) \
                .flow("T", "RW", IN(DATA(lambda n, A=A: A(0, n))),
                      OUT(DATA(lambda n, A=A: A(0, n))))
            tb.body(mul_kernel, device="tpu")
            ctx.add_taskpool(p.build())
            ctx.wait(timeout=120)
            wait_fuse_warm()
            dev = ctx.device_registry.accelerators[0]
            failures, stats = dict(dev.fuse_failures), dev.stats
    finally:
        params.unset("device_fuse")
    for n in range(MT):
        np.testing.assert_allclose(
            np.asarray(A.data_of(0, n).pull_to_host().payload), 2.0 * n)
    assert stats.executed_tasks == MT and stats.fused_launches == 0
    assert failures, "the failed width vanished"
    for (kernel, width), reason in failures.items():
        assert "mul_kernel" in kernel and width > 1
        assert reason == "ValueError: Mosaic failed to compile: Bad lhs type"
    err = capfd.readouterr().err
    for (_k, width) in failures:
        assert err.count(f"fused width {width} of kernel") == 1


def test_prestage_births_tiles_on_their_owning_device(devices):
    """Tiles of a matrix spread with distribute_devices are generated on
    the device that owns them, not all on the first."""
    from benchmark import tiles
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    devices(4)
    A = TwoDimBlockCyclic(mb=8, nb=8, lm=32, ln=32, name="A")
    with Context(nb_cores=1) as ctx:
        A.distribute_devices(ctx)
        tiles.stage(A, ctx, seed=7)
        by_space = {d.space: d.jdev for d in ctx.device_registry.accelerators}
        seen = set()
        for m, n in A.local_tiles():
            datum = A.data_of(m, n)
            copy = datum.copies()[datum.preferred_device]
            assert copy.version == datum.newest_version()
            assert copy.payload.devices() == {by_space[datum.preferred_device]}
            seen.add(datum.preferred_device)
        assert len(seen) == 4
        # the seed and the tile alone define the value
        np.testing.assert_array_equal(
            np.asarray(A.data_of(0, 0).copies()[
                A.data_of(0, 0).preferred_device].payload),
            np.asarray(tiles.make_tile(A, 7, 0, 0)))
        tiles.discard_tiles(A)
