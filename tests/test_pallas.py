"""Pallas tile-kernel tests (the user-kernel seam; reference: the BODY
[type=CUDA] incarnations + tests/dsl/ptg/cuda/stress.jdf pattern).
These run on the CPU, so every kernel is built with ``interpret=True``
here in the test — the runtime path never picks interpret mode itself
(tests/test_chip_compile.py compiles the same kernels for a v5e)."""

import numpy as np
import pytest

from parsec_tpu.apps.pallas_kernels import PALLAS, XLA, pallas_gemm_tile
from parsec_tpu.utils.mca import params


def _rel_err(got, ref):
    return np.abs(got - ref).max() / max(1.0, np.abs(ref).max())


def test_pallas_blocked_matmul_matches():
    """bf16 panels + f32 accumulator through the blocked Pallas program."""
    import jax
    import ml_dtypes
    rng = np.random.default_rng(0)
    a = rng.standard_normal((256, 256)).astype(ml_dtypes.bfloat16)
    b = rng.standard_normal((256, 256)).astype(ml_dtypes.bfloat16)
    c = rng.standard_normal((256, 256)).astype(np.float32)
    fn = pallas_gemm_tile(1.0, bm=128, bn=128, bk=128, interpret=True)
    got = np.asarray(jax.jit(fn)(a, b, c))
    ref = c + np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert _rel_err(got, ref) < 1e-3
    assert fn.selected == {(256, 256, 256): PALLAS}


def test_pallas_alpha_and_fallback():
    """Unaligned shapes (not multiples of 128) must take the fused-XLA
    fallback — Mosaic rejects such blocks — with alpha honored (TPU's
    default matmul precision is bf16, hence the tolerance)."""
    import jax
    rng = np.random.default_rng(1)
    for n in (100, 640 + 8):     # sub-block unaligned; super-block too
        a = rng.standard_normal((n, n)).astype(np.float32)
        fn = pallas_gemm_tile(2.0)
        got = np.asarray(jax.jit(fn)(a, a, a))
        ref = a + 2.0 * a @ a
        assert _rel_err(got, ref) < 5e-2
        # the fallback is visible to the caller, not silent
        assert fn.selected == {(n, n, n): XLA}
    # precision='highest' on the fallback forces f32 multiplies
    a = rng.standard_normal((100, 100)).astype(np.float32)
    got = np.asarray(jax.jit(
        pallas_gemm_tile(1.0, precision="highest"))(a, a, a))
    assert _rel_err(got, a + a @ a) < 1e-5


def test_gemm_taskpool_with_pallas_kernel():
    """The full runtime path with --mca gemm_pallas 1: every device GEMM
    task runs the hand-written kernel."""
    from parsec_tpu.apps import gemm as gemm_mod
    from parsec_tpu.apps.gemm import gemm_taskpool
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    rng = np.random.default_rng(2)
    n, mb = 256, 128
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, name="A").from_array(a)
    B = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, name="B").from_array(b)
    C = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, name="C").from_array(
        np.zeros((n, n), np.float32))
    params.set("gemm_pallas", 1)
    gemm_mod._kernels.clear()      # force kernel re-selection
    # the runtime path compiles the kernel for the attached device; on
    # this CPU the test plants the interpret-mode build under the key
    # gemm._tile_kernel looks up
    kern = gemm_mod._kernels[("pallas", 1.0, None)] = \
        pallas_gemm_tile(1.0, interpret=True)
    try:
        with Context(nb_cores=2) as ctx:
            if not ctx.device_registry.accelerators:
                pytest.skip("no accelerator attached")
            ctx.add_taskpool(gemm_taskpool(A, B, C, device="tpu"))
            ctx.wait(timeout=300)
        # the switch actually selected the Pallas kernel (a silently
        # broken param would still produce correct numerics via XLA)
        assert gemm_mod._tile_kernel(1.0) is kern
        assert kern.selected == {(mb, mb, mb): PALLAS}
    finally:
        params.unset("gemm_pallas")
        gemm_mod._kernels.clear()
    assert _rel_err(C.to_array(), a @ b) < 5e-2


def test_pallas_runtime_path_never_interprets():
    """Without ``interpret=True`` the kernels compile for the attached
    device or raise — on this CPU they raise, they do not quietly run
    in interpret mode."""
    import jax
    a = np.zeros((128, 128), np.float32)
    with pytest.raises(Exception, match="(?i)interpret|pallas|mosaic|tpu"):
        jax.block_until_ready(jax.jit(pallas_gemm_tile(1.0))(a, a, a))


def _bf16(rng, n, scale=1.0):
    import ml_dtypes
    return (scale * rng.standard_normal((n, n))).astype(ml_dtypes.bfloat16)


@pytest.mark.parametrize("app", ["potrf", "getrf"])
def test_update_kernel_matches_both_gemm_classes(app):
    """The factorizations' trailing update ``C - L R^T`` (potrf) and
    ``C - L U`` (getrf) as the in-place Pallas program, interpreted on a
    2 x 2 x 2 grid of 128 blocks, against the XLA form of the app's own
    GEMM kernel (what it runs off the chip), bf16 in and out: equal but
    for a rounding of the last bf16 place where the f32 sums differ in
    order.  The product's result takes C's buffer."""
    import importlib
    import jax
    from parsec_tpu.apps.pallas_kernels import pallas_update
    mod = importlib.import_module(f"parsec_tpu.apps.{app}")
    nt = app == "potrf"
    rng = np.random.default_rng(5)
    C, L, R = _bf16(rng, 256), _bf16(rng, 256, 0.1), _bf16(rng, 256, 0.1)

    def streamed(C, L, R):
        return pallas_update(C, L, R, nt, blocks=(128, 128, 128),
                             interpret=True)
    got = np.asarray(jax.jit(streamed)(C, L, R), np.float32)
    ref = np.asarray(jax.jit(mod._k_gemm(None))(C, L, R), np.float32)
    assert mod.selected[("GEMM", 256)] == XLA
    f32 = [np.asarray(x, np.float32) for x in (C, L, R)]
    exact = f32[0] - f32[1] @ (f32[2].T if nt else f32[2])
    # one bf16 rounding of the exact result, on either side
    assert np.abs(got - exact).max() <= 2 ** -7 * np.abs(exact).max()
    assert np.abs(got - ref).max() <= 2 ** -7 * np.abs(ref).max()
    assert np.mean(got == ref) > 0.95
    eqn, = jax.make_jaxpr(streamed)(C, L, R).eqns
    assert eqn.primitive.name == "pallas_call"
    assert eqn.params["input_output_aliases"] == ((2, 0),)


@pytest.mark.parametrize("app", ["potrf", "getrf"])
def test_update_form_by_shape(app, monkeypatch):
    """Which form each GEMM tile takes, traced as on the chip (the
    backend is made to read "tpu"; nothing runs): the streamed Pallas
    product for a bf16 6144 tile; XLA's for a tile under the smallest
    order, one that the blocks do not divide, f32 tiles and a stated
    precision — and off the chip whatever the shape.  ``selected`` says
    which."""
    import importlib
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps.pallas_kernels import (UPDATE_BLOCKS,
                                                UPDATE_MIN_ORDER)
    mod = importlib.import_module(f"parsec_tpu.apps.{app}")

    def form(n, dtype=jnp.bfloat16, precision=None):
        t = jax.ShapeDtypeStruct((n, n), dtype)
        # a fresh function each time: JAX keeps the trace of one
        out = jax.eval_shape(lambda *a: mod._k_gemm(precision)(*a), t, t, t)
        assert out.shape == (n, n) and out.dtype == dtype
        return mod.selected[("GEMM", n)]
    assert form(6144) == XLA                   # the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert form(6144) == PALLAS
    assert form(UPDATE_MIN_ORDER) == PALLAS
    assert form(2048) == XLA
    assert form(6144 + UPDATE_BLOCKS[0] // 2) == XLA
    assert form(6144, jnp.float32) == XLA
    assert form(6144, precision="highest") == XLA
