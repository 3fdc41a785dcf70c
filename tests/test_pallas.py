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


def test_pallas_gram_matches():
    """Blocked Gram kernel (the inner-blocked QR panel's HIGHEST hot
    spot): X^T X with f32 VMEM accumulation over the K-innermost grid."""
    import jax
    rng = np.random.default_rng(2)
    X = rng.standard_normal((512, 256)).astype(np.float32)
    from parsec_tpu.apps.pallas_kernels import pallas_gram_tile
    fn = pallas_gram_tile(bn=128, bk=128, interpret=True)
    got = np.asarray(jax.jit(fn)(X))
    ref = X.T @ X
    assert _rel_err(got, ref) < 1e-4
    assert fn.selected == {(512, 256): PALLAS}


def test_pallas_runtime_path_never_interprets():
    """Without ``interpret=True`` the kernels compile for the attached
    device or raise — on this CPU they raise, they do not quietly run
    in interpret mode — and the Gram kernel turns bf16 input away with
    a TypeError instead of a Mosaic failure at the first launch."""
    import jax
    import ml_dtypes
    from parsec_tpu.apps.pallas_kernels import pallas_gram_tile
    a = np.zeros((128, 128), np.float32)
    with pytest.raises(Exception, match="(?i)interpret|pallas|mosaic|tpu"):
        jax.block_until_ready(jax.jit(pallas_gemm_tile(1.0))(a, a, a))
    with pytest.raises(TypeError, match="float32"):
        jax.jit(pallas_gram_tile(interpret=True))(
            np.zeros((256, 128), ml_dtypes.bfloat16))


def test_pallas_gram_unaligned_fallback():
    import jax
    rng = np.random.default_rng(3)
    X = rng.standard_normal((100, 36)).astype(np.float32)
    from parsec_tpu.apps.pallas_kernels import pallas_gram_tile
    fn = pallas_gram_tile()
    got = np.asarray(jax.jit(fn)(X))
    assert _rel_err(got, X.T @ X) < 1e-4
    assert fn.selected == {(100, 36): XLA}


def test_blocked_geqrt_with_pallas_gram():
    """The blocked panel's Gram products routed through the Pallas
    kernel (what the qr_pallas_gram MCA knob selects in qr_taskpool);
    the factorization contract is unchanged."""
    import jax.numpy as jnp
    from parsec_tpu.apps.pallas_kernels import pallas_gram_tile
    from parsec_tpu.apps.qr import _mk_geqrt
    mb, ib = 256, 128
    rng = np.random.default_rng(4)
    T = rng.standard_normal((mb, mb)).astype(np.float32)
    gram = pallas_gram_tile(interpret=True)
    out = _mk_geqrt(ib, gram=gram)(
        jnp.asarray(T), jnp.zeros((mb, mb), jnp.float32))
    assert set(gram.selected.values()) == {PALLAS}
    R = np.asarray(out["T"], np.float64)
    Q = np.asarray(out["Q"], np.float64)
    assert np.abs(Q.T @ Q - np.eye(mb)).max() < 5e-5
    assert np.abs(Q @ R - T).max() / np.abs(T).max() < 1e-5
