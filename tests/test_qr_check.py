"""mp-QR accuracy ladder tests (apps/qr_check.py — VERDICT r5 #9): the
CSNE LS-refinement must recover f32-class accuracy from low-precision
storage factors, mirroring potrf's HPL-AI refine_solve story."""

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TwoDimBlockCyclic


def _factor(a, dtype):
    from parsec_tpu.apps.qr import qr_taskpool
    n = a.shape[0]
    mb = n // 4
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, dtype=dtype)
    for m, nn in A.local_tiles():
        A.data_of(m, nn).overwrite_host(
            a[m * mb:(m + 1) * mb, nn * mb:(nn + 1) * mb].astype(dtype))
    with Context(nb_cores=4) as ctx:
        ctx.add_taskpool(qr_taskpool(A, device="tpu"))
        ctx.wait()
    return A, mb


def test_ls_refine_f32_reaches_f32_class():
    import jax.numpy as jnp
    from parsec_tpu.apps.qr_check import ls_refine
    rng = np.random.default_rng(0)
    n = 64
    a = (0.1 * rng.standard_normal((n, n)) + np.eye(n)).astype(np.float32)
    A, mb = _factor(a, np.float32)
    orig = lambda i, j: jnp.asarray(
        a[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb])
    hist = ls_refine(A, orig, steps=3)
    assert hist[0] < 1e-2               # direct CSNE already decent
    assert min(hist) <= 1e-6            # ladder reaches f32-class
    assert hist[-1] <= hist[0]


def test_ls_refine_recovers_from_bf16_storage():
    """The HPL-AI contract for QR: bf16-storage factor, f32-class
    solution accuracy after a few refinement steps."""
    import ml_dtypes
    import jax.numpy as jnp
    from parsec_tpu.apps.qr_check import ls_refine
    rng = np.random.default_rng(1)
    n = 64
    a32 = (0.05 * rng.standard_normal((n, n)) + np.eye(n)) \
        .astype(np.float32)
    A, mb = _factor(a32, ml_dtypes.bfloat16)
    # the factor factored the bf16-ROUNDED operand; refine against it
    ar = a32.astype(ml_dtypes.bfloat16).astype(np.float32)
    orig = lambda i, j: jnp.asarray(
        ar[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb])
    hist = ls_refine(A, orig, steps=4)
    assert hist[0] > 1e-4               # bf16 factor alone is NOT f32
    assert min(hist) <= 1e-6            # ladder recovers f32-class


@pytest.mark.parametrize("storage,lo,hi", [("float32", 0.0, 1e-5),
                                           ("bfloat16", 1e-5, 2e-2)])
def test_factorization_residual_tracks_the_factor(storage, lo, hi):
    """R^T R = A^T A on a random probe (what chip_smoke.py holds the
    geqrf factor to): f32 storage sits at f32 class, bf16
    storage at bf16 class, and a damaged R tile is seen."""
    import ml_dtypes
    import jax.numpy as jnp
    from parsec_tpu.apps.qr_check import factorization_residual
    dtype = np.float32 if storage == "float32" else ml_dtypes.bfloat16
    rng = np.random.default_rng(5)
    n = 64
    a = (0.05 * rng.standard_normal((n, n)) + np.eye(n)).astype(np.float32)
    A, mb = _factor(a, dtype)
    ar = a.astype(dtype).astype(np.float32)      # what was factored
    orig = lambda i, j: jnp.asarray(
        ar[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb])
    res = factorization_residual(A, orig)
    assert lo <= res < hi
    # repeatable: the probe vector is seeded
    assert factorization_residual(A, orig) == res
    d = A.data_of(0, 1)
    d.overwrite_host(2.0 * np.asarray(d.pull_to_host().payload))
    assert factorization_residual(A, orig) > 10 * max(res, 1e-3)
