"""Driver app tests: Cholesky, QR, stencil, pingpong, redistribute
(reference: DPLASMA-style drivers named by BASELINE.json; tests/apps/)."""

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import (TwoDimBlockCyclic, TwoDimTabular,
                                    VectorTwoDimCyclic)


def _spd(n, rng):
    B = rng.standard_normal((n, n)).astype(np.float32)
    return (B @ B.T + n * np.eye(n)).astype(np.float32)


@pytest.mark.parametrize("device", ["tpu", "cpu"])
@pytest.mark.parametrize("nt", [1, 2, 5])
def test_potrf_matches_numpy(device, nt):
    from parsec_tpu.apps.potrf import potrf_taskpool
    mb = 16
    n = nt * mb
    rng = np.random.default_rng(0)
    spd = _spd(n, rng)
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n).from_array(spd.copy())
    with Context(nb_cores=4) as ctx:
        ctx.add_taskpool(potrf_taskpool(A, device=device))
        ctx.wait()
    L = np.tril(A.to_array())
    err = np.abs(L @ L.T - spd).max() / np.abs(spd).max()
    assert err < 1e-4


@pytest.mark.parametrize("device", ["tpu", "cpu"])
def test_potrf_bf16_panels_mixed_precision(device):
    """bf16-panel mixed precision (HPL-AI-style; the storage of the
    benchmark's potrf configurations): the kernels are dtype-following, so storing off-diagonal tiles bf16
    must still produce a valid factorization of a (slightly perturbed)
    matrix — loose tolerance reflects bf16 storage rounding."""
    from ml_dtypes import bfloat16
    from parsec_tpu.apps.potrf import potrf_taskpool
    mb, nt = 16, 4
    n = nt * mb
    rng = np.random.default_rng(3)
    spd = _spd(n, rng)
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, dtype=bfloat16)
    for m, nn in A.local_tiles():
        blk = spd[m * mb:(m + 1) * mb, nn * mb:(nn + 1) * mb]
        A.data_of(m, nn).overwrite_host(blk.astype(bfloat16))
    with Context(nb_cores=4) as ctx:
        ctx.add_taskpool(potrf_taskpool(A, device=device))
        ctx.wait()
    L = np.zeros((n, n), np.float32)
    for m, nn in A.local_tiles():
        if m < nn:
            continue
        L[m * mb:(m + 1) * mb, nn * mb:(nn + 1) * mb] = \
            np.asarray(A.data_of(m, nn).pull_to_host().payload,
                       dtype=np.float32)
    L = np.tril(L)
    err = np.abs(L @ L.T - spd).max() / np.abs(spd).max()
    assert err < 3e-2, err


@pytest.mark.parametrize("device", ["tpu", "cpu"])
@pytest.mark.parametrize("nt", [1, 2, 4])
def test_qr_matches_numpy(device, nt):
    from parsec_tpu.apps.qr import qr_taskpool
    mb = 8
    n = nt * mb
    rng = np.random.default_rng(1)
    a = rng.standard_normal((n, n)).astype(np.float32)
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n).from_array(a.copy())
    with Context(nb_cores=4) as ctx:
        ctx.add_taskpool(qr_taskpool(A, device=device))
        ctx.wait()
    out = A.to_array()
    assert np.abs(np.tril(out, -1)).max() < 1e-4     # R is upper-triangular
    R = np.triu(out)
    ata = a.T @ a
    assert np.abs(R.T @ R - ata).max() / np.abs(ata).max() < 1e-4


@pytest.mark.parametrize("device", ["tpu", "cpu"])
def test_stencil_matches_serial(device):
    from parsec_tpu.apps.stencil import stencil_reference, stencil_taskpool
    NT, mb, steps = 4, 8, 5
    rng = np.random.default_rng(2)
    x = rng.standard_normal(NT * mb).astype(np.float32)
    V = VectorTwoDimCyclic(mb=mb, lm=NT * mb).from_array(x.copy())
    with Context(nb_cores=4) as ctx:
        ctx.add_taskpool(stencil_taskpool(V, steps, device=device))
        ctx.wait()
    want = stencil_reference(x, steps)
    np.testing.assert_allclose(V.to_array(), want, rtol=1e-4, atol=1e-5)


def test_stencil_fused_sweeps_match_reference():
    """VERDICT r4 #4: S-deep-halo sweep fusion — fused blocks (with a
    ragged remainder) produce the same values as the per-sweep pipeline
    and the serial reference."""
    from parsec_tpu.apps.stencil import stencil_reference, stencil_taskpool
    NT, mb, steps, fuse = 4, 8, 11, 4      # remainder block of 3
    rng = np.random.default_rng(2)
    x = rng.standard_normal(NT * mb).astype(np.float32)
    V = VectorTwoDimCyclic(mb=mb, lm=NT * mb).from_array(x.copy())
    with Context(nb_cores=4) as ctx:
        tp = stencil_taskpool(V, steps, device="cpu", fuse=fuse)
        # ceil(11/4)=3 blocks of NT tasks + NT INIT tasks
        ctx.add_taskpool(tp)
        ctx.wait()
    want = stencil_reference(x, steps)
    np.testing.assert_allclose(V.to_array(), want, rtol=1e-4, atol=1e-5)
    # fuse deeper than the tile is rejected (halo correctness bound)
    with pytest.raises(ValueError):
        stencil_taskpool(V, steps, fuse=mb + 1)


def test_pingpong_single_process():
    from parsec_tpu.apps.pingpong import run_pingpong
    with Context(nb_cores=2) as ctx:
        per_hop, mbps = run_pingpong(ctx, nbytes=1024, hops=50)
    assert per_hop > 0 and mbps > 0


def test_redistribute_between_distributions():
    from parsec_tpu.apps.redistribute import redistribute_taskpool
    mt = nt = 3
    mb = 8
    rng = np.random.default_rng(3)
    S = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mt * mb, ln=nt * mb, name="S")
    # target: tabular distribution with a scrambled (single-rank) table
    table = [0] * (mt * nt)
    T = TwoDimTabular(mb=mb, nb=mb, lm=mt * mb, ln=nt * mb, table=table,
                      name="T")
    for m, n in S.local_tiles():
        S.data_of(m, n).copy_on(0).payload[:] = \
            rng.standard_normal((mb, mb)).astype(np.float32)
    with Context(nb_cores=2) as ctx:
        ctx.add_taskpool(redistribute_taskpool(S, T))
        ctx.wait()
    np.testing.assert_allclose(T.to_array(), S.to_array(), rtol=1e-6)


def test_geqrt_choleskyqr2_orthogonal_at_cond_1e3():
    """ADVICE medium: tiles with cond in ~1e2..3e3 pass the finite-chol
    check but single-pass Cholesky-QR loses orthogonality as cond^2*eps
    (~0.1 at cond 1e3 in f32).  The CholeskyQR2 reorthogonalization pass
    in the GEQRT fast branch must hold eps-level orthogonality there."""
    import jax.numpy as jnp
    from parsec_tpu.apps.qr import _mk_geqrt
    mb = 32
    rng = np.random.default_rng(5)
    u, _ = np.linalg.qr(rng.standard_normal((mb, mb)))
    v, _ = np.linalg.qr(rng.standard_normal((mb, mb)))
    s = np.logspace(0, -3, mb)                   # cond(T) = 1e3
    T = ((u * s) @ v.T).astype(np.float32)
    out = _mk_geqrt()(jnp.asarray(T), jnp.zeros((mb, mb), jnp.float32))
    R = np.asarray(out["T"], dtype=np.float64)
    Q = np.asarray(out["Q"], dtype=np.float64)
    orth = np.abs(Q.T @ Q - np.eye(mb)).max()
    assert orth < 5e-5, orth                     # 1 pass gives ~1e-1 here
    recon = np.abs(Q @ R - T).max() / np.abs(T).max()
    assert recon < 1e-5, recon


def test_qr_inner_blocked_matches_numpy():
    """r6 tentpole: the inner-blocked (ib) panel construction — HIGHEST
    work O(mb^2*ib) per panel — must produce the same factorization
    contract as the unblocked path (R upper-triangular, R^T R = A^T A)
    through the full driver."""
    from parsec_tpu.apps.qr import qr_taskpool
    from parsec_tpu.utils.mca import params
    mb, nt = 16, 3
    n = nt * mb
    rng = np.random.default_rng(11)
    a = rng.standard_normal((n, n)).astype(np.float32)
    params.set("qr_ib", 4)
    try:
        A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n).from_array(a.copy())
        with Context(nb_cores=4) as ctx:
            ctx.add_taskpool(qr_taskpool(A, device="tpu"))
            ctx.wait()
    finally:
        params.unset("qr_ib")
    out = A.to_array()
    assert np.abs(np.tril(out, -1)).max() < 1e-4
    R = np.triu(out)
    ata = a.T @ a
    assert np.abs(R.T @ R - ata).max() / np.abs(ata).max() < 1e-4


def test_geqrt_blocked_orthogonal():
    """Blocked GEQRT (BCGS2-flavored CholeskyQR2 per ib-block with one
    HIGHEST re-projection pass): eps-class orthogonality and exact
    reconstruction at moderate condition."""
    import jax.numpy as jnp
    from parsec_tpu.apps.qr import _mk_geqrt
    mb, ib = 64, 16
    rng = np.random.default_rng(5)
    u, _ = np.linalg.qr(rng.standard_normal((mb, mb)))
    v, _ = np.linalg.qr(rng.standard_normal((mb, mb)))
    s = np.logspace(0, -3, mb)                   # cond(T) = 1e3
    T = ((u * s) @ v.T).astype(np.float32)
    out = _mk_geqrt(ib)(jnp.asarray(T), jnp.zeros((mb, mb), jnp.float32))
    R = np.asarray(out["T"], dtype=np.float64)
    Q = np.asarray(out["Q"], dtype=np.float64)
    assert np.abs(Q.T @ Q - np.eye(mb)).max() < 5e-5
    assert np.abs(Q @ R - T).max() / np.abs(T).max() < 1e-5
    assert np.abs(np.tril(R, -1)).max() == 0.0


def _dense_qt(pair, mb):
    """Q^T of a TSQRT edge ``[V; T^T strip]`` as a dense 2 mb x 2 mb
    matrix: the product of its groups' reflectors
    I - [E_G; V_G] T_G^T [E_G; V_G]^T, group 0 applied first."""
    W = pair.shape[0] - mb
    V, strip = pair[:mb], pair[mb:]
    Qt = np.eye(2 * mb)
    for lo in range(0, mb, W):
        Wg = np.vstack([np.eye(mb)[:, lo:lo + W], V[:, lo:lo + W]])
        Qt = (np.eye(2 * mb) - Wg @ strip[:, lo:lo + W] @ Wg.T) @ Qt
    return Qt


@pytest.mark.parametrize("mb, ib, W", [
    (32, 8, 32),        # one group: the panel-wide factor
    (32, 8, 16),        # two groups
    (32, 8, 8),         # mb / ib groups: no accumulation at all
    (48, 8, 16),        # three groups of two blocks
    (32, 0, 32),        # unblocked: one group by construction
])
def test_tsqrt_edge_then_tsmqr_is_the_dense_qt_of_the_panel(mb, ib, W):
    """TSQRT's edge — V over the W x mb strip of T^T's diagonal blocks,
    accumulated inside column groups of W alone — is an ORTHOGONAL
    transform that annihilates B and reproduces R', and TSMQR's group
    loop applies that same transform to [C1; C2]."""
    import jax.numpy as jnp
    from parsec_tpu.apps import qr
    rng = np.random.default_rng(7)
    Rin = np.triu(rng.standard_normal((mb, mb))).astype(np.float32) \
        + 3 * np.eye(mb, dtype=np.float32)
    B = rng.standard_normal((mb, mb)).astype(np.float32)
    qr.selected.clear()
    out = qr._mk_tsqrt(ib)(jnp.asarray(Rin), jnp.asarray(B),
                           jnp.zeros((mb + W, mb), jnp.float32))
    assert out["Q"].shape == (mb + W, mb)
    assert qr.selected == ({("TSQRT", mb, ib): W} if ib else {})
    Rp = np.asarray(out["T"], np.float64)
    Qt = _dense_qt(np.asarray(out["Q"], np.float64), mb)
    stacked = np.vstack([Rin, B]).astype(np.float64)
    applied = Qt @ stacked
    assert np.abs(applied[:mb] - Rp).max() / np.abs(Rp).max() < 1e-5
    assert np.abs(applied[mb:]).max() < 1e-4       # B annihilated
    assert np.abs(Qt @ Qt.T - np.eye(2 * mb)).max() < 1e-5
    # [R; B] = Q [R'; 0]
    assert np.abs(Qt.T[:, :mb] @ Rp - stacked).max() < 1e-4
    assert np.abs(np.asarray(out["B"])).max() == 0.0
    C = rng.standard_normal((2 * mb, mb)).astype(np.float32)
    got = qr._mk_tsmqr()(out["Q"], jnp.asarray(C[:mb]), jnp.asarray(C[mb:]))
    want = Qt @ C
    assert np.abs(np.asarray(got["C1"]) - want[:mb]).max() < 1e-4
    assert np.abs(np.asarray(got["C2"]) - want[mb:]).max() < 1e-4


def test_tsqrt_refuses_an_edge_that_groups_no_block():
    """The group width is read off the Q edge: one that is no multiple
    of ib, does not divide mb, or is not (2 mb, mb) on an unblocked
    panel is a build error, not a wrong factor."""
    import jax.numpy as jnp
    from parsec_tpu.apps import qr
    t = jnp.eye(32, dtype=jnp.float32)
    for ib, rows in ((8, 32 + 12), (8, 32 + 24), (0, 32 + 16)):
        with pytest.raises(ValueError, match="TSQRT"):
            qr._mk_tsqrt(ib)(t, t, jnp.zeros((rows, 32), jnp.float32))
