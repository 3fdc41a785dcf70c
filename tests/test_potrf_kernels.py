"""The Cholesky panel kernels use the triangle (apps/potrf.py, PR 28):
SYRK and TRSM as block-triangular products, POTRF reading the lower
triangle alone.  CPU backend; the full products are the reference.

The rule (``tri_blocks``) is tested at the tile orders the chip runs.
The kernels are tested at small tile orders with the rule's smallest
block edge cut to ``EDGE`` for the test, so that both forms are driven
through the real factories; each case jits a wrapper of its own, so no
trace made under the test's edge is found by another test."""

import functools

import numpy as np
import pytest

EDGE = 2
#: tile order -> b with the smallest block edge at EDGE: the edge too
#: small; the blocked form at its smallest edge; a tile order the blocks
#: do not divide; a larger one they do; an odd one
ORDERS = {8: 1, 16: 8, 36: 1, 64: 8, 35: 1}


@pytest.fixture
def small_edge(monkeypatch):
    from parsec_tpu.apps import potrf
    monkeypatch.setattr(potrf, "_TRI_EDGE_MIN", {"SYRK": EDGE, "TRSM": EDGE})
    return potrf


@pytest.mark.parametrize("mb,syrk,trsm", [
    (6144, 8, 8), (12288, 8, 8), (2048, 8, 1), (4096, 8, 1), (1024, 1, 1),
    (16, 1, 1), (6148, 1, 1)])
def test_tri_blocks_rule(mb, syrk, trsm):
    """b is a function of the class and the tile order alone: both
    blocked at the headline tile order, SYRK alone at cell 3's (the
    8-wide TRSM wave ran no faster blocked there), the full product
    where the blocks do not divide the tile order."""
    from parsec_tpu.apps import potrf
    assert potrf.tri_blocks("SYRK", mb) == syrk
    assert potrf.tri_blocks("TRSM", mb) == trsm
    for cls, b in (("SYRK", syrk), ("TRSM", trsm)):
        assert potrf.potrf_executed_flops(cls, mb) == \
            pytest.approx(2.0 * mb ** 3 * (b + 1) / (2 * b))
    assert potrf.potrf_executed_flops("GEMM", mb) == 2.0 * mb ** 3


def _operands(mb, dtype, seed):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    T, R = (jnp.asarray(rng.standard_normal((mb, mb)), jnp.float32)
            .astype(dtype) for _ in range(2))
    # what tri_inv hands TRSM: float32, exact zeros above the diagonal
    W = jnp.asarray(np.tril(rng.standard_normal((mb, mb))), jnp.float32)
    return T, R, W


@pytest.mark.parametrize("mb", sorted(ORDERS))
@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
@pytest.mark.parametrize("cls", ["SYRK", "TRSM"])
def test_blocked_update_equals_full_product(small_edge, cls, storage, mb):
    import jax
    import jax.numpy as jnp
    potrf = small_edge
    dtype = jnp.dtype(storage)
    T, R, W = _operands(mb, dtype, seed=mb)
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    potrf.selected.pop((cls, mb), None)
    if cls == "SYRK":
        fn = potrf._k_syrk(hi)
        got = jax.jit(lambda t, r: fn(t, r))(T, R)
        full = (T.astype(f32) - jnp.matmul(
            R, R.T, precision=hi, preferred_element_type=f32)).astype(dtype)
        # the lower triangle is all anybody reads of a diagonal tile
        got, full = (np.tril(np.asarray(x.astype(f32))) for x in (got, full))
    else:
        fn = potrf._k_trsm(hi)
        got = jax.jit(lambda w, c: fn(w, c))(W, T)
        full = jnp.matmul(T, W.T, precision=hi,
                          preferred_element_type=f32).astype(dtype)
        got, full = (np.asarray(x.astype(f32)) for x in (got, full))
    assert potrf.selected[(cls, mb)] == ORDERS[mb]
    # the same products in float32; bf16 storage may round a sum made in
    # another order to the neighbouring value
    tol = 1e-6 if storage == "float32" else 2.0 ** -7
    assert np.abs(got - full).max() <= tol * np.abs(full).max()


def _spd_tile(mb, seed):
    rng = np.random.default_rng(seed)
    B = rng.standard_normal((mb, mb)).astype(np.float32)
    return (B @ B.T + mb * np.eye(mb)).astype(np.float32)


@pytest.mark.parametrize("cls", ["POTRF", "POTRFL"])
def test_potrf_reads_the_lower_triangle_alone(cls):
    """dpotrf_L: NaN in the strict upper triangle of the tile changes
    neither the factor nor the inverse."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps import potrf
    mb = 48
    sym = _spd_tile(mb, 3)
    low = np.where(np.tri(mb, dtype=bool), sym, np.nan).astype(np.float32)
    W0 = jnp.zeros((mb, mb), jnp.float32)

    def run(tile):
        if cls == "POTRF":
            out = jax.jit(potrf._k_potrf(None))(jnp.asarray(tile), W0)
            return np.asarray(out["T"]), np.asarray(out["W"])
        return (np.asarray(jax.jit(potrf._k_potrf_last(None))(
            jnp.asarray(tile))),)

    for got, want in zip(run(low), run(sym)):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)
    L = run(low)[0]
    assert np.abs(L @ L.T - sym).max() < 1e-4 * np.abs(sym).max()


#: tile orders no other test factors, so the programs traced under the
#: test's edge are found by nobody else: the full and the blocked form
@pytest.mark.parametrize("mb,b", [(12, 1), (24, 8), (40, 8)])
def test_taskpool_with_nan_above_the_diagonal(small_edge, mb, b):
    """The whole factorization on the device path: diagonal tiles that
    arrive with NaN above the diagonal end as close to A as symmetric
    ones do — no kernel reads the upper triangle of a diagonal tile."""
    from parsec_tpu.apps.potrf import potrf_taskpool
    from parsec_tpu.apps.potrf_check import backward_error
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.utils.mca import params
    potrf = small_edge
    nt = 4
    n = nt * mb
    spd = _spd_tile(n, mb)

    def factor(nan_upper):
        A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, dtype=np.float32)
        for m, k in A.local_tiles():
            blk = spd[m * mb:(m + 1) * mb, k * mb:(k + 1) * mb].copy()
            if nan_upper and m == k:
                blk[np.triu_indices(mb, 1)] = np.nan
            A.data_of(m, k).overwrite_host(blk)
        params.set("device_max", 1)
        try:
            with Context(nb_cores=4) as ctx:
                ctx.add_taskpool(potrf_taskpool(A, device="tpu"))
                ctx.wait()
                (dev,) = ctx.device_registry.accelerators
                assert dev.stats.executed_tasks + dev.stats.held_tasks == \
                    nt * (nt + 1) * (nt + 2) // 6
        finally:
            params.unset("device_max")
        return backward_error(
            A, lambda m, k: spd[m * mb:(m + 1) * mb, k * mb:(k + 1) * mb])

    clean = factor(False)
    assert potrf.selected[("SYRK", mb)] == b
    assert potrf.selected[("TRSM", mb)] == b
    assert clean < 1e-5
    assert factor(True) <= 1.5 * clean


#: order -> the leaf tri_inv inverts by Newton: the order halved while
#: it is even and wider than the MXU's 128; 96 at 3 x 2^k (384, 768, and
#: the 6144 tile)
INV_LEAVES = {96: 96, 128: 128, 384: 96, 512: 128, 768: 96, 2048: 128}


@functools.lru_cache(maxsize=None)
def _cholesky_factor(n, cond):
    """float32 lower factor L of Q diag(lam) Q^T, lam from 1 to cond^-2:
    cond(L) = cond."""
    rng = np.random.default_rng(n)
    Q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    A = (Q * np.geomspace(1.0, cond ** -2.0, n)) @ Q.T
    return np.linalg.cholesky((A + A.T) / 2).astype(np.float32)


@pytest.mark.parametrize("cond", [2.0, 1e3])
@pytest.mark.parametrize("precision", ["DEFAULT", "HIGHEST"])
@pytest.mark.parametrize("n", sorted(INV_LEAVES))
def test_tri_inv_batched_leaves(n, precision, cond):
    """The inverse from a batch of leaves: L X = I and X = inv(L) to
    float32 working accuracy (the CPU runs every product in float32
    whatever the precision asked), exact zeros above the diagonal, and
    the leaf recorded for the order."""
    import jax
    from parsec_tpu.apps import potrf
    L = _cholesky_factor(n, cond)
    potrf.selected.pop(("INV", n), None)
    prec = getattr(jax.lax.Precision, precision)
    X = np.asarray(jax.jit(lambda a: potrf.tri_inv(a, prec))(L))
    assert potrf.selected[("INV", n)] == potrf.inv_leaf(n) == INV_LEAVES[n]
    assert X.dtype == np.float32
    assert not np.triu(X, 1).any()
    L64, X64 = L.astype(np.float64), X.astype(np.float64)
    tol = 4 * np.finfo(np.float32).eps * max(1.0, cond / 100)
    assert np.abs(L64 @ X64 - np.eye(n)).max() <= tol
    ref = np.linalg.inv(L64)
    assert np.abs(X64 - ref).max() <= tol * np.abs(ref).max()
