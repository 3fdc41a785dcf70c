"""Pallas TPU kernels for the hot tile operations.

The runtime's device bodies are ordinarily single fused XLA ops (jnp
matmul & friends) — XLA already schedules those onto the MXU well.  This
module provides hand-written Pallas alternatives for the hottest tile
op, the GEMM accumulate step, demonstrating the kernel seam the
reference fills with cuBLAS/user CUDA kernels (reference: the BODY
[type=CUDA] incarnations; SURVEY §7 "tile kernels as Pallas/XLA
computations"):

- a blocked ``Ci + alpha * Ai @ Bi`` with a VMEM f32 accumulator and a
  K-innermost grid, bf16/f32 inputs straight onto the MXU;
- selection via ``--mca gemm_pallas 1`` (apps/gemm.py consults it), or
  call :func:`pallas_gemm_tile` directly as a PTG/DTD device body.

Interpret mode is the CALLER's choice (``interpret=True`` — the CPU
tests pass it); the runtime path never sets it, so on the runtime path a
kernel compiles for the attached device or raises.  Shapes that do not
tile evenly take the fused-XLA path, and each factory's ``selected`` map
says which kernel every traced shape got.

Measured (v5e, 4096-tile GEMM through the runtime): the Pallas blocked
kernel sustains ~36 TFLOP/s vs ~48 for the fused XLA matmul — XLA's MXU
pipeline wins for plain GEMM, so it stays the default; the Pallas path
is the seam for ops XLA does NOT fuse well (custom epilogues, quantized
accumulation), selected per-kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

from parsec_tpu.utils.mca import params

params.register("gemm_pallas", 0,
                "use the hand-written Pallas GEMM tile kernel instead of "
                "the fused XLA matmul")


#: the names ``selected`` maps a traced shape to
PALLAS, XLA = "pallas", "xla"


@functools.lru_cache(maxsize=None)
def _blocked_matmul(alpha: float, bm: int, bn: int, bk: int,
                    interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]

    def kernel(a_ref, b_ref, c_ref, o_ref, acc_ref):
        k = pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(k == 0)
        def _init():
            acc_ref[:, :] = c_ref[:, :].astype(jnp.float32)

        prod = jax.lax.dot_general(
            a_ref[:, :], b_ref[:, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:, :] += prod if alpha == 1.0 else alpha * prod

        @pl.when(k == nk - 1)
        def _fin():
            o_ref[:, :] = acc_ref[:, :].astype(o_ref.dtype)

    def run(Ai, Bi, Ci):
        m, kk = Ai.shape
        _, n = Bi.shape
        grid = (m // bm, n // bn, kk // bk)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct(Ci.shape, Ci.dtype),
            scratch_shapes=scratch,
            interpret=interpret,
        )(Ai, Bi, Ci)

    return run


def pallas_gemm_tile(alpha: float = 1.0, bm: int = 512, bn: int = 512,
                     bk: int = 512, precision=None,
                     interpret: bool = False):
    """A device-body kernel ``fn(Ai, Bi, Ci) -> Ci + alpha*Ai@Bi`` run as
    a blocked Pallas program (f32 VMEM accumulator, K-innermost grid).

    The Pallas path requires MXU-aligned shapes: every dimension must be
    a multiple of 128 AND divide by the (clamped) block sizes — Mosaic
    rejects unaligned blocks at compile time.  Anything else takes the
    fused XLA matmul with the same semantics (``precision`` honored
    there exactly as in the default kernel); ``fn.selected`` maps each
    traced ``(m, n, k)`` to ``"pallas"`` or ``"xla"`` so the caller can
    see which one it got."""

    def fn(Ai, Bi, Ci):
        import jax.numpy as jnp
        m, kk = Ai.shape
        _, n = Bi.shape
        cbm, cbn, cbk = min(bm, m), min(bn, n), min(bk, kk)
        aligned = all(d % 128 == 0 for d in (m, n, kk))
        if not aligned or m % cbm or n % cbn or kk % cbk:
            fn.selected[(m, n, kk)] = XLA
            acc = jnp.matmul(Ai, Bi, precision=precision,
                             preferred_element_type=Ci.dtype)
            return Ci + (acc if alpha == 1.0 else alpha * acc)
        fn.selected[(m, n, kk)] = PALLAS
        return _blocked_matmul(alpha, cbm, cbn, cbk, interpret)(
            Ai, Bi, Ci)

    fn.__name__ = f"pallas_gemm_a{alpha}"
    fn.selected = {}
    return fn


def use_pallas_gemm() -> bool:
    try:
        return bool(int(params.get("gemm_pallas", 0)))
    except (TypeError, ValueError):
        return False


# ---------------------------------------------------------------------------
# blocked Gram kernel: the HIGHEST-precision hot spot of the
# inner-blocked QR panels (apps/qr.py _cholqr2 — G = X^T X of an
# mb x ib column block, computed per ib-block of every GEQRT/TSQRT)
# ---------------------------------------------------------------------------

params.register("qr_pallas_gram", 0,
                "use the hand-written Pallas blocked Gram kernel for "
                "the inner-blocked QR panel construction (apps/qr.py) "
                "instead of the fused XLA matmul")


@functools.lru_cache(maxsize=None)
def _blocked_gram(bn: int, bk: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    scratch = [pltpu.VMEM((bn, bn), jnp.float32)]

    def kernel(xi_ref, xj_ref, o_ref, acc_ref):
        k = pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(k == 0)
        def _init():
            acc_ref[:, :] = jnp.zeros_like(acc_ref)

        # X_i^T X_j with f32 accumulation; HIGHEST so the Gram matrix —
        # the cond^2-sensitive input of the panel Cholesky — never rides
        # the MXU's bf16 passes (apps/qr.py precision discipline)
        acc_ref[:, :] += jax.lax.dot_general(
            xi_ref[:, :], xj_ref[:, :], (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _fin():
            o_ref[:, :] = acc_ref[:, :].astype(o_ref.dtype)

    def run(X):
        m, n = X.shape
        grid = (n // bn, n // bn, m // bk)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, i)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
            scratch_shapes=scratch,
            interpret=interpret,
        )(X, X)

    return run


def pallas_gram_tile(bn: int = 256, bk: int = 512,
                     interpret: bool = False):
    """``fn(X) -> X^T X`` (f32, HIGHEST) as a blocked Pallas program:
    K-innermost grid over X's rows with an f32 VMEM accumulator, the
    same shape discipline (and ``selected`` map, keyed by ``(m, n)``)
    as :func:`pallas_gemm_tile`.

    f32 input only: Mosaic refuses the HIGHEST-precision product of
    bf16 operands ("Bad lhs type" on ``tpu.matmul`` with
    ``contract_precision<fp32>``, compiled for a v5e), and the QR panel
    construction this kernel serves runs in f32 anyway (apps/qr.py
    upcasts the tile first) — so any other dtype is a TypeError here,
    not a Mosaic failure at the first launch."""

    def fn(X):
        import jax
        import jax.numpy as jnp
        if X.dtype != jnp.float32:
            raise TypeError(
                f"pallas_gram_tile takes float32 input, got {X.dtype}: "
                "Mosaic refuses the HIGHEST-precision tpu.matmul of "
                "narrower operands (cast at the call)")
        m, n = X.shape
        cbn, cbk = min(bn, n), min(bk, m)
        aligned = m % 128 == 0 and n % 128 == 0
        if not aligned or m % cbk or n % cbn:
            fn.selected[(m, n)] = XLA
            return jnp.matmul(X.T, X,
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
        fn.selected[(m, n)] = PALLAS
        return _blocked_gram(cbn, cbk, interpret)(X)

    fn.selected = {}
    return fn


def use_pallas_qr_gram() -> bool:
    try:
        return bool(int(params.get("qr_pallas_gram", 0)))
    except (TypeError, ValueError):
        return False


# ---------------------------------------------------------------------------
# the 1-D stencil's sweep, in place
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _inplace_sweep(bm: int, bn: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(hl_ref, c_ref, hr_ref, o_ref, top_ref, bot_ref, x_ref, p_ref):
        # step i holds block i (c_ref, just fetched) and block i-1
        # (x_ref, kept from the step before) and writes the NEW block
        # i-1: its last row needs the OLD first row of block i, which is
        # why the output runs one block behind the input.  Block i-1 is
        # written back after every read of blocks <= i+1 was issued, so
        # the output may take the input's buffer.
        i = pl.program_id(1)
        n = pl.num_programs(1)          # the tile's row blocks + 1

        @pl.when(i > 0)
        def _compute():
            x = x_ref[...]
            below = jnp.where(i == n - 1, hr_ref[...], c_ref[0:1, :])
            above = jnp.where(i == 1, hl_ref[...], p_ref[...])
            r = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            up = jnp.where(r == 0, above, pltpu.roll(x, 1, 0))
            dn = jnp.where(r == bm - 1, below, pltpu.roll(x, bm - 1, 0))
            new = (up + dn + x) / 3.0
            o_ref[...] = new
            p_ref[...] = x[bm - 1:bm, :]     # the old row above block i

            @pl.when(i == 1)
            def _top():
                top_ref[...] = new[0:1, :]

            @pl.when(i == n - 1)
            def _bot():
                bot_ref[...] = new[bm - 1:bm, :]

        x_ref[...] = c_ref[...]

    def run(HL, C, HR):
        mb, nb = C.shape
        nblk = mb // bm
        halo = pl.BlockSpec((1, bn), lambda j, i: (0, j))
        return pl.pallas_call(
            kernel,
            grid=(nb // bn, nblk + 1),
            in_specs=[halo,
                      pl.BlockSpec((bm, bn), lambda j, i:
                                   (jnp.minimum(i, nblk - 1), j)),
                      halo],
            out_specs=[pl.BlockSpec((bm, bn), lambda j, i:
                                    (jnp.maximum(i - 1, 0), j)),
                       halo, halo],
            out_shape=[jax.ShapeDtypeStruct(C.shape, C.dtype),
                       jax.ShapeDtypeStruct(HL.shape, C.dtype),
                       jax.ShapeDtypeStruct(HL.shape, C.dtype)],
            scratch_shapes=[pltpu.VMEM((bm, bn), C.dtype),
                            pltpu.VMEM((1, bn), C.dtype)],
            input_output_aliases={1: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=32 * 2 ** 20),
            interpret=interpret,
        )(HL, C, HR)

    return run


def pallas_sweep_tile(xla, bm: int = 256, bn: int = 2048,
                      interpret: bool = False):
    """``fn(HL, C, HR) -> (new C, its top row, its bottom row)``: one
    sweep of the 3-point periodic mean along the rows of a float32 tile
    ``C`` (mb rows x nb lanes) between its neighbours' boundary rows
    ``HL`` (above) and ``HR`` (below), each (1, nb) — IN PLACE: the new
    tile is written into ``C``'s buffer (``input_output_aliases``), so a
    caller that donates ``C`` moves every point once in and once out and
    allocates nothing.  (XLA cannot: a fusion that reads row r - 1 and
    r + 1 may not share its operand's buffer, so a donated ``C`` costs a
    copy of the tile and an undonated one a second grid.  Measured on a
    v5e, 48 tiles of 4096 x 8192 in waves of eight: this kernel 19.5 ms
    a sweep, 80 % of HBM's 819 GB/s, at 6.0 GiB; the XLA form 56 ms
    donated and 37 ms undonated at 15.6 GiB; PERF.md, PR 37.)

    Row blocks of ``bm`` run in order down each block of ``bn`` lanes,
    the output one block behind the input.  A tile that is not float32
    or does not tile evenly takes ``xla(HL, C, HR)``, which returns the
    same triple; ``selected`` says which, keyed by ``(mb, nb)``."""

    def fn(HL, C, HR):
        import jax.numpy as jnp
        mb, nb = C.shape
        cbm, cbn = min(bm, mb), min(bn, nb)
        if C.dtype != jnp.float32 or mb % cbm or nb % cbn \
                or cbm % 8 or cbn % 128:
            fn.selected[(mb, nb)] = XLA
            return xla(HL, C, HR)
        fn.selected[(mb, nb)] = PALLAS
        return tuple(_inplace_sweep(cbm, cbn, interpret)(HL, C, HR))

    fn.selected = {}
    return fn
