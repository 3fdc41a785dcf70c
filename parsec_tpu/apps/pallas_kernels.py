"""Pallas TPU kernels for the hot tile operations.

The runtime's device bodies are ordinarily single fused XLA ops (jnp
matmul & friends) — XLA already schedules those onto the MXU well.  This
module provides hand-written Pallas kernels for the tile ops where a
kernel of our own wins or XLA cannot work in place, the kernel seam the
reference fills with cuBLAS/user CUDA kernels (reference: the BODY
[type=CUDA] incarnations; SURVEY §7 "tile kernels as Pallas/XLA
computations"):

- a blocked ``Ci + alpha * Ai @ Bi`` with a VMEM f32 accumulator and a
  K-innermost grid, bf16/f32 inputs straight onto the MXU, selected via
  ``--mca gemm_pallas 1`` (apps/gemm.py consults it), or called as
  :func:`pallas_gemm_tile` directly as a PTG/DTD device body;
- the same program as the factorizations' trailing update
  ``C - L op(R)``, in place on C (:func:`pallas_update`), which potrf's
  and getrf's GEMM class take by shape (:func:`update_form`);
- the 1-D stencil's sweep, in place on its tile
  (:func:`pallas_sweep_tile`).

Interpret mode is the CALLER's choice (``interpret=True`` — the CPU
tests pass it); the runtime path never sets it, so on the runtime path a
kernel compiles for the attached device or raises.  Shapes that do not
tile evenly take the fused-XLA path, and each factory's ``selected`` map
says which kernel every traced shape got.

Measured (v5e, 4096-tile GEMM through the runtime): the Pallas blocked
kernel sustains ~36 TFLOP/s vs ~48 for the fused XLA matmul — XLA's MXU
pipeline wins for plain GEMM, so it stays the default; the Pallas path
is the seam for ops XLA does NOT fuse well (custom epilogues, quantized
accumulation), selected per-kernel.  Inside a fused wave of 6144 updates
the streamed product wins: every product runs at the pace XLA's lone
product gets only where its operand is prefetched (see update_form).
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
                    interpret: bool, nt: bool = False,
                    in_place: bool = False):
    """``run(A, B, C) -> C + alpha * A @ op(B)``, rounded to C's dtype:
    op(B) = B, or B^T where ``nt`` (B is then n x k and its blocks are
    read as they lie).  A block of C is loaded into an f32 VMEM
    accumulator at the first step of the K-innermost grid, every block
    product is summed into it, and it is rounded to C's dtype once, at
    the last step, as XLA's ``(C - A @ B).astype`` rounds it (loading C
    first leaves the last step one conversion: 0.5% faster at 6144 on a
    v5e than adding C there).  ``in_place`` writes the result
    into C's buffer (``input_output_aliases``): an output block is
    written back after the only read of its own C block, so a donated C
    costs no second tile."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    contract = (((1,), (1,)), ((), ())) if nt else (((1,), (0,)), ((), ()))

    def kernel(a_ref, b_ref, c_ref, o_ref, acc_ref):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            acc_ref[:, :] = c_ref[:, :].astype(jnp.float32)

        # summed into the accumulator in straight-line code (a product
        # held across a branch cost 5% at 6144 on a v5e); the update's
        # alpha = -1 subtracts it with no multiply
        prod = jax.lax.dot_general(a_ref[:, :], b_ref[:, :], contract,
                                   preferred_element_type=jnp.float32)
        if alpha == -1.0:
            acc_ref[:, :] -= prod
        else:
            acc_ref[:, :] += prod if alpha == 1.0 else alpha * prod

        @pl.when(k == pl.num_programs(2) - 1)
        def _fin():
            o_ref[:, :] = acc_ref[:, :].astype(o_ref.dtype)

    def run(Ai, Bi, Ci):
        m, kk = Ai.shape
        n = Bi.shape[0] if nt else Bi.shape[1]
        b_spec = pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)) if nt \
            else pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
        # two buffers of every block, and three f32 blocks: the
        # accumulator, the product and their sum (what the TPU's compiler
        # asks for at 1024 x 1024 x 1024 up to 2048 x 2048 x 512, within
        # 2 MiB); its default scope is 16 MiB
        item = Ai.dtype.itemsize
        vmem = (2 * (bm + bn) * bk * item
                + 4 * bm * bn * Ci.dtype.itemsize + 3 * bm * bn * 4)
        return pl.pallas_call(
            kernel,
            grid=(m // bm, n // bn, kk // bk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                b_spec,
                pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct(Ci.shape, Ci.dtype),
            scratch_shapes=scratch,
            input_output_aliases={2: 0} if in_place else {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=max(32 * 2 ** 20, vmem + 8 * 2 ** 20)),
            cost_estimate=pl.CostEstimate(
                flops=2 * m * n * kk, transcendentals=0,
                bytes_accessed=(m * kk + kk * n) * item
                + 2 * m * n * Ci.dtype.itemsize),
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
# the factorizations' trailing update, C - L op(R), in place
# ---------------------------------------------------------------------------

#: the block of C and the depth of one step of the update's grid
UPDATE_BLOCKS = (2048, 1024, 1024)
#: the smallest tile order whose update streams its own blocks
UPDATE_MIN_ORDER = 4096


def update_form(C, L, R, precision=None) -> str:
    """Which form computes ``C - L @ op(R)`` for these operands (the GEMM
    class of apps/potrf.py and apps/getrf.py): PALLAS on the chip for
    bf16 tiles at default precision whose order is ``UPDATE_MIN_ORDER``
    or more and a multiple of ``UPDATE_BLOCKS``, else XLA.

    Why: inside a fused wave XLA's own product reads its operands where
    the program-wide memory-space assignment put them, and that has room
    for one 6144 tile at a time in the core's fast memory, so the eight
    products of one 8-wide program ran up to 7% apart on a v5e, those
    that read both operands from HBM the slowest.  The Pallas product
    streams its own blocks whatever the program around it does: at 6144
    every product of the wave ran 2.44-2.45 ms where XLA's ran 2.42-2.63.
    At 2048 XLA's product was the faster alone (0.093 ms against 0.106
    at best) and in the wave, at every block shape tried, so
    ``UPDATE_MIN_ORDER`` leaves such tiles to XLA (PERF.md §6)."""
    import jax
    import jax.numpy as jnp
    m, n = C.shape
    bm, bn, bk = UPDATE_BLOCKS
    if (jax.default_backend() == "tpu" and precision is None
            and all(x.dtype == jnp.bfloat16 for x in (C, L, R))
            and min(m, n) >= UPDATE_MIN_ORDER
            and not (m % bm or n % bn or L.shape[1] % bk)):
        return PALLAS
    return XLA


def pallas_update(C, L, R, nt: bool, blocks=UPDATE_BLOCKS,
                  interpret: bool = False):
    """``C - L @ R^T`` (``nt``) or ``C - L @ R`` as the blocked Pallas
    program, IN PLACE: the result takes C's buffer, so a donated C costs
    no temporary.  ``blocks`` (bm, bn, bk) are clamped to the operands;
    whether they fit is the caller's (:func:`update_form`)."""
    m, n = C.shape
    bm, bn, bk = (min(b, d) for b, d in zip(blocks, (m, n, L.shape[1])))
    return _blocked_matmul(-1.0, bm, bn, bk, interpret, nt, True)(L, R, C)


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
