"""The main path's kernels, asked of the TPU's compiler at real widths.

No chip is needed: libtpu compiles for a DESCRIBED ``v5e:2x2`` topology
(section 2 of the on-chip-measurement guide).  What the compiler refuses
here it refuses on the chip, so these guard every later PR at no chip
time; nothing runs, so they say nothing about results or speed.

Kept to about a minute on one worker.  Three panel kernels are too slow
to compile at the full mb=6144 in a test and are compiled here at the
largest mb that fits; the builder compiled them by hand at full width
before the first chip run of PR 21, the two Cholesky-class ones again
for PR 28 (no symmetrization, TRSM in eight blocks an edge) and the two
QR ones for PR 31 (the ib blocks as one loop body with inner loops a
column block at a time, no Householder fall-back: 131 -> 9.6 s and
252 -> 14.4 s on the same day; 96 and 159 s at PR 21); seconds on this
sandbox's CPU, compiles only:

    POTRF diagonal (cholesky + tri_inv), 6144 bf16      31 s   (here 2048)
    GEQRT ib=512, 6144 bf16                             9.6 s   (here 2048)
    TSQRT ib=512, 6144 bf16                            14.4 s   (here 2048)
    GETRF diagonal (recursive LU + L^-1, U^-T), 6144    16 s   (here 2048)
    chained POTRF + 8-wide TRSM wave, 6144 bf16          29 s   (not here)

PR 28's compiles of the last read 29 / 39 / 60 s and the parent's
beside them 35 / 39 s (PR 21: 70 s; the diagonal alone 31-63 and
37-48 s): the sandbox's seconds wander by a factor of two with its
load, so they say which kernels are slow to compile, not what a change
costs.  The compiler's own count of that program is 10.2 mb^3 flop and
156 MB of temporaries, the parent's 17.2 and 456 MB, the nine donated
tiles aliased on both.

Rules this file keeps (a worker that breaks them takes the whole suite
down under pytest-xdist): the topology is described inside a
module-scoped fixture that skips if it cannot be — never at import,
never in conftest.py, not autouse; every compile happens in the test's
own process; the persistent compile cache is off around them (an entry
compiled for a described chip cannot be read back without one).
"""

import numpy as np
import pytest

MB = 6144          # potrf/qr tile
MB_GEMM = 12288    # gemm tile


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    import jax

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *specs, **jit_kw):
    import jax
    return jax.jit(fn, **jit_kw).lower(*specs).compile()


def test_gemm_tile_12288_bf16_f32acc(spec):
    import jax.numpy as jnp
    from parsec_tpu.apps.gemm import _tile_kernel
    t = (MB_GEMM, MB_GEMM)
    c = _compile(_tile_kernel(1.0), spec(t, jnp.bfloat16),
                 spec(t, jnp.bfloat16), spec(t, jnp.float32),
                 donate_argnums=(2,))
    # C is updated in place: no second 576 MiB buffer for the output
    assert c.memory_analysis().temp_size_in_bytes < t[0] * t[1] * 4


@pytest.mark.parametrize("kernel", ["trsm", "syrk", "gemm"])
def test_potrf_update_kernels_6144_bf16(spec, kernel):
    import jax.numpy as jnp
    from parsec_tpu.apps import potrf
    t = (MB, MB)
    bf = spec(t, jnp.bfloat16)
    fn, args, written = {
        "trsm": (potrf._k_trsm(None), (spec(t, jnp.float32), bf), 1),
        "syrk": (potrf._k_syrk(None), (bf, bf), 0),
        "gemm": (potrf._k_gemm(None), (bf, bf, bf), 0),
    }[kernel]
    c = _compile(fn, *args, donate_argnums=(written,))
    # the compiler's own count: SYRK and TRSM leave out the blocks the
    # triangle makes zero or unread, GEMM has none to leave out
    flops = c.cost_analysis()["flops"] / (2.0 * MB ** 3)
    if kernel == "gemm":
        assert flops == pytest.approx(1.0, rel=0.01)
    else:
        cls = kernel.upper()
        assert potrf.selected[(cls, MB)] == potrf.tri_blocks(cls, MB) > 1
        assert flops <= 0.70
    # the written tile is updated in place, with less than a tile beside
    ma = c.memory_analysis()
    assert ma.alias_size_in_bytes == MB * MB * 2
    assert ma.temp_size_in_bytes < MB * MB * 2


def _gemm_wave_width8(spec, mod, monkeypatch):
    """The width-8 trailing-update wave as the device layer builds it
    (XlaKernel.jitted_fused, written flows donated), traced as on the
    chip (the backend made to read "tpu"): all eight products are the
    streamed Pallas program, each in place on its C, and the program
    plans less than a tile beside them."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps.pallas_kernels import PALLAS
    from parsec_tpu.devices.xla import XlaKernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    k = XlaKernel(mod._k_gemm(None), ["C", "L", "R"], ["C", "L", "R"],
                  ["C"])
    bf = spec((MB, MB), jnp.bfloat16)
    c = k.jitted_fused(True, 8).lower(*[bf] * 24).compile()
    assert mod.selected[("GEMM", MB)] == PALLAS
    assert c.as_text().count('custom_call_target="tpu_custom_call"') == 8
    # 8 donated C tiles: the wave's outputs alias its inputs
    ma = c.memory_analysis()
    assert ma.alias_size_in_bytes >= 8 * MB * MB * 2
    assert ma.temp_size_in_bytes < MB * MB * 2


def test_potrf_fused_gemm_wave_width8(spec, monkeypatch):
    """potrf's wave, C - L R^T."""
    from parsec_tpu.apps import potrf
    _gemm_wave_width8(spec, potrf, monkeypatch)


def test_getrf_fused_gemm_wave_width8(spec, monkeypatch):
    """getrf's wave, C - L U."""
    from parsec_tpu.apps import getrf
    _gemm_wave_width8(spec, getrf, monkeypatch)


def test_potrf_diagonal_kernel_2048(spec):
    """Cholesky + tri_inv; full width compiled by hand (module
    docstring) — the Cholesky expander dominates the compile."""
    import jax.numpy as jnp
    from parsec_tpu.apps import potrf
    mb = 2048
    _compile(potrf._k_potrf(None), spec((mb, mb), jnp.bfloat16),
             spec((mb, mb), jnp.float32))


@pytest.mark.parametrize("n,precision", [(512, "HIGHEST"), (6144, "DEFAULT")])
def test_tri_inv_runs_mxu_wide_leaves(spec, n, precision):
    """The triangular inverse at the QR panel's ib block (HIGHEST) and
    at the Cholesky tile (DEFAULT): Newton on a batch of leaves of 128
    or less, not on the whole block.  Newton on one 512 leaf executed
    5.38 GFLOP where the batch executes 0.36; the recursion down to 512
    leaves ran 350 products at 6144 where the batch runs 28."""
    import re
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps import potrf
    prec = getattr(jax.lax.Precision, precision)
    c = _compile(lambda L: potrf.tri_inv(L, prec),
                 spec((n, n), jnp.float32))
    if n == 512:
        assert c.cost_analysis()["flops"] <= 0.5e9
    else:
        assert len(re.findall(r"\bconvolution\(", c.as_text())) <= 32
    assert potrf.selected[("INV", n)] <= 128


@pytest.mark.parametrize("kernel", ["trsmr", "gemm"])
def test_lu_update_kernels_6144_bf16(spec, kernel):
    """The LU cell's row-panel solve (by GETRF's f32 inverse, blocks
    above the triangle left out) and trailing update; the column panel
    is potrf's TRSM, compiled above."""
    import jax.numpy as jnp
    from parsec_tpu.apps import getrf
    t = (MB, MB)
    bf = spec(t, jnp.bfloat16)
    fn, args, written = {
        "trsmr": (getrf._k_trsmr(None), (spec(t, jnp.float32), bf), 1),
        "gemm": (getrf._k_gemm(None), (bf, bf, bf), 0),
    }[kernel]
    c = _compile(fn, *args, donate_argnums=(written,))
    flops = c.cost_analysis()["flops"] / (2.0 * MB ** 3)
    assert flops == pytest.approx(1.0, rel=0.01) if kernel == "gemm" \
        else flops <= 0.70
    ma = c.memory_analysis()
    assert ma.alias_size_in_bytes == MB * MB * 2
    assert ma.temp_size_in_bytes < MB * MB * 2


def test_lu_diagonal_kernel_2048(spec):
    """The recursive LU with both inverses; full width compiled by hand
    (module docstring)."""
    import jax.numpy as jnp
    from parsec_tpu.apps import getrf
    mb = 2048
    f32 = spec((mb, mb), jnp.float32)
    _compile(getrf._k_getrf(None), spec((mb, mb), jnp.bfloat16), f32, f32,
             donate_argnums=(0, 1, 2))


def test_qr_tsmqr_6144_bf16(spec):
    """The group loop at the cell's shapes (six groups of 1024): the
    f32 running tile passes from group to group without a copy (a
    ``copy`` of the tile's shape is what would make the rank-W updates
    memory-bound, PERF.md section 7)."""
    import re
    import jax.numpy as jnp
    from parsec_tpu.apps import qr
    bf = jnp.bfloat16
    W = qr.group_width(MB, 512)
    assert MB % W == 0 and W < MB
    c = _compile(qr._mk_tsmqr(), spec((MB + W, MB), bf), spec((MB, MB), bf),
                 spec((MB, MB), bf))
    assert not re.findall(r"= f32\[%d,%d\]\S* copy\(" % (MB, MB),
                          c.as_text())
    # 4 mb^3 + 2 W mb^2 executed, where the panel-wide factor took 6 mb^3
    flop = c.cost_analysis()["flops"] / MB ** 3
    assert abs(flop - (4 + 2 * W / MB)) < 0.01


@pytest.mark.parametrize("kernel", ["geqrt", "tsqrt"])
def test_qr_panel_kernels_ib512_2048(spec, kernel):
    """The inner-blocked panel engine (four ib=512 blocks: one loop
    body, a branch a block for what follows the block's place; TSQRT's
    reflector in two groups of 1024); full width compiled by hand
    (module docstring)."""
    import re
    import jax.numpy as jnp
    from parsec_tpu.apps import qr
    mb, bf = 2048, jnp.bfloat16
    t = spec((mb, mb), bf)
    if kernel == "geqrt":
        c = _compile(qr._mk_geqrt(512), t, t)
    else:
        c = _compile(qr._mk_tsqrt(512), t, t, spec((mb + 1024, mb), bf))
        assert qr.selected[("TSQRT", mb, 512)] == 1024
    # one loop over the blocks, and no Householder expander behind it
    hlo = c.as_text()
    assert "while" in hlo and "householder" not in hlo.lower()
    # the running f32 tile keeps its layout: no transposing copy of it
    # in or around the loop (an inverse taken of a transpose turned it)
    assert not re.findall(r"= f32\[%d,%d\]\S* copy\(" % (mb, mb), hlo)


def test_pallas_blocked_matmul_compiles_to_mosaic(spec):
    """512^3 blocks over a 6144 tile, bf16 panels, f32 C."""
    import jax.numpy as jnp
    from parsec_tpu.apps.pallas_kernels import PALLAS, pallas_gemm_tile
    fn = pallas_gemm_tile(1.0)
    t = (MB, MB)
    c = _compile(fn, spec(t, jnp.bfloat16), spec(t, jnp.bfloat16),
                 spec(t, jnp.float32))
    assert fn.selected == {(MB, MB, MB): PALLAS}
    assert "tpu_custom_call" in c.as_text()


def test_stencil_sweep_wave_is_in_place(spec):
    """The stencil cell's program (PR 37): an eight-wide wave of the
    in-place Pallas sweep over 4096 x 8192 float32 tiles with every
    ``C`` donated.  The compiler aliases the eight tiles and plans no
    temporary: a sweep moves each point once in and once out.  (The XLA
    form of the same wave plans 512 MiB of temporaries and a copy a
    tile; PERF.md, PR 37.)"""
    import jax.numpy as jnp
    from parsec_tpu.apps.pallas_kernels import PALLAS, pallas_sweep_tile
    mb, nb, w = 4096, 8192, 8
    fn = pallas_sweep_tile(xla=None)

    def wave(*flat):
        return tuple(fn(*flat[3 * t:3 * t + 3]) for t in range(w))
    tile, halo = spec((mb, nb), jnp.float32), spec((1, nb), jnp.float32)
    c = _compile(wave, *([halo, tile, halo] * w),
                 donate_argnums=tuple(3 * t + 1 for t in range(w)))
    assert fn.selected == {(mb, nb): PALLAS}
    assert c.as_text().count("tpu_custom_call") >= w
    ma = c.memory_analysis()
    assert ma.alias_size_in_bytes == w * mb * nb * 4
    assert ma.temp_size_in_bytes < 2 ** 20


@pytest.fixture(scope="module")
def mesh(topo):
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices), ("d",))


def test_four_chip_ppermute_program(mesh):
    """comm/ici.py's CollectivePermute over all four chips, one 6144
    bf16 tile per chip round the ring."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from parsec_tpu.comm.ici import permute_program
    n = mesh.devices.size
    assert n == 4
    x = jax.ShapeDtypeStruct((n, MB, MB), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("d")))
    c = permute_program(mesh, [(i, (i + 1) % n) for i in range(n)]) \
        .lower(x).compile()
    assert "collective-permute" in c.as_text()
    # each chip holds its own tile in and one tile out, not the stack
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == MB * MB * 2
    assert ma.output_size_in_bytes == MB * MB * 2


def test_four_chip_replicated_operand(mesh):
    """A panel tile replicated on every chip (what ici.bcast places,
    ``NamedSharding(mesh, P())``) feeding each chip's own update."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from parsec_tpu.apps import potrf
    n = mesh.devices.size
    bf = jnp.bfloat16
    rep = jax.ShapeDtypeStruct((MB, MB), bf,
                               sharding=NamedSharding(mesh, P()))
    own = jax.ShapeDtypeStruct((n, MB, MB), bf,
                               sharding=NamedSharding(mesh, P("d")))
    syrk = potrf._k_syrk(None)
    prog = jax.jit(jax.shard_map(
        lambda r, t: syrk(t[0], r)[None], mesh=mesh,
        in_specs=(P(), P("d")), out_specs=P("d")))
    ma = prog.lower(rep, own).compile().memory_analysis()
    # per chip: the whole replicated tile plus its own tile
    assert ma.argument_size_in_bytes == 2 * MB * MB * 2
