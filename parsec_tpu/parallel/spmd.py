"""SPMD schedules over a jax device mesh.

Each function here is a *dataflow schedule template*: the single-program
form of a task-graph pattern the runtime otherwise executes task by task.
They are what PTG dep patterns lower to on a TPU slice (SURVEY.md §5.8):

- ``summa_gemm_fn``  — owner-computes 2D GEMM; the A-row / B-column panel
  broadcasts are the reference's dataflow *bcast trees*
  (remote_dep.c:334-357 star/chain/binomial) realized as ``all_gather``
  over mesh axes (XLA picks the ICI-optimal tree/ring itself).
- ``ring_reduce_gemm_fn`` — contraction-sharded GEMM whose partial-sum
  combine is a ``psum_scatter`` ring: the reduction analog.
- ``halo_stencil_fn`` — neighbor exchange via ``ppermute``: the chain
  pipeline (Ex02/Ex04 chains, stencil halos) on the ICI torus.

All are pure jax functions built with shard_map over an explicit Mesh and
jit-compiled once; control flow is static (lax.fori_loop/scan) so XLA can
pipeline collectives with compute.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import numpy as np


def make_mesh(shape: Optional[Tuple[int, ...]] = None,
              axis_names: Sequence[str] = ("p", "q"),
              devices=None):
    """Build a Mesh over the visible devices.

    ``shape=None`` picks the most square 2D factorization of the device
    count (the PxQ process grid of the reference's 2D block-cyclic
    distribution, two_dim_rectangle_cyclic.h).
    """
    import jax
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if shape is None:
        p = int(np.sqrt(n))
        while n % p:
            p -= 1
        shape = (p, n // p) if len(axis_names) == 2 else (n,)
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} != {n} devices")
    arr = np.array(devs).reshape(shape)
    return jax.sharding.Mesh(arr, tuple(axis_names[:len(shape)]))


def summa_gemm_fn(mesh, precision: Optional[str] = None) -> Callable:
    """C = A@B with A, B, C block-distributed over a (p, q) mesh.

    Panel broadcast form of SUMMA: each rank all-gathers its A block row
    along ``q`` and its B block column along ``p``, then one local matmul
    produces its C block.  The all_gathers are the dataflow-broadcast
    edges of the tiled-GEMM PTG, batched per wavefront.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    @jax.jit
    def sharded(a, b):
        def f(a_blk, b_blk):
            a_row = jax.lax.all_gather(a_blk, "q", axis=1, tiled=True)
            b_col = jax.lax.all_gather(b_blk, "p", axis=0, tiled=True)
            return jax.numpy.matmul(a_row, b_col, precision=precision)
        fm = jax.shard_map(f, mesh=mesh,
                       in_specs=(P("p", "q"), P("p", "q")),
                       out_specs=P("p", "q"))
        return fm(a, b)

    return sharded


def ring_reduce_gemm_fn(mesh, axis: str = "p",
                        precision: Optional[str] = None) -> Callable:
    """C = A@B with the contraction (K) dimension sharded over ``axis``.

    Each rank computes a full-size partial product from its K shard; the
    partials combine with ``psum_scatter`` — a reduce-scatter ring over
    ICI — leaving C row-sharded.  This is the reduction-edge analog of
    the reference's dataflow collectives (BT_reduction.jdf pattern).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    @jax.jit
    def sharded(a, b):
        def f(a_blk, b_blk):
            part = jax.numpy.matmul(a_blk, b_blk, precision=precision)
            return jax.lax.psum_scatter(part, axis, scatter_dimension=0,
                                        tiled=True)
        fm = jax.shard_map(f, mesh=mesh,
                       in_specs=(P(None, axis), P(axis, None)),
                       out_specs=P(axis, None))
        return fm(a, b)

    return sharded


def halo_stencil_fn(mesh, axis: str = "p", radius: int = 1,
                    steps: int = 1) -> Callable:
    """1D 3-point stencil with ring halo exchange over ``axis``
    (reference: tests/apps/stencil 1D halo pattern; the neighbor sends are
    ``ppermute`` shifts on the ICI ring)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]

    @jax.jit
    def sharded(x):
        def f(x_blk):
            def step(u, _):
                left_halo = jax.lax.ppermute(u[-radius:], axis, fwd)
                right_halo = jax.lax.ppermute(u[:radius], axis, bwd)
                ext = jnp.concatenate([left_halo, u, right_halo])
                new = (ext[:-2 * radius] + ext[2 * radius:] + u) / 3.0
                return new, None
            u, _ = jax.lax.scan(step, x_blk, None, length=steps)
            return u
        fm = jax.shard_map(f, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
        return fm(x)

    return sharded
