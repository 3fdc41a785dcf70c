"""Plain reference for the GEMM cells: C0 + A B of one C tile as a plain
``jnp`` product, float32 accumulation of the stored operands (bfloat16
products are exact in float32, so this is the precision the
configuration states, not a lower one).  Imports nothing of the program.
"""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _kernels():
    import jax
    import jax.numpy as jnp

    def bench_check_madd(acc, a, b):
        return acc + jnp.matmul(a, b, preferred_element_type=jnp.float32)

    def bench_check_gap(got, ref):
        return jnp.max(jnp.abs(got - ref)), jnp.max(jnp.abs(ref))

    return jax.jit(bench_check_madd), jax.jit(bench_check_gap)


def reference_tile(c0, a_row, b_col, store=None):
    """c0 + sum_k a_row[k] b_col[k].  ``store`` rounds A's and B's tiles
    first (the control passes ``store_fp8``)."""
    madd, _ = _kernels()
    ref = c0
    for a, b in zip(a_row, b_col):
        if store is not None:
            a, b = store(a), store(b)
        ref = madd(ref, a, b)
    return ref


def gap(got, ref) -> tuple:
    """(max |got - ref|, max |ref|) over one tile."""
    num, den = _kernels()[1](got, ref)
    return float(num), float(den)


def store_fp8(t):
    """Round an operand tile to e4m3 (entries lie within +-1.74, well
    inside the format's range): the nearest precision below bfloat16.
    ``lax.reduce_precision`` does the rounding (a convert to
    ``float8_e4m3fn`` and back is folded away on the TPU); the stored
    values come back exactly in bfloat16."""
    import jax
    import jax.numpy as jnp
    return jax.lax.reduce_precision(
        t.astype(jnp.float32), exponent_bits=4,
        mantissa_bits=3).astype(jnp.bfloat16)
