"""Device layer: registry + XLA (TPU) device modules.

reference: parsec/mca/device/ — see device.py and xla.py in this package.
"""

from __future__ import annotations

import os
from typing import Optional

from parsec_tpu.devices.device import Device, DeviceRegistry, DeviceStats
from parsec_tpu.utils.mca import params
from parsec_tpu.utils.output import debug_verbose

params.register("device_enabled", 1, "attach XLA accelerator devices")
params.register("device_max", 0, "max XLA devices to attach (0 = all)")

# Relative throughput weights per platform, in rough TFLOPS (reference:
# the CUDA module's per-architecture flop-rate table,
# device_cuda_module.c:53).  Used only for load balancing ratios.
_PLATFORM_WEIGHTS = {"tpu": 100.0, "gpu": 50.0, "cuda": 50.0, "cpu": 1.0}

#: the persistent XLA compile cache when nobody placed one from outside:
#: a FIXED directory inside the checkout (the directory is where a later
#: process looks the programs up again — a temporary name, a pid or a
#: time in it would never hit)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Give JAX a persistent compilation cache before the first compile,
    and return the directory in effect.

    ``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code — JAX reads
    it itself.  Otherwise ``jax_compilation_cache_dir``, unless the
    process already configured one, becomes :data:`COMPILE_CACHE_DIR`.

    With the cache on, the fused-width warmer's discarded
    ``lower().compile()`` (devices/xla.py _FuseWarmer) is a cache write
    the later jit call reads back, and a second process on the same
    machine skips the minutes-long panel-kernel compiles (JAX stores
    only programs that took >= 1 s to compile; the rest recompile
    faster than a disk read is worth)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def init_devices(context) -> DeviceRegistry:
    """Attach every visible jax device as a runtime device module
    (reference: parsec_mca_device_init/attach, parsec.c:823-828).

    A backend that fails to initialise RAISES: a machine whose TPU did
    not come up must not pass as a host-only runtime.  ``device_enabled=0``
    is the way to ask for one."""
    reg = DeviceRegistry(context)
    if not params.get("device_enabled", 1):
        return reg
    import jax
    configure_compile_cache()
    jdevs = jax.devices()
    limit = int(params.get("device_max", 0))
    if limit > 0:
        jdevs = jdevs[:limit]
    from parsec_tpu.devices.xla import XlaDevice
    for jd in jdevs:
        w = _PLATFORM_WEIGHTS.get(jd.platform, 1.0)
        reg.attach(XlaDevice(jd, weight=w))
    debug_verbose(3, "attached %d XLA devices (%s)", len(jdevs),
                  jdevs[0].platform if jdevs else "-")
    return reg


__all__ = ["Device", "DeviceRegistry", "DeviceStats", "init_devices",
           "configure_compile_cache", "COMPILE_CACHE_DIR"]
