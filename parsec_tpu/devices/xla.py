"""XLA device module: TPU (and any jax backend) task offload.

Rebuild of the reference's GPU device machinery on the XLA execution model
(reference: parsec/mca/device/device_gpu.{c,h} generic GPU base +
parsec/mca/device/cuda/device_cuda_module.c offload pipeline;
parsec/mca/device/template/ is the seam this module fills): each attached
jax device gets a manager thread (stage-in + kernel dispatch — the
reference's mutex-elected manager loop, device_cuda_module.c:2537-2763)
and a completer thread (the analog of CUDA-event polling in
progress_stream:1961).  Kernel dispatch through jax is asynchronous, so the
manager pipelines stage-in and launch while the completer blocks on the
oldest in-flight task's outputs, preserving the reference's
``PARSEC_HOOK_RETURN_ASYNC`` completion contract: the device owns the task
until it re-enters ``complete_execution``.

Device memory is a coherency-tracked cache of datum copies with LRU
eviction and byte accounting (reference: gpu_mem_lru + zone_malloc; here
XLA owns the actual HBM, we manage copy lifetime).  Kernels are pure jax
functions over flow payloads; they are jitted once per (shape, dtype)
signature with input buffers of written flows donated so XLA reuses their
HBM (the moral equivalent of in-place tile updates).

TPU notes: keep tiles MXU-friendly (multiples of 128, bf16/f32); the jit
cache means steady-state execution launches pre-compiled executables only.
"""

from __future__ import annotations

import re
import threading
import time as _time
import weakref
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from parsec_tpu.core.task import HookReturn, Task, normalize_body_outputs
from parsec_tpu.data.data import (ACCESS_WRITE, Coherency, Data, DataCopy,
                                  FLAG_COW, FLAG_REPLICA, FLAG_SCRATCH)
from parsec_tpu.devices.device import Device
from parsec_tpu.prof.pins import SPAN_OFF, open_span, spans_live
from parsec_tpu.utils import faultinject as _fi
from parsec_tpu.utils.mca import params
from parsec_tpu.utils.output import debug_verbose, warning


params.register("device_inflight_depth", 8,
                "max in-flight device tasks per XLA device")
params.register("device_fuse_warm_wait_ms", 3000.0,
                "how long, from the moment a fused-width program's "
                "background compile was asked for, its waves wait for "
                "the compile before they run as de-fused singles (every "
                "manager that meets the warming width waits to the same "
                "deadline and wakes on the warmer's signal): long enough "
                "to cover a matmul-class compile or a persistent-cache "
                "read (~1-3s), far below a cold Cholesky/tri_inv-class "
                "compile (tens of seconds)")
params.register("device_fuse_window_ms", 0.0,
                "how long a manager waits for same-class siblings before "
                "launching a narrower-than-device_fuse wave (ms).  "
                "Trading a few ms of batching window for 4-8x fewer "
                "programs wins when the fixed cost of a dispatch is "
                "large against the wave and readiness arrives in bursts "
                "(eager dep release makes it so); 0 = launch immediately")
params.register("device_runahead", 256,
                "max eagerly-completed tasks with unmaterialized outputs "
                "before the completer blocks (memory safety valve; each "
                "blocking wait stalls the completer for a device sync, "
                "so keep this well above the DAG's width)")
params.register("device_mem_mb", 0,
                "device copy-cache capacity in MiB (0 = unlimited)")
params.register("device_max_faults", 0,
                "disable a device after this many launch faults and fall "
                "back to other incarnations (0 = fail the context, like "
                "an unguarded run; reference: HOOK_RETURN_DISABLE)")
params.register("device_fuse", 8,
                "max same-class ready device tasks fused into ONE XLA "
                "launch (wavefront launch fusion: the TRSM panel or "
                "SYRK/GEMM trailing-update wave of a dense factorization "
                "rides a single dispatch, amortizing per-launch latency; "
                "1 disables)")
params.register("device_dispatchers", 2,
                "manager (launch) threads per XLA device: a dispatch "
                "blocks its thread through stage-in and any first-use "
                "compile, so overlapping independent launches keeps the "
                "device queue fed; ordering stays safe because a "
                "successor is only submitted after its producer's "
                "dispatch returned")


class XlaKernel:
    """Device incarnation spec: a pure jax function over flow payloads.

    The function's named arguments are bound from flow payloads (as jax
    arrays) and task parameters (passed as static arguments, so a kernel
    indexing by a parameter recompiles per value — keep parameters out of
    kernels on hot paths).  It returns the new values of the written flows:
    a dict {flow: array}, a tuple in written-flow declaration order, or a
    single array when exactly one flow is written.
    (reference: the BODY [type=CUDA] incarnation of a JDF task class,
    jdf2c.c:6556 GPU hook generation.)
    """

    #: guards the caches on the kernel functions (jitted callables and
    #: fused-width states) and is what a wave waits on for a warming
    #: width: the warmer notifies it when it posts a width's state
    _jit_cv = threading.Condition()

    def __init__(self, fn, arg_names: Sequence[str],
                 flow_names: Sequence[str], writable_flows: Sequence[str],
                 cls: Optional[str] = None):
        self.fn = fn
        #: the task class this kernel is the body of (POTRF, GEMM...):
        #: what its programs and their scopes are named after, so a
        #: device trace reads ``jit_parsec_GEMM_x8`` and not ``jit_target``
        self.cls = re.sub(r"\W", "_", cls or getattr(fn, "__name__", "")
                          or "kernel")
        self.arg_names = list(arg_names)
        self.flow_names = set(flow_names)
        self.writable = list(writable_flows)   # flow declaration order
        #: what a launch asks of every argument, settled once: (name,
        #: bound from a flow's payload?) in call order, and the
        #: positions whose buffers a donating call hands to XLA (flows
        #: the kernel writes)
        self.arg_plan = tuple((a, a in self.flow_names)
                              for a in self.arg_names)
        #: the flows whose payloads the kernel is handed; a flow it only
        #: returns (a halo, a norm) is staged without a buffer of its own
        self.flow_args = frozenset(a for a, is_flow in self.arg_plan
                                   if is_flow)
        self.donate_pos = tuple(
            i for i, a in enumerate(self.arg_names)
            if a in self.flow_names and a in self.writable)
        self._keep_pos = tuple(i for i in range(len(self.arg_names))
                               if i not in self.donate_pos)
        #: per-instance fast path: donate-flag -> jitted callable, dodging
        #: the lock + tuple rebuild on every launch (hot path)
        self._fast: Dict[bool, Any] = {}
        # The cache lives ON the kernel function object, so its lifetime
        # is the function's: module-level kernels (apps memoize theirs,
        # e.g. gemm._kernels) share traced executables, and which fused
        # widths are ready, across taskpool rebuilds, while per-build
        # lambdas die with their pools instead of pinning entries in a
        # global table forever.
        with XlaKernel._jit_cv:
            cache = getattr(fn, "__parsec_jit_cache__", None)
            if cache is None:
                cache = {}
                try:
                    fn.__parsec_jit_cache__ = cache
                except AttributeError:   # unsettable callable: no sharing,
                    pass                 # this instance keeps its own
        #: jit key -> jitted callable, and fused-width key -> its state
        #: (see fuse_ready), for every XlaKernel over this function
        self._cache: Dict[Any, Any] = cache

    @property
    def name(self) -> str:
        """Where the kernel was defined (``potrf._k_trsm.<locals>.fn``):
        what fused-width failure reports key on."""
        fn = self.fn
        mod = (getattr(fn, "__module__", None) or "").rsplit(".", 1)[-1]
        qual = getattr(fn, "__qualname__", None) or repr(fn)
        return f"{mod}.{qual}" if mod else qual

    def jitted(self, donate: bool):
        jf = self._fast.get(donate)
        if jf is not None:
            return jf
        jf = self._jitted_slow(donate)
        self._fast[donate] = jf
        return jf

    def jitted_fused(self, donate: bool, n: int):
        """One XLA program applying the kernel to ``n`` independent task
        instances (wavefront launch fusion).  The traced body unrolls the
        n applications; XLA schedules them back-to-back on device, so a
        whole same-class wave costs one dispatch round trip instead of n.
        Compiled once per (n, donate) per shape signature."""
        key = (donate, n)
        jf = self._fast.get(key)
        if jf is not None:
            return jf
        jf = self._jitted_slow(donate, n)
        self._fast[key] = jf
        return jf

    def _jitted_slow(self, donate: bool, n: int = 1):
        k = len(self.arg_names)
        static1 = tuple(i for i, (_a, is_flow) in enumerate(self.arg_plan)
                        if not is_flow)
        dn1 = self.donate_pos if donate else ()
        static = tuple(t * k + i for t in range(n) for i in static1)
        dn = tuple(t * k + i for t in range(n) for i in dn1)
        # the class is part of the key: two classes sharing one kernel
        # function each get a program under their own name
        key = (static, dn, n, self.cls)
        with XlaKernel._jit_cv:
            jf = self._cache.get(key)
            if jf is None:
                import jax
                fn, cls = self.fn, self.cls
                if n == 1:
                    def target(*flat):
                        with jax.named_scope(cls):
                            return fn(*flat)
                else:
                    def target(*flat):
                        outs = []
                        for t in range(n):
                            with jax.named_scope(cls):
                                outs.append(fn(*flat[t * k:(t + 1) * k]))
                        return tuple(outs)
                # the program's stable name: XLA calls the module
                # jit_<__name__>, and that is what the trace prints
                target.__name__ = target.__qualname__ = \
                    f"parsec_{cls}" if n == 1 else f"parsec_{cls}_x{n}"
                jf = jax.jit(target, static_argnums=static, donate_argnums=dn)
                self._cache[key] = jf
            return jf

    def bind_outputs(self, result: Any) -> Dict[str, Any]:
        return normalize_body_outputs(result, self.writable, what="kernel")

    def task_sig(self, task: Task):
        """What specializes this kernel's program for ``task``: the
        values of its non-flow arguments (static argnums) and the shape
        and dtype of each flow's payload, in call order — objects that
        hash and compare cheaply, no strings.  Two tasks of one spec may
        ride one fused launch exactly when their signatures are equal.
        None: not fusable (a flow unbound, a static that does not hash).
        A device computes it once a task, at ``submit``."""
        sig = []
        data = task.data
        try:
            for a, is_flow in self.arg_plan:
                if is_flow:
                    copy = data.get(a)
                    p = copy.payload if copy is not None else None
                    if p is None:
                        return None
                    sig.append(p.shape)
                    sig.append(p.dtype)
                else:
                    sig.append(task.locals.get(
                        a, task.taskpool.globals.get(a)))
            sig = tuple(sig)
            hash(sig)
        except Exception:
            return None
        return sig

    def args_sig(self, args: Sequence[Any]):
        """:meth:`task_sig` read off one task's staged arguments."""
        sig = []
        for a, (_name, is_flow) in zip(args, self.arg_plan):
            if is_flow and hasattr(a, "shape"):
                sig.append(a.shape)
                sig.append(a.dtype)
            else:
                sig.append(a)
        return tuple(sig)

    def fuse_ready(self, donate: bool, n: int, flat: Sequence[Any],
                   device: Optional["XlaDevice"] = None,
                   sig: Any = None) -> bool:
        """Whether the width-``n`` fused program may be dispatched NOW.

        First use of a fused width triggers a full XLA compile — tens of
        seconds for Cholesky/tri_inv-class programs — and which widths a
        run needs depends on nondeterministic wave scheduling, so a cold
        width mid-measurement stalls the whole pipeline (the r4 geqrf
        variance).  Instead the first request WARMS the width in a
        background thread (shape-only lower+compile: the result lands
        in the persistent compile cache that
        devices.configure_compile_cache placed, so the eventual jit call
        reads it back instead of compiling again).

        A width's state belongs to the PROGRAM, not to the taskpool: it
        sits beside the jitted callables on the kernel function, under a
        key that names the program the jit call will ask for — class,
        donation, width, the wave's signature (``sig``: the ONE
        ``task_sig`` its members were queued under; read off the first
        member's arguments where the caller has none) and the device —
        so every later taskpool over the same kernel function finds the
        width ready with one lookup: no compile submitted, nothing
        waited for, and the arguments are looked at only by the call
        that submits the warm compile.  The states: absent (never asked
        for), ``("warming", deadline)``, ``True``, ``("failed", when,
        reason)``.

        Whoever meets a warming width — the manager that asked for it
        or any other — waits on the condition the warmer notifies when
        it posts the state, up to the width's deadline
        (``device_fuse_warm_wait_ms`` from the request): a matmul-class
        compile or a persistent-cache read lands inside it and the wave
        goes out FUSED, far cheaper than a rep of singles.  Past the
        deadline (a cold Cholesky-class program) nobody waits: waves
        run as singles until the compile lands.

        A width whose compile failed answers False too, without a wait,
        and is asked for again after 60 s; the compiler's words stay in
        its stamp, and a caller that passes its ``device`` gets them
        recorded in the device's ``fuse_failures``, with one warning at
        the first record.  On that device the background compile shows
        as a ``warm.compile`` span and in ``compiles``, a wait as a
        ``mgr.warm_wait`` span and in ``warm_waits``."""
        if n <= 1:
            return True
        if sig is None:
            sig = self.args_sig(flat[:len(self.arg_names)])
        key = ("w", self.cls, donate, n, sig,
               device.name if device is not None else None)
        state = self._cache
        if state.get(key) is True:
            return True
        specs = None
        with XlaKernel._jit_cv:
            st = state.get(key)
            if st is True:
                return True
            now = _time.monotonic()
            if st is not None and st[0] == "failed":
                self._fuse_failed(device, n, st[2])
                if now - st[1] < 60.0:
                    return False    # backoff: singles, no wait
                st = None
            if st is None:
                import jax
                # the sharding rides along so the warm compile is THE
                # program the jit call will ask for (same device
                # assignment: the persistent-cache key covers it)
                specs = [jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None))
                    if hasattr(a, "shape") else a for a in flat]
                st = state[key] = ("warming", now + 1e-3 * float(
                    params.get("device_fuse_warm_wait_ms", 3000.0)))
            left = st[1] - now      # of the width's bound
            if left > 0 and device is not None:
                device.stats.warm_waits += 1
        if specs is not None:
            _fuse_warmer.submit(self, key, donate, n, specs, device)
        if left <= 0:
            return False    # a slow compile: singles until it lands
        with open_span(device.es if device is not None else None,
                       "mgr.warm_wait",
                       program=_program_name(self.jitted_fused(donate, n))):
            with XlaKernel._jit_cv:
                XlaKernel._jit_cv.wait_for(
                    lambda: state[key] is True or state[key][0] != "warming",
                    left)
                st = state[key]
        if st is True:
            return True
        if st[0] == "failed":       # singles this time
            return self._fuse_failed(device, n, st[2])
        return False

    def _fuse_failed(self, device: Optional["XlaDevice"], n: int,
                     reason: str) -> bool:
        """Record a failed width with the device that asked, warning at
        its first record there; False, what ``fuse_ready`` answers."""
        if device is not None and (self.name, n) not in device.fuse_failures:
            device.fuse_failures[(self.name, n)] = reason
            warning("fused width %d of kernel %s failed to compile; "
                    "its waves run as singles: %s", n, self.name,
                    reason[:2000])
        return False


class _FuseWarmer:
    """ONE background thread compiling fused-width programs serially:
    the compiles share the host's cores with the workers and the
    dispatching managers, and a single queue still warms every width
    well before steady state.  It posts each width's outcome where
    ``XlaKernel.fuse_ready`` reads it — the cache on the kernel
    function, shared by every taskpool — and notifies the waves that
    wait for it."""

    def __init__(self):
        self._q: deque = deque()
        self._cv = threading.Condition()
        self._thread = None
        self._busy = 0
        #: programs compiled for a chip that has not called them yet
        #: (cover): (spec, donate, width, argument specs, device)
        self._unprimed: List[Tuple] = []
        self._at_exit = False     # _drain_at_exit is registered

    def _drain_at_exit(self) -> None:
        """Drop what is queued and let the compile in hand finish: the
        interpreter tears a daemon thread down inside XLA otherwise, and
        the process aborts on its way out."""
        with self._cv:
            self._q.clear()
            self._cv.wait_for(lambda: not self._busy, 120.0)

    def submit(self, spec, key, donate, n, arg_specs, device=None,
               prime: bool = False) -> None:
        with self._cv:
            self._q.append((spec, key, donate, n, arg_specs, device, prime))
            if self._thread is None or not self._thread.is_alive():
                if not self._at_exit:
                    import atexit
                    atexit.register(self._drain_at_exit)
                    self._at_exit = True
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="xla-fuse-warm")
                self._thread.start()
            self._cv.notify_all()

    def cover(self, spec, donate, n, flat, device, chips, sig=None) -> None:
        """A context that drives several chips met a wave of a kernel on
        one of them: which power-of-two wave widths meet on which chip
        is timing, so what one chip asks for every chip will — queue
        every width up to ``device_fuse``, the single included, for
        every chip, once a (class, donation, signature) a process; the
        asking chip's own width first.  (A class that never meets in a
        wave — a panel's diagonal kernel — runs where its tile is pinned,
        the same in every job, and is left alone.)  Each lands in the
        persistent cache and is marked ready where
        ``XlaKernel.fuse_ready`` looks; ``prime`` then makes the jitted
        call itself on the chips that have not."""
        args = list(flat[:len(spec.arg_names)])
        if sig is None:
            sig = spec.args_sig(args)
        seen = ("cover", spec.cls, donate, sig)
        state = spec._cache
        if seen in state:
            return
        import jax
        from jax.sharding import SingleDeviceSharding
        limit = max(1, int(params.get("device_fuse", 8)))
        widths = [1 << i for i in range(limit.bit_length())]
        todo = []
        with XlaKernel._jit_cv:
            if seen in state:
                return
            state[seen] = True
            deadline = _time.monotonic() + 1e-3 * float(
                params.get("device_fuse_warm_wait_ms", 3000.0))
            for dev in sorted(chips, key=lambda d: d is not device):
                sh = SingleDeviceSharding(dev.jdev)
                one = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
                       if hasattr(a, "shape") else a for a in args]
                for w in sorted(widths, key=lambda w: w != n):
                    key = ("w", spec.cls, donate, w, sig, dev.name)
                    if key not in state:
                        state[key] = ("warming", deadline)
                        todo.append((key, w, one * w, dev))
        for key, w, specs, dev in todo:
            self.submit(spec, key, donate, w, specs, dev, prime=True)

    def wait_idle(self, timeout: float = 600.0) -> bool:
        """Block until every queued width compile has finished — the
        bench-warmup hook: a timed rep must not run de-fused because
        its widths are still warming (see xla.wait_fuse_warm)."""
        deadline = _time.monotonic() + timeout
        with self._cv:
            while self._q or self._busy:
                left = deadline - _time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.2))
        return True

    def _run(self):
        while True:
            with self._cv:
                if not self._q:
                    # linger briefly for more work, then retire —
                    # clearing _thread UNDER THE LOCK first, so a
                    # submit() racing the unwind sees a dead warmer and
                    # restarts one (else its item would never compile)
                    self._cv.wait(5.0)
                    if not self._q:
                        self._thread = None
                        return
                spec, key, donate, n, arg_specs, device, prime = \
                    self._q.popleft()
                self._busy += 1
            reason = None
            if device is not None:
                device.stats.compiles += 1
            try:
                jf = spec.jitted_fused(donate, n)
                with open_span(device.es if device is not None else None,
                               "warm.compile", program=_program_name(jf)):
                    jf.lower(*arg_specs).compile()
            except Exception as exc:
                reason = f"{type(exc).__name__}: {exc}"
            with XlaKernel._jit_cv:
                # failure memoization with backoff: a persistently
                # failing width must not make every wave re-pay the
                # bounded wait.  The compiler's words stay in the stamp;
                # fuse_ready hands them to the device that asks next
                spec._cache[key] = True if reason is None else \
                    ("failed", _time.monotonic(), reason)
                XlaKernel._jit_cv.notify_all()
            with self._cv:
                if prime and reason is None:
                    self._unprimed.append((spec, donate, n, arg_specs,
                                           device))
                self._busy -= 1
                self._cv.notify_all()

    def prime(self) -> int:
        """Call, once, every program that ``cover`` compiled for a chip
        and that chip has not called since: the first call of a jitted
        function on a chip traces it and reads its executable back from
        the persistent cache, which is a compile to whoever counts them,
        and belongs to the warm-up.  The arguments are zeros made on the
        chip; the caller is at a quiet point (``wait_fuse_warm``)."""
        import jax
        import jax.numpy as jnp
        with self._cv:
            todo, self._unprimed = self._unprimed, []
        shared: Dict[Tuple, Any] = {}
        called = 0
        for spec, donate, n, arg_specs, device in todo:
            jf = spec.jitted_fused(donate, n)
            if device.jdev.id in getattr(jf, "_parsec_ran", ()) \
                    or not device.enabled:
                continue
            k = len(spec.arg_names)
            args = []
            for i, a in enumerate(arg_specs):
                if not hasattr(a, "shape"):
                    args.append(a)
                    continue
                name = spec.arg_names[i % k]
                zkey = (device.jdev.id, tuple(a.shape), str(a.dtype))
                if donate and name in spec.writable:
                    # a donated position needs a buffer of its own
                    args.append(jnp.zeros(a.shape, a.dtype,
                                          device=device.jdev))
                else:
                    if zkey not in shared:
                        shared[zkey] = jnp.zeros(a.shape, a.dtype,
                                                 device=device.jdev)
                    args.append(shared[zkey])
            try:
                jax.block_until_ready(device._call(jf, args))
                called += 1
            except Exception as exc:
                warning("priming %s on %s failed: %s", _program_name(jf),
                        device.name, exc)
        return called


_fuse_warmer = _FuseWarmer()


def _program_name(jf) -> str:
    """What the device trace calls the jitted callable's program."""
    return "jit_" + getattr(jf, "__name__", "?")


def _chain_name(node_specs, wave_spec, n: int) -> str:
    """``parsec_chain_<HEADS>__<CLS>_x<n>``: the held heads in launch
    order (a run of one class as ``<CLS><count>``), then the consumer
    wave; ``parsec_chain_<HEADS>`` where no wave follows.  (The device
    itself asks for one head and its successor wave: ``_run_chain``.)"""
    heads = []
    for sp in node_specs:
        if heads and heads[-1][0] == sp.cls:
            heads[-1][1] += 1
        else:
            heads.append([sp.cls, 1])
    name = "parsec_chain_" + "_".join(
        c if k == 1 else f"{c}{k}" for c, k in heads)[:80]
    if wave_spec is not None and n:
        name += f"__{wave_spec.cls}_x{n}"
    return name


def wait_fuse_warm(timeout: float = 600.0) -> bool:
    """Wait for all in-flight fused-width background compiles (benches
    call this between warmup and timed reps, then run ONE more warm
    pass so the newly-ready widths' client-side jit calls also land in
    cache — otherwise reps run de-fused singles while widths warm).
    Where the context drives several chips, every width compiled for a
    chip that has not met it is then called there once
    (``_FuseWarmer.prime``): the caller is between jobs."""
    ok = _fuse_warmer.wait_idle(timeout)
    _fuse_warmer.prime()
    return ok


class Deferred:
    """Placeholder payload of a chain-held task's output (cross-panel
    fused dispatch; reference analog: the panel chains DPLASMA keeps on
    one CUDA stream so POTRF->TRSM never round-trips through the host).

    A held task's deps release eagerly — consumers instantiate and reach
    the device with Deferred payloads — and the held kernel is traced
    into the first consuming launch (XlaDevice._dispatch_chained), which
    resolves ``array`` for every other consumer.  Foreign consumers (a
    CPU body, another device, the ICI layer) call :meth:`force`, which
    dispatches the held head alone on its owning device."""

    __slots__ = ("hold", "flow", "_shape", "_dtype", "array")

    #: duck-typing marker for layers that must not touch placeholder
    #: payloads (engine.stage_in_host, comm/ici.py)
    parsec_deferred = True

    def __init__(self, hold, flow, shape, dtype):
        self.hold = hold
        self.flow = flow
        self._shape = shape
        self._dtype = dtype
        self.array = None      # filled at resolution

    @property
    def shape(self):
        return self._shape

    @property
    def dtype(self):
        return self._dtype

    @property
    def nbytes(self):
        if self.array is not None:
            return getattr(self.array, "nbytes", 0)
        try:
            n = 1
            for d in self._shape:
                n *= int(d)
            return n * np.dtype(self._dtype).itemsize
        except Exception:
            return 0

    def force(self):
        """Dispatch the held head now (owning device) and return the
        real array."""
        if self.array is None:
            self.hold.device._force_hold(self.hold)
        return self.array

    def is_ready(self):
        a = self.array
        if a is None:
            return False
        r = getattr(a, "is_ready", None)
        try:
            return bool(r()) if r is not None else True
        except Exception:
            return True

    def block_until_ready(self):
        import jax
        a = self.array if self.array is not None else self.force()
        return jax.block_until_ready(a)


class _Hold:
    """One chain-held device task: staged inputs + deferred outputs.
    ``state`` moves held -> launching -> resolved under the device's
    ``_chain_cv``."""

    __slots__ = ("device", "task", "spec", "flat", "outputs", "state",
                 "seq", "succ", "deadline")


#: how long a task of another class that reads a held head's output
#: waits for the launch of the head's declared successor before it
#: forces the head alone (s), counted only while the device has nothing
#: else in hand: behind a busy chip the successor's other input may be
#: seconds of queued work away, and waiting costs nothing.  The bound
#: only keeps a successor that never comes (a CPU incarnation, another
#: pool's cancellation) from parking its siblings for good
_HOLD_PATIENCE_S = 0.25


_chain_jit_lock = threading.Lock()
#: (node structure, wave structure) -> jitted combined program.  Keys
#: hold the kernel function objects, so entries die only with the app's
#: memoized kernels; chain structures repeat per panel index, so steady
#: state compiles each shape once.
_chain_jit_cache: Dict[Any, Any] = {}


def _declared_successor(fuse_chain) -> Optional[str]:
    """The successor class of a ``fuse_chain`` = (flow, successor class)
    property; None where a bare flow name takes any consumer."""
    return fuse_chain[1] if isinstance(fuse_chain, (tuple, list)) \
        and len(fuse_chain) > 1 else None


def _chain_jitted(key, node_specs, node_descs, wave_spec, wave_descs,
                  donate=()):
    """One XLA program executing the held chain nodes in topological
    order, then the consumer wave, wiring arguments by descriptor:
    ("l", i) = leaf input, ("n", j, flow) = node j's output, ("s", v) =
    static value closed over (part of the cache key)."""
    with _chain_jit_lock:
        jf = _chain_jit_cache.get(key)
        if jf is not None:
            return jf

    def resolve(d, leaves, node_outs):
        tag = d[0]
        if tag == "l":
            return leaves[d[1]]
        if tag == "n":
            return node_outs[d[1]][d[2]]
        return d[1]

    import jax

    def prog(*leaves):
        node_outs = []
        for sp, ds in zip(node_specs, node_descs):
            args = [resolve(d, leaves, node_outs) for d in ds]
            with jax.named_scope(sp.cls):
                node_outs.append(sp.bind_outputs(sp.fn(*args)))
        waves = []
        if wave_spec is not None:
            for ds in wave_descs:
                args = [resolve(d, leaves, node_outs) for d in ds]
                with jax.named_scope(wave_spec.cls):
                    waves.append(wave_spec.bind_outputs(
                        wave_spec.fn(*args)))
        return node_outs, waves

    prog.__name__ = prog.__qualname__ = _chain_name(
        node_specs, wave_spec, len(wave_descs))
    jf = jax.jit(prog, donate_argnums=tuple(donate))
    with _chain_jit_lock:
        return _chain_jit_cache.setdefault(key, jf)


def device_put_private(payload, jdev):   # lint: alias-wrapper
    """``jax.device_put`` that GUARANTEES a private buffer.

    On the CPU client (virtual multi-device meshes, tests, the dryrun)
    ``np.asarray`` of a device array is a zero-copy view and
    ``device_put`` of an aligned host buffer is zero-copy too — so a
    cross-device "copy" can silently ALIAS the source buffer.  Donation
    or an in-place update of either side then corrupts the other: the
    r8 root cause of the intermittent geqrf wrong-R (a consumer's staged
    tile changed under it when the producer-side buffer was donated).
    Real accelerator transfers never alias (and keep their direct D2D
    path here), so the probe runs on the CPU client only: HBM addresses
    are per-device offsets, and two chips' allocators can hand out the
    SAME number for source and copy — a pointer compare there would send
    a device-to-device tile through the host for nothing."""
    import jax
    out = jax.device_put(payload, jdev)
    if jdev.platform != "cpu":
        devs = getattr(payload, "devices", None)
        if devs is not None and jdev in devs():
            # already there: device_put handed the same buffer back
            import jax.numpy as jnp
            out = jnp.array(out, copy=True)
        return out
    try:
        optr = out.unsafe_buffer_pointer()
    except Exception:
        return out   # probe unsupported on this backend: transfers copy
    sptr = _source_pointer(payload)
    if sptr is not None and optr == sptr:
        out = jax.device_put(np.asarray(payload).copy(), jdev)
    return out


def _source_pointer(payload):
    """Best-effort raw buffer pointer of a host/device payload (the
    alias probe shared by the private-put wrappers)."""
    try:
        return payload.unsafe_buffer_pointer()
    except Exception:
        iface = getattr(payload, "__array_interface__", None)
        return iface["data"][0] if iface is not None else None


def device_put_replicated_private(payload, sharding):   # lint: alias-wrapper
    """``jax.device_put`` onto a (replicating) sharding that GUARANTEES
    no shard aliases the source buffer — the multi-device sibling of
    :func:`device_put_private`.  On the CPU client the shard co-located
    with the host buffer can alias it, so a later in-place mutation or
    donation of the source would corrupt every consumer's replica (the
    same geqrf wrong-R hazard, through the broadcast path).  Real
    accelerator transfers never alias, so the probe runs on the CPU
    client only (see :func:`device_put_private`)."""
    import jax
    rep = jax.device_put(payload, sharding)
    if next(iter(sharding.device_set)).platform != "cpu":
        return rep
    sptr = _source_pointer(payload)
    if sptr is not None:
        try:
            aliased = any(s.data.unsafe_buffer_pointer() == sptr
                          for s in rep.addressable_shards)
        except Exception:
            aliased = False   # probe unsupported: transfers copy
        if aliased:
            rep = jax.device_put(np.asarray(payload).copy(), sharding)
    return rep


#: marks an LRU entry as an in-progress adopt claim (distinguishable from
#: a real accounted entry even at nbytes == 0)
_PLACEHOLDER = object()


def _item_priority(item) -> int:
    """A queued ``(task, spec, load, sig)`` item's task priority."""
    return item[0].priority


class _Inflight:
    __slots__ = ("es", "task", "spec", "outputs", "pinned", "load",
                 "release_after", "prepublished", "seq")

    def __init__(self, es, task, spec, outputs, pinned, load, release_after,
                 seq=0):
        self.es = es
        #: the launch (``mgr.launch`` span) this task rode
        self.seq = seq
        self.task = task
        self.spec = spec
        self.outputs = outputs
        self.pinned = pinned
        self.load = load
        #: host arena copies to return to their freelist once the kernel
        #: (and therefore the H2D transfer reading them) has completed
        self.release_after = release_after
        #: chain-held tasks already planted their (Deferred) payloads at
        #: hold time; the completer must not overwrite the resolution
        self.prepublished = False


class XlaDevice(Device):
    """One jax device as a runtime device module."""

    kind = "xla"

    def __init__(self, jdev, weight: float = 1.0):
        super().__init__(f"{jdev.platform}:{jdev.id}")
        self.jdev = jdev
        self.platform = jdev.platform
        self.weight = weight
        self.kind = "tpu" if self.platform == "tpu" else "xla"
        #: whether this platform donates input buffers to XLA: plain,
        #: chained and head-alone launches all read it
        self._donate = self.platform in ("tpu", "gpu", "cuda", "rocm")
        self._depth = max(1, int(params.get("device_inflight_depth", 8)))
        self._runahead = max(self._depth,
                             int(params.get("device_runahead", 256)))
        cap_mb = int(params.get("device_mem_mb", 0))
        self._capacity = cap_mb * (1 << 20) if cap_mb > 0 else None
        self._bytes_used = 0
        #: bytes of the replicas this chip holds now (stats.replica_bytes_peak
        #: is its high-water mark); under _mem_lock
        self._replica_bytes = 0
        #: (kernel name, width) -> the compiler's words, for every fused
        #: width this device asked for whose background compile failed
        #: (its waves ran as singles; XlaKernel.fuse_ready records it).
        #: benchmark/harness.py reports it; chip_smoke.py fails on it
        self.fuse_failures: Dict[Tuple[str, int], str] = {}
        #: segment ledger over the HBM budget (reference: the GPU slab
        #: zone_malloc, utils/zone_malloc.c — XLA owns physical HBM, so
        #: the zone tracks logical segments to drive eviction exactly
        #: where the reference drove cudaMalloc'd slabs)
        if self._capacity is not None:
            self._zone = None
            try:
                from parsec_tpu.native import NativeZoneAllocator, available
                if available():
                    self._zone = NativeZoneAllocator(self._capacity)
            except Exception:
                pass
            if self._zone is None:
                from parsec_tpu.utils.zone_alloc import ZoneAllocator
                self._zone = ZoneAllocator(self._capacity)
        else:
            self._zone = None
        #: datum-id -> (weakref to device copy, nbytes, zone offset);
        #: insertion order = LRU order.  Weak so per-task temporaries
        #: (NEW-flow datums) do not accumulate here forever — a finalizer
        #: drops the accounting when the copy dies with its datum.
        self._lru: "OrderedDict[int, Tuple[Any, int, Any]]" = OrderedDict()
        self._pins: Dict[int, int] = {}
        #: (shape, dtype) -> the one zeros buffer that stands in, until
        #: the kernel's output lands, for every NEW flow no kernel is
        #: handed (_stage_in): never read, never donated, so shared.
        #: The bytes a flow reserves are its OUTPUT's, which takes the
        #: blank's place in the same copy when the launch returns; the
        #: blank itself (one a shape) goes with the scratch it served
        #: (discard_scratch, fini)
        self._blanks: Dict[Tuple, Any] = {}
        # a Condition so adopt() can WAIT for a concurrent claim on the
        # same datum to resolve instead of polling (notified whenever a
        # placeholder resolves); plain `with self._mem_lock:` still works
        self._mem_lock = threading.Condition()

        self._pending: deque = deque()
        #: submitted tasks that read a head held for ANOTHER class's
        #: launch (_awaits_successor): back in front of _pending when the
        #: head resolves or its patience runs out; under _cond
        self._parked: deque = deque()
        self._inflight: deque = deque()
        #: chain-held tasks (cross-panel fused dispatch): id(task) ->
        #: _Hold, resolved when a consumer launch traces them in
        self._held: "OrderedDict[int, _Hold]" = OrderedDict()
        self._chain_cv = threading.Condition()
        self._hold_seq = 0
        #: eagerly-completed tasks whose outputs are not yet materialized
        #: on device; finalized (pins/load/arena released) as they become
        #: ready, oldest-first
        self._retire: deque = deque()
        self._launching = 0
        self._launch_seq = 0    # launches popped so far (under _cond)
        self._completing = 0
        self._finalizing = 0
        self._cond = threading.Condition()
        self._stop = False
        self.es = None   # device execution stream, set on first submit
        self._managers = [
            threading.Thread(target=self._manager_loop,
                             name=f"xla-mgr-{self.name}-{i}", daemon=True)
            for i in range(max(1, int(params.get("device_dispatchers", 2))))]
        self._completer = threading.Thread(
            target=self._completer_loop, name=f"xla-fin-{self.name}",
            daemon=True)
        for m in self._managers:
            m.start()
        self._completer.start()

    # ------------------------------------------------------------------
    # submit: worker thread -> device ownership (HOOK_RETURN_ASYNC)
    # ------------------------------------------------------------------
    def submit(self, es, task: Task, spec: XlaKernel) -> HookReturn:
        flops = task.task_class.properties.get("flops", 1.0)
        load = float(flops(task.locals)) if callable(flops) else float(flops)
        self.load_add(load)
        # the fusion signature rides the queued item: computed ONCE a
        # task, here on the submitting worker's thread and outside
        # ``_cond``, and only compared by the managers' wave scan.  A
        # panel-chain link (POTRF, GEQRT, TSQRT) has none: it goes alone
        # (see _pop_wave_locked)
        sig = None if task.task_class.properties.get("fuse_chain") \
            else spec.task_sig(task)
        item = (task, spec, load, sig)
        batch = es.hand_in
        if batch is not None:
            # a releasing thread's direct hand-in (core/scheduling.
            # schedule): queued with the rest of its call or pass by
            # enqueue
            batch.append(item)
            return HookReturn.ASYNC
        with self._cond:
            if self.es is None:
                self._make_stream_locked(es.context)
            self._pending.append(item)
            self._cond.notify_all()
        return HookReturn.ASYNC

    def enqueue(self, es, items: List[Tuple]) -> None:
        """Queue what a releasing thread handed in (``submit`` items: one
        ``schedule()`` call's, or a completer pass's) in the order the
        ready queue would have popped them — priority, first come first
        among equals — under ONE hold of ``_cond`` and one wake-up."""
        items.sort(key=_item_priority, reverse=True)   # stable
        with self._cond:
            if self.es is None:
                self._make_stream_locked(es.context)
            self._pending.extend(items)
            self.stats.direct_submits += len(items)
            self._cond.notify_all()

    def _make_stream_locked(self, ctx) -> None:
        """The device's execution stream: the managers' spans and the
        completer's releases run on it.  The completer owns it for the
        direct hand-in of the tasks its releases make ready.  Caller
        holds ``_cond``."""
        from parsec_tpu.core.context import ExecutionStream
        self.es = ExecutionStream(ctx, th_id=900 + self.space)
        self.es.releaser = self._completer.ident

    # ------------------------------------------------------------------
    # manager: stage-in + dispatch (reference: parsec_cuda_kernel_push /
    # submit phases of the manager state machine)
    # ------------------------------------------------------------------
    def _manager_loop(self):
        while True:
            with self._cond:
                first = self._take_first_locked()
                if first is None and not self._stop:
                    # one span an episode, however many wake-ups
                    self.stats.starved_waits += 1
                    with open_span(self.es, "mgr.starved", dev=self.name):
                        while first is None and not self._stop:
                            self._cond.wait(0.1)
                            first = self._take_first_locked()
                if first is None:
                    return
                seq = self._launch_seq = self._launch_seq + 1
                launch = open_span(self.es, "mgr.launch", dev=self.name,
                                   seq=seq)
                with open_span(self.es, "mgr.pop_wave"):
                    batch = self._pop_wave_locked(first)
                self._launching += 1
            try:
                if launch is not SPAN_OFF:
                    task0 = batch[0][0]
                    late = {"pool": task0.taskpool.taskpool_id,
                            "cls": task0.task_class.name,
                            "n": len(batch), "held": 0}
                    ready = [item[0].ready_at for item in batch
                             if item[0].ready_at is not None]
                    if ready:
                        late["wait_us"] = int(
                            (_time.perf_counter() - min(ready)) * 1e6)
                    launch.late = late
                if _fi.ARMED:
                    # fault plan delay_dispatch: perturb the manager /
                    # completer interleaving deterministically
                    _fi.device_delay()
                if self._launch(batch, seq) and launch is not SPAN_OFF:
                    late["held"] = 1
            except Exception as exc:   # stage-in/compile failure
                from parsec_tpu.core import scheduling
                self.stats.faults += 1
                for item in batch:
                    self.load_sub(item[2])
                rescued = self._degrade([item[0] for item in batch], exc)
                if not rescued:
                    for item in batch:
                        self.es.context.record_error(exc, item[0])
                        scheduling.complete_execution(self.es, item[0],
                                                      failed=True)
            finally:
                launch.end()
                with self._cond:
                    self._launching -= 1
                    self._cond.notify_all()

    def _awaits_successor(self, task: Task) -> bool:
        """Whether ``task`` reads the output of a head that is held here
        for the launch of ANOTHER class (its declared successor) and has
        not run out of patience: such a task waits its turn in
        ``_parked``, so that which program a head goes out in does not
        depend on which of its consumers reached the device first.
        Caller holds ``_cond``."""
        if not self._held or self._stop:
            return False
        name = task.task_class.name
        now = _time.monotonic()
        for copy in task.data.values():
            p = copy.payload if copy is not None else None
            if isinstance(p, Deferred) and p.array is None:
                hd = p.hold
                if hd.device is not self or hd.succ in (None, name) \
                        or hd.state != "held":
                    continue
                if self._inflight or self._retire:
                    hd.deadline = now + _HOLD_PATIENCE_S   # chip in work
                if now < hd.deadline:
                    return True
        return False

    def _take_first_locked(self):
        """The next queued task that may launch now, or None: parked
        tasks whose wait is over go back in front of the queue first,
        in their old order, and tasks that have to wait for a held
        head's successor are parked.  Caller holds ``_cond``."""
        if self._parked:
            self._pending.extendleft(reversed(self._parked))
            self._parked.clear()
        while self._pending:
            item = self._pending.popleft()
            if not self._awaits_successor(item[0]):
                return item
            self._parked.append(item)
        return None

    def _pop_wave_locked(self, first):
        """``first`` plus every queued same-class sibling it can fuse
        with (same kernel spec, equal signature — ``XlaKernel.task_sig``:
        equal non-flow args, matching payload shapes and dtypes — as
        ``submit`` queued it), up to ``device_fuse`` (wavefront launch
        fusion; reference analog: the GPU manager draining its pending
        FIFO into the exec streams, device_cuda_module.c:2697 — here the
        drain fuses the wave into one XLA program).  Non-matching entries
        keep their queue order.  Caller holds ``_cond``."""
        limit = int(params.get("device_fuse", 8))
        spec, sig = first[1], first[3]
        if limit <= 1 or sig is None:
            # no signature: a flow unbound, a static that does not hash,
            # or a panel-chain link (POTRF, GEQRT, TSQRT), which goes
            # alone: its queued siblings sit on OTHER panels' serial
            # chains, which links meet is timing, and a wave of them
            # costs the heaviest kernel's compile once more per width
            # (seen on a v5e: a two-wide TSQRT wave at mb=6144, ~250 s
            # and a 180 MB executable, PERF.md PR 21)
            return [first]
        window = float(params.get("device_fuse_window_ms", 0.0)) * 1e-3
        if not self._pending and window <= 0:
            return [first]
        batch = [first]
        rest = []
        deadline = _time.monotonic() + window
        while True:
            # bound each scan at a small multiple of the fuse width: the
            # lock is shared with submit()/sync(), so an unbounded walk
            # over a deep mixed-class queue would serialize workers
            scan_budget = 4 * limit
            while self._pending and len(batch) < limit \
                    and scan_budget > 0:
                scan_budget -= 1
                cand = self._pending.popleft()
                if cand[1] is spec and cand[3] == sig \
                        and not self._awaits_successor(cand[0]):
                    batch.append(cand)
                else:
                    rest.append(cand)
            if len(batch) >= limit or window <= 0:
                break
            remaining = deadline - _time.monotonic()
            if remaining <= 0:
                break
            # sibling-batching window: readiness arrives in bursts
            # (deps release eagerly at dispatch), so a short wait
            # consolidates the burst into one wide program.  Requeue the
            # skipped non-matching entries BEFORE waiting — the wait
            # releases _cond and the other manager must be able to
            # dispatch those other-class tasks meanwhile.
            for item in reversed(rest):
                self._pending.appendleft(item)
            rest = []
            self._cond.wait(min(remaining, 0.002))
            if self._stop:
                break
        # quantize to the largest power of two <= wave size: each distinct
        # fused width is a separate XLA compile, so arbitrary widths would
        # keep tripping fresh compiles mid-run; powers of two cap the
        # variety at log2(device_fuse) per kernel
        quant = 1 << (len(batch).bit_length() - 1)
        # requeue order: skipped non-matching entries first (restoring
        # their queue positions), then the quantization extras IN FRONT so
        # they lead the next wave and can fuse with arriving siblings
        for item in reversed(rest):
            self._pending.appendleft(item)
        for item in reversed(batch[quant:]):
            self._pending.appendleft(item)
        batch = batch[:quant]
        return batch

    def _degrade(self, tasks: List[Task], exc: Exception) -> bool:
        """Degraded mode (the reference's ONLY fault tolerance: device
        errors disable the device and push tasks back to the CPU
        incarnation, PARSEC_HOOK_RETURN_DISABLE /
        device_cuda_module.c:2757-2762).  After ``device_max_faults``
        launch failures the device disables itself and the failing tasks
        — plus everything still queued here — reschedule to fall
        through to the next incarnation.  Returns True when the tasks
        were rescued."""
        limit = int(params.get("device_max_faults", 0))
        if limit <= 0 or self.es is None:
            return False      # unguarded: the fault fails the context
        from parsec_tpu.core import scheduling
        from parsec_tpu.utils.output import warning
        rescued = list(tasks)
        with self._cond:
            if self.stats.faults >= limit and self.enabled:
                # past the limit: stop taking work and drain the queue
                # back to the scheduler for other incarnations
                self.enabled = False
                warning("device %s disabled after %d faults (%s); "
                        "falling back to other incarnations", self.name,
                        self.stats.faults, exc)
            if not self.enabled:
                while self._pending:
                    qtask, _spec, qload, _sig = self._pending.popleft()
                    self.load_sub(qload)
                    rescued.append(qtask)
        for t in rescued:
            t.status = scheduling.TaskStatus.READY
        scheduling.schedule(self.es, rescued)
        return True

    def _launch(self, batch, seq: int = 0) -> bool:
        """Stage and dispatch one wave: a list of (task, spec, load, sig)
        with a shared kernel spec and ONE signature (len 1 = the plain
        single-task launch).  The whole wave rides ONE jitted call
        (XlaKernel.jitted_fused), so a k-wide TRSM/SYRK/GEMM wavefront
        costs one dispatch round trip.
        ``seq`` is the launch's number on this device; returns True
        where the wave was a chain head that was held, not dispatched."""
        spec: XlaKernel = batch[0][1]
        n = len(batch)
        #: pins and deferred arena releases stay PER TASK: each inflight
        #: entry holds only its own, so finalizing one entry of a fused
        #: wave cannot unpin a sibling's datums before that sibling's
        #: completion ran (a concurrent dispatcher's _reserve would evict
        #: the still-live copy)
        pinned_per: List[List[Any]] = []
        release_per: List[List[DataCopy]] = []
        flat: List[Any] = []
        chained = False
        try:
            stage = open_span(self.es, "mgr.stage_in")
            bytes0 = self.stats.bytes_in
            # pin every datum the wave touches before any eviction
            # decision, and freshen it in the LRU
            self._pin_wave(batch, pinned_per)
            for item in batch:
                task = item[0]
                data = task.data
                pinned_flows = task.pinned_flows
                staged: Dict[str, Any] = {}
                release_after: List[DataCopy] = []
                release_per.append(release_after)
                for flow in task.task_class.flows:
                    name = flow.name
                    copy = data.get(name)
                    if copy is None:
                        continue
                    dc = self._stage_in(copy, flow.access,
                                        name in pinned_flows,
                                        name in spec.flow_args)
                    if dc is not copy:
                        if copy.device == 0 and copy.arena is not None:
                            # host arena temp fully superseded by the
                            # device copy: return it to the freelist once
                            # the kernel completes (the H2D transfer may
                            # still read it)
                            copy.data.detach_copy(0)
                            release_after.append(copy)
                        data[name] = dc
                    p = dc.payload
                    if p.__class__ is Deferred:
                        # an already-resolved chain placeholder
                        # substitutes transparently
                        if p.array is not None:
                            p = p.array
                        else:
                            chained = True
                    staged[name] = p
                for a in spec.arg_names:
                    if a in staged:
                        flat.append(staged[a])
                    elif a in task.locals:
                        flat.append(task.locals[a])
                    else:
                        flat.append(task.taskpool.globals.get(a))
            # (with two managers the delta can hold the other's bytes)
            stage.end(bytes_in=self.stats.bytes_in - bytes0)
            stage = SPAN_OFF
            if n == 1 and spec.writable and not chained \
                    and self._chain_eligible(batch[0][0], spec):
                # chain head (POTRF(k), TSQRT(m,k)...): hold instead of
                # dispatching — deps release eagerly through the normal
                # completer path with Deferred payloads, and the kernel
                # is traced into its successor's launch.  A link that
                # reads a held head is that launch, never a second hold:
                # one head a program, whatever the column's length
                self.stats.held_tasks += 1
                self._hold_task(batch[0], flat, pinned_per[0],
                                release_per[0], seq)
                return True
            # a panel-chain link (POTRF, GEQRT, TSQRT) is the class whose
            # every width is a Cholesky-class compile: not for cover()
            cover = not batch[0][0].task_class.properties.get("fuse_chain")
            if chained:
                outs_per_task = self._dispatch_chained(
                    spec, n, flat, cover, batch[0][0].task_class.name,
                    batch[0][3])
                fused = False
            else:
                fused, outs_per_task = self._dispatch_plain(
                    spec, n, flat, cover, batch[0][3])
            if fused:
                # count only waves the fused program actually executed —
                # a de-fused n>1 wave (fuse_ready False) ran singles
                self.stats.fused_launches += 1
                self.stats.fused_tasks += n
        except Exception:
            stage.end()
            self._unpin_all(d for pinned in pinned_per for d in pinned)
            # arena copies already detached for deferred release would
            # otherwise leak on the failure path (ADVICE r1 low);
            # release_unheld: a chained NEW-flow buffer a predecessor's
            # repo entry still holds must wait for that retirement
            for release_after in release_per:
                for copy in release_after:
                    copy.arena.release_unheld(copy)
            raise
        self.stats.executed_tasks += n
        if self.es.context._device_spans:
            # device span opens at dispatch (the wave just entered the
            # accelerator pipeline); the matching device_done fires when
            # the outputs materialize (_finalize) — together the
            # dispatch->done device segment of the causal trace (and of
            # the flight recorder's incident ring).  The gate is
            # maintained by Context._recompute_ready_stamp, so a
            # recorder whose classes exclude 'device' costs nothing
            for item in batch:
                self.es.pins("device_dispatch", item[0])
        with self._cond:
            self._wait_room_locked(n)
            for i, item in enumerate(batch):
                self._inflight.append(
                    _Inflight(self.es, item[0], spec, outs_per_task[i],
                              pinned_per[i], item[2], release_per[i], seq))
            self._cond.notify_all()
        return False

    def _wait_room_locked(self, n: int) -> None:
        """Wait until ``n`` more entries fit under the inflight depth.
        The gate is on the WHOLE wave fitting: appending n entries after
        a <depth check would let the window exceed device_inflight_depth
        by fuse-width-1 and under-account HBM backpressure (ADVICE r3
        low).  Caller holds ``_cond``."""
        room = max(self._depth - n, 0)   # n>depth: drain fully first
        if len(self._inflight) > room and not self._stop:
            self.stats.inflight_waits += 1
            with open_span(self.es, "mgr.inflight_wait"):
                while len(self._inflight) > room and not self._stop:
                    self._cond.wait(0.1)

    def _call(self, jf, args):
        """One jitted call: one program on the device's queue, one
        ``mgr.dispatch`` span.  ``first`` marks the first call of this
        callable on this chip, in which a trace and a compile (or a read
        of the persistent cache) hides."""
        ran = getattr(jf, "_parsec_ran", None)
        if ran is None:
            ran = jf._parsec_ran = set()
        first = self.jdev.id not in ran
        if first:
            ran.add(self.jdev.id)
            self.stats.compiles += 1
        self.stats.launches += 1
        with open_span(self.es, "mgr.dispatch", program=_program_name(jf),
                       first=int(first)):
            return jf(*args)

    def _dispatch_plain(self, spec: XlaKernel, n: int, flat: List[Any],
                        cover: bool = False, sig: Any = None):
        """The pre-existing dispatch path: one (possibly width-fused)
        jitted call over real arrays.  Returns (fused, bound outputs per
        task).  ``cover``: the class is one whose widths may be warmed
        on every chip (``_FuseWarmer.cover``); ``sig``: the signature
        the wave's members were queued under (``XlaKernel.task_sig``),
        where the caller has it."""
        donate = self._donate and not self._donation_hazard(spec, flat)
        ici = self.es.context.ici if cover and n > 1 else None
        if ici is not None:
            # several chips, and a class that meets in waves: what this
            # chip runs of it, every chip will
            _fuse_warmer.cover(spec, donate, n, flat, self,
                               ici.xla_devices, sig)

        if n == 1:
            fused, results = False, [self._call(spec.jitted(donate), flat)]
        elif not spec.fuse_ready(donate, n, flat, self, sig):
            # the fused width is still compiling in the background
            # (Cholesky-class programs take tens of seconds), or its
            # compile failed (fuse_failures says why): dispatch singles
            # now — the wave fuses once the width is warm
            self.stats.defused_waves += 1
            k = len(spec.arg_names)
            jf = spec.jitted(donate)
            fused, results = False, [self._call(jf, flat[i * k:(i + 1) * k])
                                     for i in range(n)]
        else:
            fused, results = True, list(
                self._call(spec.jitted_fused(donate, n), flat))
        return fused, [spec.bind_outputs(r) for r in results]

    # ------------------------------------------------------------------
    # cross-panel chain fusion: hold chain heads, trace them into their
    # consumer wave's launch
    # ------------------------------------------------------------------
    def _chain_eligible(self, task: Task, spec: XlaKernel) -> bool:
        """Whether this task may be chain-held: its class names a
        'fuse_chain' (flow, successor class), the run is single-rank
        (remote activations must never see a Deferred payload), and the
        chain flow has at least one task successor to force the eventual
        launch — all of them, where the context drives several chips,
        expected on this one."""
        fc = task.task_class.properties.get("fuse_chain")
        if not fc:
            return False
        tp = task.taskpool
        ctx = getattr(tp, "context", None)
        if ctx is None or getattr(ctx, "nranks", 1) > 1:
            return False
        flow_name = fc[0] if isinstance(fc, (tuple, list)) else fc
        flow = task.task_class.flow(flow_name)
        if flow is None:
            return False
        ici = ctx.ici
        expects = getattr(tp, "expects_successor", None)
        if expects is not None:
            # a discovered graph (dsl/dtd): the flows declare no
            # successor, the pool knows which were inserted.  Several
            # chips: nobody has looked where discovery places a chain
            return ici is None and expects(task, _declared_successor(fc))
        from parsec_tpu.core.task import ToTask
        found = False
        try:
            for dep in flow.active_outputs(task.locals):
                if isinstance(dep.end, ToTask):
                    if ici is None:
                        for _ in dep.end.instances(task.locals):
                            return True
                        continue
                    # several chips: a head is held only where the whole
                    # chain stays on its chip.  A consumer elsewhere
                    # would force the chain alone, so which programs a
                    # panel runs (head alone, head + wave of 1, 2, 4, 8)
                    # would be a matter of timing between the chips, each
                    # a Cholesky-class compile; dispatched plainly, the
                    # head is one program and its output a real tile
                    # that ICI moves at once
                    succ_tc = tp.task_classes[dep.end.task_class]
                    for loc in dep.end.instances(task.locals):
                        if ici.predicted_space(
                                succ_tc, succ_tc.complete_locals(loc)) \
                                != self.space:
                            return False
                        found = True
        except Exception:
            return False
        return found

    def _hold_task(self, item, flat, pinned, release_after, seq=0) -> None:
        """Park a chain head: its outputs become Deferred payloads on
        the already-staged copies, and the task completes eagerly
        through the normal completer path (deps release, successors
        instantiate) without any dispatch."""
        task, spec, load = item[:3]
        h = _Hold()
        h.device = self
        h.task = task
        h.spec = spec
        h.flat = list(flat)
        h.state = "held"
        # (flow, successor class); a bare flow name takes any consumer
        h.succ = _declared_successor(
            task.task_class.properties["fuse_chain"])
        h.deadline = _time.monotonic() + _HOLD_PATIENCE_S
        h.outputs = {}
        for fl in spec.writable:
            dc = task.data.get(fl)
            p = dc.payload if dc is not None else None
            d = Deferred(h, fl, tuple(getattr(p, "shape", ()) or ()),
                         getattr(p, "dtype", None))
            h.outputs[fl] = d
            if dc is not None:
                dc.payload = d
        with self._chain_cv:
            self._hold_seq += 1
            h.seq = self._hold_seq
            self._held[id(task)] = h
        inf = _Inflight(self.es, task, spec, h.outputs, pinned, load,
                        release_after, seq)
        inf.prepublished = True
        with self._cond:
            self._wait_room_locked(1)
            self._inflight.append(inf)
            self._cond.notify_all()

    def _claim(self, hd: _Hold) -> bool:
        """Take a held head for launching; False once it has resolved.
        Waits while another thread is launching it (that launch either
        resolves it or hands it back: ``_unclaim``)."""
        with self._chain_cv:
            while hd.state == "launching":
                self._chain_cv.wait(0.1)
            if hd.state == "resolved":
                return False
            hd.state = "launching"
            return True

    def _run_chain(self, hd: _Hold, wave_spec, n, flat):
        """Trace the claimed head and its successor wave into ONE
        jitted program and dispatch it.  A leaf is donated to XLA only
        when it feeds a WRITTEN flow position and appears exactly once
        in the whole program (the usage count is the chained analog of
        _donation_hazard) — in-place tile updates keep their HBM
        headroom on chained panel waves too."""
        leaves: List[Any] = []
        leaf_ix: Dict[int, int] = {}
        leaf_uses: Dict[int, int] = {}
        donatable: set = set()

        def desc(a, writable=False):
            if isinstance(a, Deferred):
                if a.array is not None:
                    a = a.array
                else:
                    if a.hold is not hd:     # the caller's failure path
                        raise KeyError("placeholder of another head")
                    return ("n", 0, a.flow)
            if hasattr(a, "shape") and hasattr(a, "dtype"):
                j = leaf_ix.get(id(a))
                if j is None:
                    j = leaf_ix[id(a)] = len(leaves)
                    leaves.append(a)
                leaf_uses[j] = leaf_uses.get(j, 0) + 1
                if writable:
                    donatable.add(j)
                return ("l", j)
            return ("s", a)

        def spec_descs(sp, args):
            wr = [a in sp.flow_names and a in sp.writable
                  for a in sp.arg_names]
            return tuple(desc(a, wr[i]) for i, a in enumerate(args))

        node_descs = [spec_descs(hd.spec, hd.flat)]
        k = len(wave_spec.arg_names)
        wave_descs = tuple(spec_descs(wave_spec, flat[t * k:(t + 1) * k])
                           for t in range(n))
        # a donated buffer may be overwritten by its first reader, so a
        # chain donates only the leaves its program reads once
        donate = tuple(sorted(j for j in donatable
                              if leaf_uses.get(j) == 1)) \
            if self._donate else ()
        key = (((hd.spec.fn, hd.spec.cls, node_descs[0]),),
               (wave_spec.fn, wave_spec.cls), wave_descs, donate)
        hash(key)    # unhashable static -> the caller's failure path
        with _chain_jit_lock:
            built = key not in _chain_jit_cache
        jf = _chain_jitted(key, [hd.spec], node_descs, wave_spec,
                           wave_descs, donate)
        (head_outs,), wave_outs = self._call(jf, leaves)
        self.stats.chain_programs += built
        self.stats.chained_launches += 1
        self.stats.chained_tasks += 1 + n
        return head_outs, wave_outs

    def _run_alone(self, hd: _Hold) -> Dict[str, Any]:
        """Dispatch a claimed head through its class's plain program
        (``jit_parsec_<CLS>``, the one a link that is never held runs):
        a head forced without its successor adds no program."""
        flat = [a.array if isinstance(a, Deferred) else a for a in hd.flat]
        donate = self._donate \
            and not self._donation_hazard(hd.spec, flat)
        return hd.spec.bind_outputs(self._call(hd.spec.jitted(donate), flat))

    def _resolve_hold(self, hd: _Hold, outs) -> None:
        """Publish a dispatched head's outputs: fill every Deferred and
        swap the placeholder payloads for the real (asynchronous)
        arrays, then wake claim-waiters."""
        with self._chain_cv:
            for fl, arr in outs.items():
                d = hd.outputs.get(fl)
                if d is not None:
                    d.array = arr
                dc = hd.task.data.get(fl)
                # identity check, not isinstance: on an RW chain the
                # SAME copy carries successive holds' placeholders
                # (TSQRT column T), and resolving an earlier link
                # must not regress the payload over a later one
                if dc is not None and dc.payload is d:
                    dc.payload = arr
            hd.state = "resolved"
            hd.flat = None          # release the leaf input buffers
            self._held.pop(id(hd.task), None)
            self._chain_cv.notify_all()
        with self._cond:
            if self._parked:            # their wait is over
                self._cond.notify_all()

    def _unclaim(self, hd: _Hold) -> None:
        with self._chain_cv:
            hd.state = "held"
            self._chain_cv.notify_all()

    def _dispatch_chained(self, spec: XlaKernel, n: int, flat: List[Any],
                          cover: bool, cls: str,
                          sig: Any = None) -> List[Dict[str, Any]]:
        """Launch a wave of class ``cls`` whose inputs include unresolved
        chain placeholders.  ONE head held for this class is traced in
        front of the wave in one program (``jit_parsec_chain_<HEAD>__
        <cls>_x<n>``) and resolved from the same launch; every other
        head the wave reads — held for another class whose launch did
        not come in time, or a second one for this class — is forced
        alone first.  So the chain programs a taskpool can ask for are
        its (declaring class, declared successor, fused width) triples,
        whatever its size and whoever arrived first.  Returns the
        wave's bound outputs per task."""
        while True:
            holds = {id(a.hold): a.hold for a in flat
                     if isinstance(a, Deferred) and a.array is None}
            mine = [hd for hd in holds.values() if hd.succ in (None, cls)]
            head = min(mine, key=lambda hd: hd.seq) if mine else None
            for hd in holds.values():
                if hd is not head:
                    self._force_hold(hd)
            claimed = head is not None and self._claim(head)
            # heads resolved meanwhile substitute transparently
            flat = [a.array if isinstance(a, Deferred)
                    and a.array is not None else a for a in flat]
            if not claimed:
                if any(isinstance(a, Deferred) for a in flat):
                    continue          # raced a fresh hold: look again
                _f, outs = self._dispatch_plain(spec, n, flat, cover, sig)
                return outs
            try:
                head_outs, wave_outs = self._run_chain(head, spec, n, flat)
            except Exception:
                self._unclaim(head)
                raise
            self._resolve_hold(head, head_outs)
            return wave_outs

    def _force_hold(self, hd: _Hold) -> None:
        """Dispatch a held head alone, without its successor (a consumer
        of another class whose patience ran out, foreign-device/CPU
        consumers, sync, teardown); counted in ``heads_forced``."""
        if not self._claim(hd):
            return                    # resolved concurrently
        try:
            outs = self._run_alone(hd)
        except Exception:
            self._unclaim(hd)
            raise
        self.stats.heads_forced += 1
        self._resolve_hold(hd, outs)

    def _resolve_all_held(self) -> None:
        """Force every remaining hold (sync/teardown): consumers that
        never reached this device must not leave a panel chain
        undispatched."""
        deadline = _time.monotonic() + 60.0
        while True:
            with self._chain_cv:
                pending = [hd for hd in self._held.values()
                           if hd.state == "held"]
                busy = any(hd.state == "launching"
                           for hd in self._held.values())
            if pending:
                self._force_hold(pending[0])
                continue
            if not busy:
                return
            if _time.monotonic() > deadline:
                raise TimeoutError(
                    f"device {self.name}: chain holds stuck in launch")
            _time.sleep(0.002)

    @staticmethod
    def _donation_hazard(spec: XlaKernel, flat: List[Any]) -> bool:
        """True when a to-be-donated buffer also appears as another
        argument of the same (possibly fused) call: two wave tasks
        sharing an operand where one donates it would hand XLA the same
        buffer as both alias-donated and live input.  Falling back to
        no-donation for the launch is always safe.  One pass over the
        wave's arguments, the donated positions (``spec.donate_pos``)
        first."""
        dpos = spec.donate_pos
        if not dpos:
            return False
        k = len(spec.arg_names)
        bases = range(0, len(flat), k)
        donated = {id(flat[b + i]) for b in bases for i in dpos}
        if len(donated) < len(bases) * len(dpos):
            return True         # one buffer at two donated positions
        keep = spec._keep_pos
        return any(id(flat[b + i]) in donated for b in bases for i in keep)

    def _stage_in(self, copy: DataCopy, access: int,
                  pinned: bool = False, handed: bool = True) -> DataCopy:
        """Ensure a valid copy of ``copy``'s datum on this device
        (reference: parsec_gpu_data_stage_in, device_cuda_module.c:1261).
        The caller has pinned the datum and freshened it in the LRU
        (``_pin_wave``); ``handed`` says whether the kernel is handed the
        flow's payload or only returns it.

        The resident case comes first: the device's copy is there, valid
        at the newest version, and the bound copy is neither a snapshot
        nor a COW alias nor NEW-arena scratch — one hold of the datum's
        lock (``Data.acquire_on``) says so and applies the access's
        coherency transition, and the copy goes out as it is
        (``resident_flows``).  Everything else is staged
        (``staged_flows``) by the code below.

        A bound copy that a writeback replacement detached — or, for a
        task-fed (pinned) input, invalidated in place — is a
        version-pinned snapshot; it stages into a private standalone
        device copy without consulting the datum's coherency, which has
        moved on.  (A detached copy with payload None was merely evicted
        and re-stages from the datum's newest valid copy below.)"""
        datum = copy.data
        p0 = copy.payload
        held_here = False
        if p0.__class__ is Deferred:
            if p0.array is not None:
                copy.payload = p0.array     # resolved: unwrap in place
            elif p0.hold.device is not self:
                # produced by a chain held on ANOTHER device: force that
                # chain there, then stage the real array normally (D2D)
                copy.payload = p0.force()
            else:
                # this device's launch traces the chain into the
                # consuming program (_dispatch_chained): the placeholder
                # stays, unless a private buffer has to be made from it
                held_here = True
        flags = copy.flags
        acquired = not flags & (FLAG_COW | FLAG_SCRATCH)
        if acquired:
            dc, snapshot, src = datum.acquire_on(self.space, access, copy,
                                                 pinned)
            if dc is not None and src is None and not snapshot \
                    and dc.payload is not None:
                self.stats.resident_flows += 1
                return dc
        else:
            dc = src = None
            snapshot = not flags & FLAG_COW \
                and copy.is_pinned_snapshot(pinned)
        self.stats.staged_flows += 1
        if held_here and (flags & FLAG_COW or snapshot):
            # snapshot/COW paths materialize private buffers from
            # the payload — they need the real array
            copy.payload = p0.force()
        import jax
        if flags & FLAG_SCRATCH and copy.version == 0 \
                and access & ACCESS_WRITE and copy.arena is not None:
            # NEW-flow scratch straight from the arena: the np.empty host
            # buffer's content is undefined, so materialize the copy
            # directly in device memory (zeros) instead of paying an H2D
            # transfer for garbage bytes
            import jax.numpy as jnp
            nbytes = getattr(copy.payload, "nbytes", 0)
            off = self._reserve(nbytes)
            dc = datum.copy_on(self.space)
            if dc is None:
                dc = datum.create_copy(self.space)
            shape = copy.payload.shape
            dtype = copy.payload.dtype
            blank = None if handed else self._blanks.get((shape, dtype))
            if blank is None:
                blank = jax.device_put(   # lint: private-ok (a fresh
                    # jnp.zeros has no host-side owner to alias)
                    jnp.zeros(shape, dtype=dtype), self.jdev)
                if not handed:
                    # a flow the kernel only returns: nothing reads or
                    # donates what stands here until the output lands,
                    # so every such flow of this shape shares one
                    # buffer and costs no dispatch of its own
                    self._blanks[(shape, dtype)] = blank
            dc.payload = blank
            dc.version = copy.version
            datum.transfer_ownership(self.space, access)
            self._account(datum, dc, nbytes, off)
            self._touch(datum)
            return dc
        if snapshot:
            payload = copy.payload
            nbytes = getattr(payload, "nbytes", 0)
            off = self._reserve(nbytes)
            if self._on_this_device(payload):
                import jax.numpy as jnp
                staged = jnp.array(payload, copy=True)
            else:
                staged = device_put_private(payload, self.jdev)
                if copy.arena is not None:
                    # eager completion can retire (and recycle) the arena
                    # host buffer before this async H2D drains: wait it out
                    staged.block_until_ready()
            snap = Data(nb_elts=datum.nb_elts)
            dc = snap.create_copy(self.space, payload=staged,
                                  coherency=Coherency.SHARED,
                                  version=copy.version)
            self.stats.bytes_in += nbytes
            self.stats.snapshot_flows += 1
            self.stats.snapshot_bytes += nbytes
            self._account(snap, dc, nbytes, off)
            return dc
        if not acquired:
            dc = datum.copy_on(self.space)
        fresh = dc is None
        if fresh:
            dc = datum.create_copy(self.space)
        if fresh or not acquired:
            src = datum.transfer_ownership(self.space, access)
        if src is not None or dc.payload is None:
            payload = src.payload if src is not None else copy.payload
            nbytes = getattr(payload, "nbytes", 0)
            # only a FRESH copy claims a zone segment: a re-staged copy
            # already owns one, and a surplus claim could evict victims
            # (or spuriously exhaust the budget) for nothing
            off = self._reserve(nbytes) if fresh else None
            if self._on_this_device(payload):
                # already resident (copy-on-write alias): device_put would
                # be a no-op sharing the buffer, which donation/in-place
                # update must not see — make a private HBM buffer
                import jax.numpy as jnp
                dc.payload = jnp.array(payload, copy=True)
            else:
                # cross-device/host staging must be private too: on the
                # CPU client a plain device_put ALIASES the source
                # buffer (see device_put_private — the r8 wrong-R root
                # cause)
                dc.payload = device_put_private(payload, self.jdev)
                if (src.arena if src is not None else copy.arena) \
                        is not None:
                    # see the snapshot path above: don't let an eager
                    # retirement recycle the arena buffer mid-H2D
                    dc.payload.block_until_ready()
            dc.version = src.version if src is not None else copy.version
            self.stats.bytes_in += nbytes
            if flags & FLAG_COW:
                self.stats.snapshot_flows += 1
                self.stats.snapshot_bytes += nbytes
            if fresh:
                self._account(datum, dc, nbytes, off)
            if not access & ACCESS_WRITE:
                self._note_replica(datum, dc)
        if flags & FLAG_COW and copy is not dc:
            # The COW alias's payload aliases the producer's buffer (for
            # DATA-fed fan-outs: the collection's backing array).  The
            # device copy above is private, so drop the alias from the
            # datum NOW — otherwise flush()/_evict() later treats this
            # datum's device copy as authoritative and pull_to_host
            # np.copyto's an intermediate result through the alias into
            # the shared storage (ADVICE r1 high: the stencil corruption).
            datum.detach_copy(copy.device)
            copy.payload = None
            copy.coherency = Coherency.INVALID
            copy.flags &= ~FLAG_COW
        self._touch(datum)
        return dc

    def _on_this_device(self, payload) -> bool:
        devs = getattr(payload, "devices", None)
        if devs is None:
            return False
        try:
            return self.jdev in devs()
        except TypeError:
            return False

    # ------------------------------------------------------------------
    # completer: EAGER completion on dispatch order (reference:
    # parsec_cuda_kernel_pop/epilog + progress_stream events — but where
    # the CUDA module must poll events before releasing deps, XLA
    # dispatch returns asynchronous arrays that successors may consume
    # directly: the dependency is enforced by dataflow ON DEVICE, so
    # deps are released immediately and the Python side runs ahead,
    # keeping the device pipeline full).  Pins, arena buffers and load
    # accounting are held until the outputs actually materialize
    # (_finalize), with a bounded run-ahead window of unmaterialized
    # tasks providing backpressure.
    # ------------------------------------------------------------------
    def _completer_loop(self):
        """One PASS a turn: whatever the managers have handed over is
        taken under one hold of ``_cond``, released task by task outside
        it, retired under one more hold and drained (probed, finalized)
        once.  A pass of one entry is the per-task loop this replaced;
        its size follows the queue."""
        from parsec_tpu.core import scheduling
        while True:
            with self._cond:
                if not self._inflight and not self._stop:
                    with open_span(self.es, "fin.idle", dev=self.name):
                        while not self._inflight and not self._stop:
                            self._cond.wait(0.1)
                            if self._retire and not self._inflight:
                                break   # idle tick: see the drain
                if not self._inflight and self._stop:
                    break   # stopped and drained
                # from the head, in dispatch order, and never past the
                # valve: released and unfinalized together stay within
                # device_runahead + 1, as one entry a turn kept them
                take = min(len(self._inflight),
                           max(1, self._runahead - len(self._retire)))
                batch = [self._inflight.popleft() for _ in range(take)]
                if batch:
                    # _completing keeps the pass visible to sync() between
                    # the queue pop and the retire: complete_execution
                    # below is what wakes Context.wait, which may race
                    # into sync()
                    self._completing += take
                    self._cond.notify_all()   # room was made
            if batch:
                self.stats.release_passes += 1
                es = self.es
                with open_span(es, "fin.pass", n=take):
                    # one chip: the device tasks the pass's releases make
                    # ready are handed in here (core/scheduling.schedule)
                    # and queued together at its end, in the priority
                    # order the ready queue would have popped them
                    handed = [] if es.context.direct_device is self \
                        else None
                    es.hand_in = handed
                    try:
                        for inf in batch:
                            self._release(inf, scheduling)
                    finally:
                        es.hand_in = None
                        if handed:
                            self.enqueue(es, handed)
                    with self._cond:
                        self._retire.extend(batch)
                        self._completing -= take
                        self._cond.notify_all()
            # ONE probe and one finalization a pass.  With no batch it is
            # the idle tick: nothing in flight, but retired tasks still
            # hold their pins: their outputs were not ready when last
            # probed, and nothing would probe again before the NEXT
            # dispatch — which may itself be waiting in _reserve for
            # exactly those pins (tight device_mem_mb + a slow or busy
            # device: the manager then starved for its 30 s and failed
            # the task with device-oom).  Re-probe while idle.
            try:
                self._drain_retired(max_unfinalized=self._runahead)
            except Exception as exc:   # the completer thread must survive
                self.stats.faults += 1
                if self.es is not None:
                    self.es.context.record_error(
                        exc, batch[-1].task if batch else None)
            # the idle wait must not keep the pass's entries alive: they
            # name their tasks' output arrays, and a job's last ones
            # would stay on the device beside the next job's
            batch = inf = None
        self._drain_retired(max_unfinalized=0)

    def _release(self, inf: _Inflight, scheduling) -> None:
        """Publish one dispatched task's outputs and release its deps
        (``scheduling``: the module, imported once a thread).  A release
        that raises is that task's error: the rest of its pass is
        released all the same."""
        try:
            if not inf.prepublished:
                # chain-held tasks planted their Deferred payloads at
                # hold time; rewriting here could clobber a resolution
                for fname, arr in inf.outputs.items():
                    dc = inf.task.data.get(fname)
                    if dc is not None:
                        dc.payload = arr
            # dep release and the scheduling of successors: the one
            # span that is per task by design (seq = the launch the
            # task rode), so its arguments are built only when live
            release = open_span(
                inf.es, "fin.release",
                pool=inf.task.taskpool.taskpool_id,
                cls=inf.task.task_class.name, seq=inf.seq) \
                if spans_live(inf.es) else SPAN_OFF
            try:
                scheduling.complete_execution(inf.es, inf.task)
            finally:
                release.end()
        except Exception as exc:
            self.stats.faults += 1
            inf.es.context.record_error(exc, inf.task)

    def _drain_retired(self, max_unfinalized: int) -> None:
        """Finalize retired tasks whose outputs are ready; when more than
        ``max_unfinalized`` are still pending, block on the oldest (the
        run-ahead memory valve).  The device queue is in-order, so ONE
        readiness probe of the newest entry covers the whole list."""
        while True:
            block = False
            with self._cond:
                if not self._retire:
                    return
                newest = self._retire[-1]
            # probe OUTSIDE the lock: is_ready() crosses into the PJRT
            # client, and submit()/manager/sync all contend on _cond
            newest_ready = self._outputs_ready(newest)
            with self._cond:
                if not self._retire:
                    return
                if self._retire[-1] is not newest:
                    continue   # the list moved on; re-probe
                if newest_ready:
                    batch = list(self._retire)
                    self._retire.clear()
                elif len(self._retire) > max_unfinalized:
                    batch = [self._retire.popleft()]
                    block = True
                else:
                    return
                # popped entries stay visible to sync() until their
                # finalization lands (late errors must beat wait())
                self._finalizing += len(batch)
            try:
                drain = open_span(self.es, "fin.drain", block=int(block),
                                  n=len(batch)) \
                    if spans_live(self.es) else SPAN_OFF
                try:
                    self._finalize(batch, block=block)
                finally:
                    drain.end()
            finally:
                with self._cond:
                    self._finalizing -= len(batch)
                    self._cond.notify_all()

    @staticmethod
    def _outputs_ready(inf: _Inflight) -> bool:
        for a in inf.outputs.values():
            r = getattr(a, "is_ready", None)
            if r is None:
                continue
            try:
                if not r():
                    return False
            except Exception as exc:
                if "deleted" in str(exc).lower():
                    # a successor kernel donated this buffer away — it
                    # was consumed, ordering is the device's problem now
                    continue
                # any OTHER probe failure must NOT report "ready": that
                # would finalize without blocking and swallow the error
                return False
        return True

    def _finalize(self, batch: List[_Inflight], block: bool) -> None:
        """Give back what ``batch`` (retired entries, oldest first) held
        until its outputs materialized: the load in one ``load_sub``,
        the pins under one hold of ``_mem_lock``; replicas and arena
        copies entry by entry, in dispatch order.  ``block`` waits for
        the newest entry's outputs first: the chip's queue is in order,
        so that wait covers the earlier ones."""
        ctx = self.es.context
        if block:
            newest = batch[-1]
            try:
                import jax
                for a in newest.outputs.values():
                    try:
                        jax.block_until_ready(a)
                    except Exception as exc:
                        if "deleted" in str(exc).lower():
                            continue   # donated away — see _outputs_ready
                        raise
            except Exception as exc:
                # deps were already released at dispatch; a late
                # device-side failure surfaces as a context error
                # (sync()/wait raise)
                self.stats.faults += 1
                ctx.record_error(exc, newest.task)
        if ctx._device_spans:
            # outputs are materialized (or the failure surfaced):
            # close the dispatch->done device spans
            for inf in batch:
                inf.es.pins("device_done", inf.task)
        self.load_sub(sum(inf.load for inf in batch))
        self._unpin_all(d for inf in batch for d in inf.pinned)
        ici = ctx.ici
        for inf in batch:
            if ici is not None:
                # deps were released at dispatch and the chip's queue is
                # in order: a replica this task read may leave now
                ici.consumed(inf.task, inf.pinned)
            for copy in inf.release_after:
                # a predecessor's repo entry may still hold this
                # superseded host buffer for its OTHER consumers
                copy.arena.release_unheld(copy)

    def adopt(self, datum, dc: DataCopy) -> None:
        """Account a device copy attached by an EXTERNAL placer (the ICI
        engine's prebroadcast/preplace): claim its bytes against the HBM
        budget and enter it in the LRU so eviction can see it — an
        unaccounted attach would let collective placement overcommit the
        budget invisibly."""
        key = id(datum)
        nbytes = getattr(dc.payload, "nbytes", 0)
        with self._mem_lock:
            while True:
                ent = self._lru.get(key)
                if ent is None:
                    # placeholder claims the key atomically with the
                    # check, so a concurrent adopt/stage-in of the same
                    # datum cannot double-account; pinned so eviction
                    # skips the stub
                    self._lru[key] = (weakref.ref(dc), 0, _PLACEHOLDER)
                    self._pins[key] = self._pins.get(key, 0) + 1
                    break
                if ent[2] is not _PLACEHOLDER:
                    return      # already accounted (payload refresh)
                # another adopt of this datum is mid-reserve: wait for it
                # to resolve (account or fail) rather than piggy-backing
                # on a claim that may yet be rolled back (ADVICE r2 low)
                self._mem_lock.wait(0.05)
        def _drop_pin_locked():
            n = self._pins.get(key, 0) - 1
            if n <= 0:
                self._pins.pop(key, None)
            else:
                self._pins[key] = n

        try:
            off = self._reserve(nbytes)
        except BaseException:
            # roll the placeholder back, or every later adopt of this
            # datum early-returns "already accounted" and its bytes never
            # hit the budget (ADVICE r2 low)
            with self._mem_lock:
                ent = self._lru.get(key)
                if ent is not None and ent[2] is _PLACEHOLDER:
                    self._lru.pop(key)
                _drop_pin_locked()
                self._mem_lock.notify_all()
            raise
        with self._mem_lock:
            # entry lands and the claim pin drops under ONE lock hold: an
            # unpinned placeholder must never be visible to a concurrent
            # victim scan (it would _evict the just-adopted copy)
            self._lru[key] = (weakref.ref(dc), nbytes, off)
            self._bytes_used += nbytes
            _drop_pin_locked()
            self._mem_lock.notify_all()
        weakref.finalize(dc, self._forget, key, nbytes)
        self.stats.bytes_in += nbytes
        self._note_replica(datum, dc)

    def _note_replica(self, datum, dc: DataCopy) -> None:
        """``dc`` serves consumers that are counted on this chip
        (``datum.replica_readers``): enter it in the replica ledger,
        once, whoever brought it — the ICI engine's push or a consumer's
        own stage-in."""
        rr = datum.replica_readers
        if not rr:
            return
        with datum._lock:
            if not rr.get(self.space) or dc.flags & FLAG_REPLICA \
                    or datum.copy_on(self.space) is not dc:
                return
            dc.flags |= FLAG_REPLICA
            nbytes = getattr(dc.payload, "nbytes", 0)
        with self._mem_lock:
            self.stats.replicas_adopted += 1
            self._replica_bytes += nbytes
            if self._replica_bytes > self.stats.replica_bytes_peak:
                self.stats.replica_bytes_peak = self._replica_bytes

    def release_replica(self, datum) -> None:
        """The last counted consumer of ``datum``'s replica on this chip
        is through (comm/ici.py consumed / release_pool): detach the
        SHARED copy, drop its payload and give its bytes back to the
        ledger.  Never a write-back and never the owner's copy: a copy
        that was written here since, or that is the only valid one, only
        stops being a replica."""
        with datum._lock:
            dc = datum.copy_on(self.space)
            if dc is None or not dc.flags & FLAG_REPLICA:
                return
            dc.flags &= ~FLAG_REPLICA
            nbytes = getattr(dc.payload, "nbytes", 0)
            drop = dc.coherency == Coherency.INVALID or (
                dc.coherency == Coherency.SHARED and any(
                    c is not dc and c.coherency != Coherency.INVALID
                    and c.version >= dc.version
                    for c in datum._copies.values()))
            if drop:
                datum.detach_copy(self.space)
                dc.payload = None
                dc.coherency = Coherency.INVALID
        with self._mem_lock:
            self._replica_left_locked(nbytes)
            ent = self._lru.get(id(datum))
            if drop and ent is not None and ent[0]() is dc:
                del self._lru[id(datum)]
                self._bytes_used -= ent[1]
                self._zone_free(ent[2])

    def _replica_left_locked(self, nbytes: int) -> None:
        self.stats.replicas_released += 1
        self._replica_bytes -= nbytes

    def sync(self, timeout: Optional[float] = None) -> None:
        """Drain the device: block until every dispatched kernel has
        materialized its outputs (the stream-synchronize at pool
        quiescence; reference: the GPU manager drains its exec and
        stage-out streams before epilog).  ``timeout`` bounds the wait
        for the dispatch queues; the final materialization block is
        unbounded, like a stream synchronize."""
        with self._cond:
            ok = self._cond.wait_for(
                lambda: (not self._pending and not self._parked
                         and self._launching == 0
                         and self._completing == 0
                         and self._finalizing == 0
                         and not self._inflight) or self._stop,
                timeout=timeout)
            if not ok:
                raise TimeoutError(f"device {self.name}: sync timed out")
        # chain holds whose consumer never launched here (tail of the
        # last panel, cancelled pools) dispatch now — quiescence means
        # every held kernel has actually run
        self._resolve_all_held()
        with self._cond:
            entries = list(self._retire)
            self._retire.clear()
        if not entries:
            return
        self._finalize(entries, block=True)

    # ------------------------------------------------------------------
    # device memory cache management (reference: gpu_mem_lru / zone_malloc)
    # ------------------------------------------------------------------
    def _pin_wave(self, batch, pinned_per: List[List[Any]]) -> None:
        """Pin every datum the tasks of ``batch`` touch — one list a
        task appended to ``pinned_per``, what its inflight entry gives
        back — and freshen each in the LRU, under ONE hold of
        ``_mem_lock``: no ``_reserve`` of the wave's own stage-ins, nor
        a concurrent dispatcher's, finds one of them a victim."""
        with self._mem_lock:
            pins, lru = self._pins, self._lru
            for item in batch:
                task = item[0]
                data = task.data
                pinned: List[Any] = []
                pinned_per.append(pinned)
                for flow in task.task_class.flows:
                    copy = data.get(flow.name)
                    if copy is not None and copy.data is not None:
                        datum = copy.data
                        key = id(datum)
                        pins[key] = pins.get(key, 0) + 1
                        if key in lru:
                            lru.move_to_end(key)
                        pinned.append(datum)

    def _unpin_all(self, data) -> None:
        with self._mem_lock:
            pins = self._pins
            for datum in data:
                n = pins.get(id(datum), 0) - 1
                if n <= 0:
                    pins.pop(id(datum), None)
                else:
                    pins[id(datum)] = n

    def _touch(self, datum) -> None:
        with self._mem_lock:
            if id(datum) in self._lru:
                self._lru.move_to_end(id(datum))

    def _account(self, datum, dc: DataCopy, nbytes: int,
                 offset: Any = None) -> None:
        key = id(datum)
        with self._mem_lock:
            self._lru[key] = (weakref.ref(dc), nbytes, offset)
            self._bytes_used += nbytes
        weakref.finalize(dc, self._forget, key, nbytes)

    def _forget(self, key: int, nbytes: int) -> None:
        """Finalizer: a device copy died with its (temporary) datum —
        drop its cache accounting.  Only removes the entry if it still
        refers to the dead copy (the key may have been reused by a
        re-staged copy of the same datum, or by a new datum at the same
        address)."""
        with self._mem_lock:
            ent = self._lru.get(key)
            if ent is not None and ent[0]() is None:
                self._lru.pop(key)
                self._bytes_used -= ent[1]
                self._zone_free(ent[2])

    def _zone_free(self, offset: Any) -> None:
        if self._zone is not None and offset is not None \
                and offset is not _PLACEHOLDER:
            self._zone.free(offset)

    def _reserve(self, nbytes: int) -> Any:
        """Claim a segment of the HBM budget, evicting LRU unpinned
        copies until it fits (reference:
        parsec_gpu_data_reserve_device_space, device_cuda_module.c:864,
        over the zone_malloc slab).  Returns the zone offset (None when
        the budget is unlimited); the caller threads it into _account or
        releases it via _zone_free if the copy turns out not to be
        fresh."""
        if self._zone is None:
            return None
        deadline = _time.monotonic() + 30.0
        while True:
            with self._mem_lock:
                while True:
                    off = self._zone.malloc(nbytes)
                    if off is not None:
                        return off
                    victim = None
                    for key in self._lru.keys():
                        if self._pins.get(key, 0) > 0:
                            continue
                        dcv = self._lru[key][0]()
                        if dcv is not None and \
                                isinstance(dcv.payload, Deferred) and \
                                dcv.payload.array is None:
                            # an unresolved chain placeholder holds no
                            # bytes yet and its value exists nowhere
                            # else: never a victim
                            continue
                        victim = key
                        break
                    if victim is None:
                        break   # all pinned right now: wait outside
                    dcref, sz, voff = self._lru.pop(victim)
                    dc = dcref()
                    if dc is None:
                        self._bytes_used -= sz
                        self._zone_free(voff)
                        continue
                    self._evict(dc.data, dc, sz, voff)
            # every resident copy is transiently pinned by in-flight
            # tasks: wait for a finalization to unpin instead of failing
            # (the reference requeues, HOOK_RETURN_AGAIN, rather than
            # aborting)
            if _time.monotonic() > deadline:
                from parsec_tpu.utils.output import show_help
                raise MemoryError(show_help(
                    "device-oom", warn=False,
                    budget=(self._capacity or 0) >> 20, nbytes=nbytes))
            _time.sleep(0.001)

    def _evict(self, datum, dc: DataCopy, nbytes: int,
               offset: Any = None) -> None:
        """Write back if authoritative, then drop (caller holds _mem_lock)."""
        if dc.coherency in (Coherency.OWNED, Coherency.EXCLUSIVE) and \
                dc.version >= datum.newest_version():
            self._writeback_host(datum, dc)
        if dc.flags & FLAG_REPLICA:
            dc.flags &= ~FLAG_REPLICA
            self._replica_left_locked(getattr(dc.payload, "nbytes", 0))
        datum.detach_copy(self.space)
        dc.payload = None
        dc.coherency = Coherency.INVALID
        self._bytes_used -= nbytes
        self._zone_free(offset)
        self.stats.evictions += 1

    def _writeback_host(self, datum, dc: DataCopy) -> None:
        """Pull the datum home (one locked, version-guarded path:
        Data.pull_to_host), accounting the transfer."""
        host = datum.copy_on(0)
        if host is None or host.coherency == Coherency.INVALID or \
                host.version < dc.version:
            self.stats.bytes_out += getattr(dc.payload, "nbytes", 0)
        datum.pull_to_host()

    def discard_scratch(self) -> None:
        """Drop device copies of collection-less datums (NEW-flow arena
        temporaries) WITHOUT writeback, with full accounting — the
        quiescent-point twin of flush() for data nobody user-visible
        will ever read.  Benches call it before teardown so fini's
        flush does not D2H gigabytes of dead QR panels / potrf
        inverses through a slow link."""
        with self._mem_lock:
            self._blanks.clear()
            for key in list(self._lru.keys()):
                dcref, sz, voff = self._lru[key]
                dc = dcref()
                if dc is None:
                    del self._lru[key]
                    self._bytes_used -= sz
                    self._zone_free(voff)
                    continue
                datum = dc.data
                if datum is None or datum.collection is not None:
                    continue   # user-visible data keeps flush semantics
                del self._lru[key]
                if dc.flags & FLAG_REPLICA:
                    dc.flags &= ~FLAG_REPLICA
                    self._replica_left_locked(sz)
                # _mem_lock -> datum._lock is the established order
                # (_reserve's eviction path writes back under it), so
                # taking the per-datum lock here is deadlock-free and
                # closes the window against concurrent flush/pull
                with datum._lock:
                    datum.detach_copy(self.space)
                    dc.payload = None
                    dc.coherency = Coherency.INVALID
                self._bytes_used -= sz
                self._zone_free(voff)

    def flush(self) -> None:
        """Push every authoritative device copy home (reference:
        parsec_dtd_data_flush_all / GPU w2r writeback tasks).  Flush is a
        quiescent point, so replaced host payloads re-link into their
        collection's user-visible backing storage."""
        with self._mem_lock:
            entries = [ref() for ref, _sz, _off in self._lru.values()]
        for dc in entries:
            if dc is None:
                continue
            datum = dc.data
            with datum._lock:
                if dc.payload is not None and \
                        dc.coherency in (Coherency.OWNED, Coherency.EXCLUSIVE) \
                        and dc.version >= datum.newest_version():
                    self._writeback_host(datum, dc)
            if datum.collection is not None:
                datum.collection.refresh_backing(datum)

    def fini(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        for m in self._managers:
            m.join(timeout=5)
        try:
            # undispatched chain holds would poison flush() with
            # placeholder payloads; the completer's final drain then
            # finds real arrays to block on
            self._resolve_all_held()
        except Exception as exc:
            warning("device %s: chain resolution at fini failed: %s",
                    self.name, exc)
        self._completer.join(timeout=5)
        self.flush()
        self._blanks.clear()
        debug_verbose(5, "device %s: %s", self.name, self.stats.as_dict())
