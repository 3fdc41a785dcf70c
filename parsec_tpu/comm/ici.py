"""ICI transport: payload movement as XLA device collectives.

The second comm-engine module (reference seam: the transport-neutral
``parsec_comm_engine_t`` vtable, parsec/parsec_comm_engine.h:161-183, whose
only in-tree implementation is funnelled MPI, parsec_mpi_funnelled.c).  On
TPU the equivalent of registered-memory put/get between ranks is
device-to-device movement over the ICI mesh — so this module lowers
dataflow payload edges between the runtime's XLA devices to XLA
collective programs, keeping control (activation bookkeeping) on the
host:

- ``put``      — one point-to-point tile edge (DMA d2h-free device copy;
                 on a real slice this is an ICI transfer).
- ``bcast``    — one producer tile replicated to many devices in a single
                 XLA replication (the dataflow-broadcast primitive of
                 remote_dep.c:334-357, ridden on the interconnect instead
                 of N host round-trips).  The first customer is the GEMM
                 panel broadcast (apps/gemm.py RA/RB): release_deps calls
                 ``prebroadcast`` when one copy fans out to consumers on
                 several devices.
- ``permute``  — a batch of same-shaped tile edges executed as ONE
                 ``lax.ppermute`` (CollectivePermute) program over the
                 mesh — the per-wavefront batched schedule of SURVEY §5.8.
                 Non-permutation batches are split into permutation
                 rounds (each device sends/receives at most once per
                 round, matching CollectivePermute semantics).

Programs are shard_map computations over a 1D mesh of every attached XLA
device, cached per (shape, dtype, permutation).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from parsec_tpu.data.data import Coherency, DataCopy
from parsec_tpu.utils.mca import params
from parsec_tpu.utils.output import debug_verbose

params.register("comm_ici_enabled", 1,
                "lower multi-device payload edges to XLA collectives")
params.register("comm_ici_bcast_min", 2,
                "minimum distinct consumer devices to trigger a collective "
                "panel broadcast")
params.register("comm_ici_permute_window_ms", 2.0,
                "how long a deferred point-to-point placement may wait for "
                "same-wavefront siblings before an idle worker flushes the "
                "batch as CollectivePermute rounds")
params.register("comm_ici_permute_min", 2,
                "minimum batched edges to lower a flush to ppermute; "
                "smaller flushes fall back to per-edge puts")


def permute_program(mesh, perm):
    """ONE CollectivePermute over the mesh's device axis ``d``: shard i of
    the stacked operand goes to shard j for every (i, j) of ``perm``,
    the other shards come back zero (``lax.ppermute`` semantics)."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def body(t):
        return lax.ppermute(t, "d", perm)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P("d"), out_specs=P("d")))


class IciStats:
    __slots__ = ("puts", "put_bytes", "bcasts", "bcast_bytes",
                 "permutes", "permute_edges", "permute_bytes")

    def __init__(self):
        self.puts = 0
        self.put_bytes = 0
        self.bcasts = 0
        self.bcast_bytes = 0
        self.permutes = 0
        self.permute_edges = 0
        self.permute_bytes = 0

    def as_dict(self):
        return {k: getattr(self, k) for k in self.__slots__}


class IciEngine:
    """Collective payload transport over the local device mesh."""

    #: comm-engine capability flags (reference: parsec_comm_engine.h
    #: capabilities) — one-sided puts and collective broadcast, no
    #: two-sided AM (control rides the host/TCP engine)
    CAP_ONESIDED = True
    CAP_COLLECTIVE = True

    def __init__(self, registry):
        from parsec_tpu.devices.xla import XlaDevice
        self.registry = registry
        self.xla_devices = [d for d in registry.devices
                            if isinstance(d, XlaDevice) and d.enabled]
        self._space_to_pos: Dict[int, int] = {
            d.space: i for i, d in enumerate(self.xla_devices)}
        self._jdev = {d.space: d.jdev for d in self.xla_devices}
        self.stats = IciStats()
        self._mesh = None
        self._prog_cache: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()
        #: serializes COLLECTIVE program launches: two multi-device
        #: programs dispatched concurrently from different worker
        #: threads (an idle-worker window flush racing a full-round
        #: defer_place flush) can interleave their per-device
        #: participant enqueues and deadlock the XLA rendezvous — the
        #: r8 repro's "two CollectivePermute run-ids stuck waiting for
        #: participants" wedge (the pre-existing dryrun >3min stall).
        #: One launch at a time gives every device queue the same
        #: program order.
        self._launch_lock = threading.Lock()
        #: deferred single-consumer placements awaiting same-wavefront
        #: siblings: (produced copy, destination space, enqueue time).
        #: Flushed as batched CollectivePermute rounds (SURVEY §5.8's
        #: "batched per DAG wavefront" schedule) when a full round
        #: accumulates or an idle worker drains the window.
        self._pending_edges: List[Tuple[DataCopy, int, float]] = []
        self._pending_lock = threading.Lock()
        #: when the last single-consumer edge was seen: a fresh edge after
        #: a quiet spell is treated as a chain hop (placed immediately),
        #: one arriving inside the window as a wavefront sibling (batched)
        self._last_edge = float("-inf")

    # ------------------------------------------------------------------
    @property
    def ndev(self) -> int:
        return len(self.xla_devices)

    def mesh(self):
        """Lazy 1D mesh over every attached XLA device."""
        if self._mesh is None:
            from jax.sharding import Mesh
            self._mesh = Mesh(
                np.array([d.jdev for d in self.xla_devices]), ("d",))
        return self._mesh

    # ------------------------------------------------------------------
    # point-to-point: the put of the CE vtable
    # ------------------------------------------------------------------
    def put(self, payload, dst_space: int):
        """Move one tile to ``dst_space``'s device, device-to-device
        (reference: CE put with registered memory,
        parsec_mpi_funnelled.c:793).  The placed copy must be PRIVATE:
        on the CPU client a plain device_put can alias the source
        buffer, which a later donation would corrupt (the r8 wrong-R
        root cause; see devices/xla.device_put_private)."""
        from parsec_tpu.devices.xla import device_put_private
        out = device_put_private(payload, self._jdev[dst_space])
        self.stats.puts += 1
        self.stats.put_bytes += getattr(payload, "nbytes", 0)
        return out

    # ------------------------------------------------------------------
    # broadcast: one producer tile -> many devices, one XLA replication
    # ------------------------------------------------------------------
    def bcast(self, payload, dst_spaces: Sequence[int]) -> Dict[int, Any]:
        """Replicate ``payload`` onto every device of the mesh in one XLA
        data movement; return {space: on-device array} for the requested
        targets (reference: the dataflow bcast trees, remote_dep.c:334-357
        — here the tree is the interconnect's native replication)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from parsec_tpu.devices.xla import device_put_replicated_private
        want = set(dst_spaces)
        sharding = NamedSharding(self.mesh(), P())   # fully replicated
        # the replicated "copies" must be PRIVATE: on the CPU client the
        # shard co-located with the host buffer can alias it (the same
        # r8 wrong-R hazard device_put_private closes for put/stage-in)
        # — a later in-place mutation or donation of the source would
        # corrupt every consumer's tile
        rep = device_put_replicated_private(payload, sharding)
        out: Dict[int, Any] = {}
        by_jdev = {jd: sp for sp, jd in self._jdev.items()}
        for shard in rep.addressable_shards:
            sp = by_jdev.get(shard.device)
            if sp in want:
                out[sp] = shard.data
        self.stats.bcasts += 1
        self.stats.bcast_bytes += getattr(payload, "nbytes", 0) * len(out)
        return out

    # ------------------------------------------------------------------
    # batched permute: one CollectivePermute program per wavefront round
    # ------------------------------------------------------------------
    def permute(self, edges: Iterable[Tuple[int, int, Any]]
                ) -> Dict[Tuple[int, int], Any]:
        """Execute a batch of (src_space, dst_space, payload) tile edges.
        Same-shaped edges forming a partial permutation ride ONE
        ``lax.ppermute`` launch; the batch is split into permutation
        rounds and (shape, dtype) groups as needed.  Returns
        {(src_space, dst_space): array-on-dst}."""
        groups: Dict[Tuple, List[Tuple[int, int, Any]]] = {}
        results: Dict[Tuple[int, int], Any] = {}
        for s, d, payload in edges:
            if s == d:
                results[(s, d)] = payload
                continue
            arr_shape = tuple(getattr(payload, "shape", ()))
            dt = str(getattr(payload, "dtype", "f4"))
            groups.setdefault((arr_shape, dt), []).append((s, d, payload))
        for (shape, dt), group in groups.items():
            for round_edges in self._rounds(group):
                results.update(self._permute_round(shape, round_edges))
        return results

    @staticmethod
    def _rounds(group: List[Tuple[int, int, Any]]
                ) -> List[List[Tuple[int, int, Any]]]:
        """Split edges into rounds where each device sends at most once
        and receives at most once (CollectivePermute is a partial
        permutation)."""
        rounds: List[List[Tuple[int, int, Any]]] = []
        for edge in group:
            for r in rounds:
                if all(edge[0] != e[0] and edge[1] != e[1] for e in r):
                    r.append(edge)
                    break
            else:
                rounds.append([edge])
        return rounds

    def _permute_round(self, shape, round_edges):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh()
        n = self.ndev
        srcs: Dict[int, Any] = {}
        perm: List[Tuple[int, int]] = []
        for s, d, payload in round_edges:
            perm.append((self._space_to_pos[s], self._space_to_pos[d]))
            srcs[self._space_to_pos[s]] = payload
        perm.sort()
        dtype = None
        for a in srcs.values():
            dtype = a.dtype
            break
        from parsec_tpu.devices.xla import device_put_private
        shards = []
        for i, dev in enumerate(self.xla_devices):
            a = srcs.get(i)
            if a is None:
                a = jnp.zeros(shape, dtype)
            # PRIVATE stage-in: ``a`` is a producer's live tile — a
            # zero-copy device_put alias would let a concurrent donation
            # of the source corrupt the program's input mid-permute
            a = device_put_private(a, dev.jdev)
            shards.append(jnp.reshape(a, (1,) + shape))
        sharding = NamedSharding(mesh, P("d"))
        x = jax.make_array_from_single_device_arrays(
            (n,) + shape, sharding, shards)

        key = ("perm", shape, str(dtype), tuple(perm))
        with self._lock:
            prog = self._prog_cache.get(key)
            if prog is None:
                prog = self._prog_cache[key] = permute_program(mesh, perm)
        with self._launch_lock:
            # dispatch AND completion inside the lock: async dispatch
            # alone could still leave per-device enqueues of two
            # collectives interleaved (see _launch_lock)
            y = jax.block_until_ready(prog(x))
        pos_to_space = {v: k for k, v in self._space_to_pos.items()}
        recv = {d_pos: s_pos for s_pos, d_pos in perm}
        by_jdev = {jd: sp for sp, jd in self._jdev.items()}
        out: Dict[Tuple[int, int], Any] = {}
        for shard in y.addressable_shards:
            sp = by_jdev.get(shard.device)
            if sp is None:
                continue
            pos = self._space_to_pos[sp]
            if pos not in recv:
                continue
            out[(pos_to_space[recv[pos]], sp)] = shard.data[0]
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize \
            if shape else 0
        self.stats.permutes += 1
        self.stats.permute_edges += len(perm)
        self.stats.permute_bytes += nbytes * len(perm)
        return out

    # ------------------------------------------------------------------
    # runtime hook: collective panel broadcast on dataflow fan-out
    # ------------------------------------------------------------------
    def prebroadcast(self, copy: DataCopy, target_spaces: Sequence[int]
                     ) -> int:
        """Replicate a produced copy onto the consumer devices in one
        collective, attaching SHARED device copies to its datum so each
        consumer's stage-in finds the tile resident (zero further
        movement).  Returns the number of devices the tile landed on."""
        datum = copy.data
        if datum is None or copy.payload is None \
                or getattr(copy.payload, "parsec_deferred", False):
            # chain-held placeholder (devices/xla.py Deferred): the value
            # does not exist yet — consumers lazily stage (and force) it
            return 0
        spaces = sorted({s for s in target_spaces
                         if s in self._jdev})
        with datum._lock:
            missing = [s for s in spaces
                       if (c := datum.copy_on(s)) is None or
                       c.coherency == Coherency.INVALID or
                       c.version < copy.version]
        if len(missing) < int(params.get("comm_ici_bcast_min", 2)):
            return 0
        replicas = self.bcast(copy.payload, missing)
        attached = 0
        adopt = []
        with datum._lock:
            for sp, arr in replicas.items():
                existing = datum.copy_on(sp)
                if existing is None:
                    dc = DataCopy(datum, sp, payload=arr,
                                  coherency=Coherency.SHARED,
                                  version=copy.version)
                    datum.attach_copy(dc)
                    adopt.append((sp, dc))
                    attached += 1
                elif existing.coherency == Coherency.INVALID or \
                        existing.version < copy.version:
                    existing.payload = arr
                    existing.coherency = Coherency.SHARED
                    existing.version = copy.version
                    adopt.append((sp, existing))
                    attached += 1
        self._adopt(datum, adopt)
        debug_verbose(7, "ici prebroadcast: %d replicas of %s", attached,
                      datum)
        return attached

    def preplace(self, copy: DataCopy, space: int) -> bool:
        """Single-consumer counterpart of :meth:`prebroadcast`: move one
        produced device-resident tile onto the consumer's device NOW —
        overlapping the transfer with scheduling — instead of lazily
        inside the consumer's stage-in (reference: the CE put of a
        point-to-point dep edge, parsec_mpi_funnelled.c:793; on TPU a
        device-to-device ICI hop)."""
        datum = copy.data
        if datum is None or copy.payload is None or space not in self._jdev \
                or getattr(copy.payload, "parsec_deferred", False):
            return False
        if copy.device == space or copy.device not in self._jdev:
            return False      # host-resident payloads stage in normally
        with datum._lock:
            existing = datum.copy_on(space)
            if existing is not None and \
                    existing.coherency != Coherency.INVALID and \
                    existing.version >= copy.version:
                return False  # already resident
        arr = self.put(copy.payload, space)
        return self._attach_placed(copy, space, arr)

    def _attach_placed(self, copy: DataCopy, space: int, arr) -> bool:
        """Attach a freshly-moved replica to the datum as a SHARED copy on
        ``space`` (version-guarded: a consumer that already wrote a newer
        version wins) and register it with the device's HBM ledger."""
        datum = copy.data
        placed = None
        with datum._lock:
            existing = datum.copy_on(space)
            if existing is None:
                placed = DataCopy(datum, space, payload=arr,
                                  coherency=Coherency.SHARED,
                                  version=copy.version)
                datum.attach_copy(placed)
            elif existing.version <= copy.version:
                existing.payload = arr
                existing.coherency = Coherency.SHARED
                existing.version = copy.version
                placed = existing
        if placed is not None:
            self._adopt(datum, [(space, placed)])
        return True

    # ------------------------------------------------------------------
    # deferred placement: batch single-consumer edges per DAG wavefront
    # into CollectivePermute rounds (SURVEY §5.8; reference counterpart:
    # the per-peer aggregation of the comm thread, remote_dep_mpi.c —
    # here aggregation happens across DEVICE edges of one wavefront)
    # ------------------------------------------------------------------
    def defer_place(self, copy: DataCopy, space: int) -> bool:
        """Queue a device-resident single-consumer placement; when the
        batch completes a permutation round (every device sends/receives
        at most once) — or an idle worker drains the window
        (:meth:`flush_placements`) — the whole wavefront rides one
        ``lax.ppermute`` launch instead of N separate puts.  Placement is
        purely a prefetch: consumers that stage in before the flush win
        the version race and the late replica is dropped."""
        datum = copy.data
        if datum is None or not self.device_resident(copy) \
                or space not in self._jdev or copy.device == space \
                or self.ndev < 2:
            return False
        with datum._lock:
            existing = datum.copy_on(space)
            if existing is not None and \
                    existing.coherency != Coherency.INVALID and \
                    existing.version >= copy.version:
                return False  # already resident
        import time
        now = time.monotonic()
        window = float(params.get("comm_ici_permute_window_ms", 2.0)) / 1e3
        immediate = False
        flush_now = None
        with self._pending_lock:
            if not self._pending_edges and now - self._last_edge > window:
                # a lone edge after a quiet spell is a serialized chain
                # hop until proven otherwise: place it NOW so the
                # transfer overlaps scheduling (a deferred chain hop
                # always loses the race against its consumer's lazy
                # stage-in and the flush would be pure waste).  It also
                # opens the wave window: siblings arriving within it DO
                # defer, so a k-edge wavefront costs one put plus one
                # (k-1)-edge permute — within the "k edges ride <=2
                # launches" contract.
                immediate = True
            else:
                self._pending_edges.append((copy, space, now))
                # flush when the batch completes a permutation round —
                # OR when the oldest deferred edge has already outlived
                # the window (under load the gaps between wavefront
                # siblings stretch past it; without the age trigger the
                # batch would sit until an idle worker happens by,
                # losing every version race to lazy stage-in — the
                # "wavefront permute did not fire" flake, ~1/7 loaded)
                full_round = any(
                    e[0].device == copy.device or e[1] == space
                    for e in self._pending_edges[:-1]) \
                    or len(self._pending_edges) >= self.ndev - 1 \
                    or now - self._pending_edges[0][2] >= window
                if full_round:
                    flush_now, self._pending_edges = self._pending_edges, []
            self._last_edge = now
        if immediate:
            return self.preplace(copy, space)
        if flush_now:
            self._flush_edges(flush_now)
        return True

    def flush_placements(self, force: bool = False) -> int:
        """Drain deferred placements older than the batching window (all
        of them when ``force``).  Called from idle workers and quiescence
        points; failures are swallowed — placement is best-effort
        prefetch and consumers fall back to lazy stage-in."""
        if not self._pending_edges:
            return 0
        import time
        window = float(params.get("comm_ici_permute_window_ms", 2.0)) / 1e3
        take = None
        with self._pending_lock:
            if self._pending_edges and (
                    force or time.monotonic() - self._pending_edges[0][2]
                    >= window):
                take, self._pending_edges = self._pending_edges, []
        if not take:
            return 0
        try:
            self._flush_edges(take)
        except Exception as exc:
            debug_verbose(3, "ici flush_placements dropped %d edges: %s",
                          len(take), exc)
        return len(take)

    def _flush_edges(self, edges) -> None:
        live = []
        for copy, space, _t in edges:
            p = copy.payload
            if p is None or (hasattr(p, "is_deleted") and p.is_deleted()):
                continue     # evicted/donated since: consumer stages lazily
            datum = copy.data
            with datum._lock:
                existing = datum.copy_on(space)
                if existing is not None and \
                        existing.coherency != Coherency.INVALID and \
                        existing.version >= copy.version:
                    # the consumer staged in (or wrote) while the edge sat
                    # in the window: a collective for it would move bytes
                    # nobody reads
                    continue
            live.append((copy, space))
        if not live:
            return
        if len(live) < int(params.get("comm_ici_permute_min", 2)):
            for copy, space in live:
                self.preplace(copy, space)
            return
        # unique (src, dst) keys per permute() call: duplicate pairs would
        # collide in its result map, so they go in follow-up calls
        calls: List[List[Tuple[DataCopy, int]]] = []
        for item in live:
            key = (item[0].device, item[1])
            for c in calls:
                if all((e[0].device, e[1]) != key for e in c):
                    c.append(item)
                    break
            else:
                calls.append([item])
        for c in calls:
            try:
                results = self.permute(
                    [(copy.device, space, copy.payload)
                     for copy, space in c])
            except Exception as exc:
                debug_verbose(3, "ici permute batch failed (%s); "
                              "falling back to puts", exc)
                for copy, space in c:
                    try:
                        self.preplace(copy, space)
                    except Exception:
                        pass      # best-effort prefetch
                continue
            for copy, space in c:
                arr = results.get((copy.device, space))
                if arr is not None:
                    self._attach_placed(copy, space, arr)

    def device_resident(self, copy: DataCopy) -> bool:
        """Cheap hot-path gate: only device-resident produced copies are
        candidates for collective placement (chain-held placeholders —
        devices/xla.py Deferred — are not: the value does not exist)."""
        return copy.device in self._jdev and copy.payload is not None \
            and not getattr(copy.payload, "parsec_deferred", False)

    def _adopt(self, datum, placed) -> None:
        """Register externally-attached copies with their device's HBM
        ledger so eviction/budget accounting can see them."""
        by_space = {d.space: d for d in self.xla_devices}
        for sp, dc in placed:
            dev = by_space.get(sp)
            if dev is not None and hasattr(dev, "adopt"):
                dev.adopt(datum, dc)

    def consumer_spaces(self, taskpool, deliveries) -> List[int]:
        """Best-effort device targets for a list of local deliveries:
        each successor's affinity datum names its preferred/resident
        accelerator (reference: parsec_get_best_device's data-affinity
        rule, device.c:79-140)."""
        spaces: List[int] = []
        for succ_tc, succ_locals, _dflow in deliveries:
            if succ_tc.affinity is None:
                continue
            try:
                ref = succ_tc.affinity(succ_locals)
                datum = ref.resolve()
            except Exception:
                continue
            pref = datum.preferred_device
            if pref is not None and pref in self._jdev:
                spaces.append(pref)
                continue
            v = datum.newest_version()
            for sp, c in datum.copies().items():
                if sp in self._jdev and c.version == v \
                        and c.coherency != Coherency.INVALID:
                    spaces.append(sp)
                    break
        return spaces
