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

What a transfer leaves on a consumer's chip is a REPLICA: a SHARED copy
of another chip's tile.  ``fan_out`` counts, from the flow's deliveries,
how many consumers each chip will see (``Data.replica_readers``); each
consumer counts itself down where its inputs are unpinned
(``consumed``), and at the last one the replica leaves its chip
(``XlaDevice.release_replica``) — so several chips hold a larger
problem than one, not a copy of it each.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from parsec_tpu.data.data import Coherency, DataCopy, FLAG_REPLICA
from parsec_tpu.prof.pins import open_span
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
        self._by_space = {d.space: d for d in self.xla_devices}
        self.stats = IciStats()
        self._mesh = None
        #: sorted tuple of spaces -> the sharding that replicates over them
        self._rep_shardings: Dict[Any, Any] = {}
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
        #: siblings: (produced copy, destination space, enqueue time,
        #: whether the destination's consumers are counted).
        #: Flushed as batched CollectivePermute rounds (SURVEY §5.8's
        #: "batched per DAG wavefront" schedule) when a full round
        #: accumulates or an idle worker drains the window.
        self._pending_edges: List[Tuple[DataCopy, int, float, bool]] = []
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
        nbytes = getattr(payload, "nbytes", 0)
        with open_span(self._es(), "ici.put", bytes=nbytes, ndst=1):
            out = device_put_private(payload, self._jdev[dst_space])
        self.stats.puts += 1
        self.stats.put_bytes += nbytes
        return out

    def _es(self):
        """The stream the transport's spans go out on: whichever device
        stream exists (spans are per thread; the stream only names the
        context whose sink is asked)."""
        for d in self.xla_devices:
            if d.es is not None:
                return d.es
        return None

    # ------------------------------------------------------------------
    # broadcast: one producer tile -> many devices, one XLA replication
    # ------------------------------------------------------------------
    def bcast(self, payload, dst_spaces: Sequence[int]) -> Dict[int, Any]:
        """Replicate ``payload`` onto the devices of ``dst_spaces``, and
        no others, in one XLA data movement; return {space: on-device
        array} (reference: the dataflow bcast trees, remote_dep.c:334-357
        — here the tree is the interconnect's native replication).
        ``bcast_bytes`` counts what moved: one payload a destination."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from parsec_tpu.devices.xla import device_put_replicated_private
        want = tuple(sorted({s for s in dst_spaces if s in self._jdev}))
        if not want:
            return {}
        sharding = self._rep_shardings.get(want)
        if sharding is None:
            sharding = self._rep_shardings[want] = NamedSharding(
                Mesh(np.array([self._jdev[s] for s in want]), ("d",)), P())
        nbytes = getattr(payload, "nbytes", 0)
        # the replicated "copies" must be PRIVATE: on the CPU client the
        # shard co-located with the host buffer can alias it (the same
        # r8 wrong-R hazard device_put_private closes for put/stage-in)
        # — a later in-place mutation or donation of the source would
        # corrupt every consumer's tile
        with open_span(self._es(), "ici.bcast", bytes=nbytes * len(want),
                       ndst=len(want)):
            rep = device_put_replicated_private(payload, sharding)
        by_jdev = {self._jdev[s]: s for s in want}
        out = {by_jdev[shard.device]: shard.data
               for shard in rep.addressable_shards}
        self.stats.bcasts += 1
        self.stats.bcast_bytes += nbytes * len(out)
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
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize \
            if shape else 0
        with open_span(self._es(), "ici.permute", bytes=nbytes * len(perm),
                       ndst=len(perm)), self._launch_lock:
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
        self.stats.permutes += 1
        self.stats.permute_edges += len(perm)
        self.stats.permute_bytes += nbytes * len(perm)
        return out

    # ------------------------------------------------------------------
    # runtime hook: a flow fans out onto other chips (release_deps)
    # ------------------------------------------------------------------
    def fan_out(self, taskpool, copy: DataCopy, deliveries) -> None:
        """A produced copy goes to ``deliveries`` ((successor class,
        locals, ...) tuples): count the consumers each OTHER chip will
        see, so the replica there can leave at its last one
        (:meth:`expect`), and move the tile now.  Onto DISTINCT consumer
        devices: one collective replication; onto a single one (one
        consumer, or several sharing a device): one proactive d2d put
        that overlaps with scheduling, deferred so that the whole DAG
        wavefront (stencil halos, ring neighbor hops, panel sends) rides
        ONE batched CollectivePermute instead of N puts (reference:
        dataflow bcast trees remote_dep.c:334-357 and the CE put; SURVEY
        §5.8 ICI lowering).  A host-resident copy with one target falls
        through to lazy stage-in."""
        readers: Dict[int, int] = {}
        for d in deliveries:
            sp = self.predicted_space(d[0], d[1])
            if sp is not None and sp != copy.device:
                readers[sp] = readers.get(sp, 0) + 1
        if not readers:
            return
        self.expect(taskpool, copy, readers)
        if len(readers) > 1:
            self.prebroadcast(copy, sorted(readers), counted=True)
        else:
            sp = next(iter(readers))
            if not self.defer_place(copy, sp, counted=True):
                self.preplace(copy, sp, counted=True)

    def expect(self, taskpool, copy: DataCopy, readers: Dict[int, int]
               ) -> None:
        """``readers[space]`` consumers will read ``copy``'s datum on
        ``space``: whatever SHARED copy serves them there — pushed by
        this engine or pulled by the first consumer's stage-in — is that
        chip's replica until the last of them has been counted down
        (:meth:`consumed`), or the taskpool ends (:meth:`release_pool`)."""
        datum = copy.data
        if datum is None:
            return
        with datum._lock:
            rr = datum.replica_readers
            if rr is None:
                rr = datum.replica_readers = {}
            for sp, n in readers.items():
                rr[sp] = rr.get(sp, 0) + n
        taskpool.replica_data.add(datum)

    def consumed(self, task, datums) -> None:
        """``task``'s inputs are unpinned: count it down on the replica
        of each datum it read, on the chip where it was EXPECTED — the
        count is by consumer, so one that ran elsewhere still frees the
        replica that waited for it.  The last one releases it."""
        sp = -1
        for datum in datums:
            rr = datum.replica_readers
            if not rr:
                continue
            if sp == -1:
                sp = self.predicted_space(task.task_class, task.locals)
            with datum._lock:
                left = rr.get(sp)
                if left is None:
                    continue
                if left > 1:
                    rr[sp] = left - 1
                    continue
                del rr[sp]
            self._by_space[sp].release_replica(datum)

    def release_pool(self, taskpool) -> None:
        """The taskpool has ended: whatever its consumers left counted
        (they ran on the host, were cancelled, or were never expected
        where they ran) is released now."""
        data, taskpool.replica_data = taskpool.replica_data, set()
        for datum in data:
            with datum._lock:
                datum.replica_readers = None
                spaces = [sp for sp, c in datum._copies.items()
                          if c.flags & FLAG_REPLICA]
            for sp in spaces:
                self._by_space[sp].release_replica(datum)

    def _attach(self, copy: DataCopy, arrays: Dict[int, Any],
                counted: bool) -> int:
        """Attach freshly-moved replicas of ``copy`` to its datum as
        SHARED copies (version-guarded: a consumer that already wrote a
        newer version wins) and register them with their devices' HBM
        ledgers.  A ``counted`` replica whose consumers have all been
        counted down meanwhile is dropped, not attached."""
        datum = copy.data
        adopt = []
        with datum._lock:
            rr = datum.replica_readers
            for sp, arr in arrays.items():
                if counted and not (rr and rr.get(sp)):
                    continue      # every reader there has come and gone
                dc = datum.copy_on(sp)
                if dc is None:
                    dc = DataCopy(datum, sp, payload=arr,
                                  coherency=Coherency.SHARED,
                                  version=copy.version)
                    datum.attach_copy(dc)
                elif dc.coherency == Coherency.INVALID or \
                        dc.version < copy.version:
                    dc.payload = arr
                    dc.coherency = Coherency.SHARED
                    dc.version = copy.version
                else:
                    continue
                adopt.append((sp, dc))
        for sp, dc in adopt:
            self._by_space[sp].adopt(datum, dc)
        return len(adopt)

    def _resident(self, datum, space: int, version: int) -> bool:
        c = datum.copy_on(space)
        return c is not None and c.coherency != Coherency.INVALID \
            and c.version >= version

    def prebroadcast(self, copy: DataCopy, target_spaces: Sequence[int],
                     counted: bool = False) -> int:
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
        with datum._lock:
            missing = [s for s in sorted(set(target_spaces))
                       if s in self._jdev
                       and not self._resident(datum, s, copy.version)]
        if len(missing) < int(params.get("comm_ici_bcast_min", 2)):
            # too few for a collective (on a 2 x 2 grid a row panel has
            # one other chip to reach): a put each, from the chip where
            # the tile is resident
            src = copy if self.device_resident(copy) \
                else datum.copy_on(self._resident_on(datum))
            return sum(self.preplace(src, s, counted) for s in missing) \
                if src is not None else 0
        attached = self._attach(copy, self.bcast(copy.payload, missing),
                                counted)
        debug_verbose(7, "ici prebroadcast: %d replicas of %s", attached,
                      datum)
        return attached

    def preplace(self, copy: DataCopy, space: int,
                 counted: bool = False) -> bool:
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
            if self._resident(datum, space, copy.version):
                return False
        self._attach(copy, {space: self.put(copy.payload, space)}, counted)
        return True

    # ------------------------------------------------------------------
    # deferred placement: batch single-consumer edges per DAG wavefront
    # into CollectivePermute rounds (SURVEY §5.8; reference counterpart:
    # the per-peer aggregation of the comm thread, remote_dep_mpi.c —
    # here aggregation happens across DEVICE edges of one wavefront)
    # ------------------------------------------------------------------
    def defer_place(self, copy: DataCopy, space: int,
                    counted: bool = False) -> bool:
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
            if self._resident(datum, space, copy.version):
                return False
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
                self._pending_edges.append((copy, space, now, counted))
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
            return self.preplace(copy, space, counted)
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
        for copy, space, _t, counted in edges:
            p = copy.payload
            if p is None or (hasattr(p, "is_deleted") and p.is_deleted()):
                continue     # evicted/donated since: consumer stages lazily
            datum = copy.data
            with datum._lock:
                rr = datum.replica_readers
                if self._resident(datum, space, copy.version) or \
                        (counted and not (rr and rr.get(space))):
                    # the consumer staged in (or wrote), or every counted
                    # one has come and gone, while the edge sat in the
                    # window: a collective for it would move bytes
                    # nobody reads
                    continue
            live.append((copy, space, counted))
        if not live:
            return
        if len(live) < int(params.get("comm_ici_permute_min", 2)):
            for copy, space, counted in live:
                self.preplace(copy, space, counted)
            return
        # unique (src, dst) keys per permute() call: duplicate pairs would
        # collide in its result map, so they go in follow-up calls
        calls: List[List[Tuple[DataCopy, int, bool]]] = []
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
                     for copy, space, _c in c])
            except Exception as exc:
                debug_verbose(3, "ici permute batch failed (%s); "
                              "falling back to puts", exc)
                for copy, space, counted in c:
                    try:
                        self.preplace(copy, space, counted)
                    except Exception:
                        pass      # best-effort prefetch
                continue
            for copy, space, counted in c:
                arr = results.get((copy.device, space))
                if arr is not None:
                    self._attach(copy, {space: arr}, counted)

    def device_resident(self, copy: DataCopy) -> bool:
        """Cheap hot-path gate: only device-resident produced copies are
        candidates for collective placement (chain-held placeholders —
        devices/xla.py Deferred — are not: the value does not exist)."""
        return copy.device in self._jdev and copy.payload is not None \
            and not getattr(copy.payload, "parsec_deferred", False)

    def predicted_space(self, tc, locals_) -> Optional[int]:
        """Best-effort device target of one task, by the rules of
        ``DeviceRegistry.best_device`` in their order: the chip its
        affinity datum is pinned to, else the chip of the datum a
        ``coaffinity`` hint names (a panel's diagonal tile), else the
        chip where the affinity datum is resident (reference:
        parsec_get_best_device's data-affinity rule, device.c:79-140)."""
        if tc.affinity is None:
            return None
        try:
            datum = tc.affinity(locals_).resolve()
            if datum.preferred_device in self._jdev:
                return datum.preferred_device
            coaff = tc.properties.get("coaffinity")
            if coaff is not None and int(params.get("device_fuse_panel", 1)):
                sp = self._home(coaff(locals_).resolve())
                if sp is not None:
                    return sp
        except Exception:
            return None
        return self._resident_on(datum)

    def _resident_on(self, datum) -> Optional[int]:
        v = datum.newest_version()
        for sp, c in datum.copies().items():
            if sp in self._jdev and c.version == v \
                    and c.coherency != Coherency.INVALID \
                    and c.payload is not None:
                return sp
        return None

    def _home(self, datum) -> Optional[int]:
        if datum.preferred_device in self._jdev:
            return datum.preferred_device
        return self._resident_on(datum)
