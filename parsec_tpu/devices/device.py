"""Device registry and module base: accelerator seam of the runtime.

Rebuild of the reference's device MCA framework (reference:
parsec/mca/device/device.h:115-148 module vtable, device.c:79-140
``parsec_get_best_device`` and load counters device.h:159-162): devices
register with the runtime, carry a relative compute weight and a live load,
expose per-device statistics, and the engine picks the best device for a
task by data affinity first, then weighted load.

Memory spaces: space 0 is host RAM; each attached accelerator device gets
the next space index.  DataCopy.device is a memory-space index into this
registry.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from parsec_tpu.core.task import HookReturn, Task
from parsec_tpu.data.data import ACCESS_WRITE, Coherency


class DeviceStats:
    """Per-device counters (reference: device.h:132-137)."""

    __slots__ = ("executed_tasks", "bytes_in", "bytes_out", "faults",
                 "evictions", "fused_launches", "fused_tasks",
                 "chained_launches", "chained_tasks", "chain_programs",
                 "launches",
                 "held_tasks", "defused_waves", "starved_waits",
                 "inflight_waits", "compiles", "warm_waits",
                 "release_passes", "resident_flows", "staged_flows",
                 "snapshot_flows", "snapshot_bytes",
                 "replicas_adopted", "replicas_released",
                 "replica_bytes_peak", "direct_submits", "heads_forced")

    def __init__(self):
        self.executed_tasks = 0
        self.bytes_in = 0
        self.bytes_out = 0
        self.faults = 0
        self.evictions = 0
        #: wavefront launch fusion counters: launches that carried >1 task,
        #: and how many tasks rode them (devices/xla.py manager batching)
        self.fused_launches = 0
        self.fused_tasks = 0
        #: cross-panel chain fusion counters: launches that traced a held
        #: panel chain into a consumer wave, and how many tasks (held +
        #: wave) rode them (devices/xla.py _run_chain)
        self.chained_launches = 0
        self.chained_tasks = 0
        #: chain programs this device built: a (head, successor wave)
        #: structure no device of the process had asked for before,
        #: traced, compiled and called here.  Bounded by the taskpool's
        #: classes and the fused widths, and 0 from a pool's second job
        self.chain_programs = 0
        #: counts at the boundaries of the device module's spans
        #: (devices/xla.py, PERF.md section 3): jitted calls; chain heads
        #: parked without a dispatch (executed_tasks + held_tasks is
        #: every task); waves dispatched as singles because their fused
        #: width was not ready; manager episodes with an empty queue;
        #: launches that waited for room under device_inflight_depth;
        #: first calls of a program (a trace and a compile or a cache
        #: read hides in each) plus the background width compiles;
        #: launches that blocked on a fused width's background compile;
        #: passes of the completer that took at least one task
        #: ((executed_tasks + held_tasks) / release_passes tasks a pass:
        #: 1.0 where it never found more than one handed over)
        self.launches = 0
        self.held_tasks = 0
        self.defused_waves = 0
        self.starved_waits = 0
        self.inflight_waits = 0
        self.compiles = 0
        self.warm_waits = 0
        self.release_passes = 0
        #: flows the managers staged (devices/xla.py _stage_in), by the
        #: branch they took: the device's copy was there and valid, one
        #: hold of the datum's lock and no transfer (resident), or
        #: anything else — a pull from the host or another chip, a
        #: NEW-arena scratch, a COW alias, a pinned snapshot, an evicted
        #: payload (staged).  Their sum is every flow of every task the
        #: device launched or held
        self.resident_flows = 0
        self.staged_flows = 0
        #: of the staged flows, those for which the device module made a
        #: private copy of a payload that was there already — a
        #: version-pinned snapshot, or the alias a copy-on-write fan-out
        #: hands a writer beside its readers — and the bytes of those
        #: copies: on the chip each is a program of its own that reads
        #: and writes the whole payload once more (also in bytes_in)
        self.snapshot_flows = 0
        self.snapshot_bytes = 0
        #: SHARED copies this chip held for counted consumers of another
        #: chip's tile (comm/ici.py expect; pushed over ICI or pulled by
        #: a stage-in), how many of them left again at their last
        #: consumer or their taskpool's end, and the high-water mark of
        #: the bytes they held at once
        self.replicas_adopted = 0
        self.replicas_released = 0
        self.replica_bytes_peak = 0
        #: tasks handed in by the thread that made them ready (a
        #: completer's release, a DTD inserter) without a worker: the
        #: direct hand-in of core/scheduling.schedule on a one-chip
        #: context; every other task came through a worker
        self.direct_submits = 0
        #: held chain heads that went out ALONE (``_force_hold``): no
        #: launch of a declared successor carried them — a reader of a
        #: class they do not declare ran out of patience, a foreign
        #: consumer forced them, or a sync found them still held
        self.heads_forced = 0

    def as_dict(self) -> Dict[str, int]:
        return {k: getattr(self, k) for k in self.__slots__}


class Device:
    """Device module base (reference: parsec_device_module_t).

    ``space`` is the memory-space index (0 = host); ``weight`` is the
    relative throughput used for load balancing (reference: device
    gflops weights); ``load`` counts outstanding work units.
    """

    kind = "base"

    def __init__(self, name: str):
        self.name = name
        self.space = -1          # assigned by the registry
        self.weight = 1.0
        self.load = 0.0
        self._load_lock = threading.Lock()
        self.stats = DeviceStats()
        self.enabled = True
        #: extensible per-device info slots (reference: class/info.h
        #: object arrays on device modules)
        from parsec_tpu.utils.info import InfoObjectArray, device_info
        self.info = InfoObjectArray(device_info, owner=self)

    # -- load accounting (reference: parsec_device_load/sload) ------------
    def load_add(self, units: float) -> None:
        with self._load_lock:
            self.load += units

    def load_sub(self, units: float) -> None:
        with self._load_lock:
            self.load = max(0.0, self.load - units)

    # -- module vtable -----------------------------------------------------
    def submit(self, es, task: Task, spec: Any) -> HookReturn:
        """Take ownership of a device task; return ASYNC on success."""
        raise NotImplementedError

    def flush(self) -> None:
        """Write every dirty device copy back to its host datum."""

    def fini(self) -> None:
        """Stop device threads and release resources."""

    def __repr__(self):
        return f"<Device {self.name} space={self.space} load={self.load:.1f}>"


class HostDevice(Device):
    """Memory space 0: host RAM + inline CPU execution (reference: the
    implicit CPU device, PARSEC_DEV_CPU)."""

    kind = "cpu"

    def __init__(self):
        super().__init__("cpu")
        self.space = 0


class DeviceRegistry:
    """Process-wide device table (reference: parsec_mca_device_* in
    device.c)."""

    def __init__(self, context=None):
        self.context = context
        self.host = HostDevice()
        self.devices: List[Device] = [self.host]

    def attach(self, dev: Device) -> Device:
        """reference: parsec_mca_device_add (device.h:186)."""
        dev.space = len(self.devices)
        self.devices.append(dev)
        return dev

    @property
    def accelerators(self) -> List[Device]:
        return [d for d in self.devices[1:] if d.enabled]

    def get(self, space: int) -> Device:
        return self.devices[space]

    def best_device(self, task: Task) -> Optional[Device]:
        """Pick the execution device for a task (reference:
        parsec_get_best_device, device.c:79-140): honor the owner/preferred
        device of the task's written data when it is an accelerator,
        otherwise the enabled accelerator with the least weighted load.

        A pool carrying a serving-fabric carve stamp
        (``Taskpool.device_spaces``) restricts every choice — affinity
        hints included — to its carved subset, so concurrent tenants
        never share an exclusively-placed device."""
        allowed = getattr(task.taskpool, "device_spaces", None)
        accs = self.accelerators
        if allowed is not None:
            accs = [d for d in accs if d.space in allowed]
        if not accs:
            return None
        # owner computes: a written tile that was pinned to a chip
        # (distribute_devices) keeps its task there, before any hint
        # that would gather a panel onto one chip
        for flow in task.task_class.flows:
            copy = task.data.get(flow.name) \
                if flow.access & ACCESS_WRITE else None
            if copy is None or copy.data is None:
                continue
            pref = copy.data.preferred_device
            if pref is not None and 1 <= pref < len(self.devices) \
                    and self.devices[pref].enabled \
                    and (allowed is None or pref in allowed):
                return self.devices[pref]
        dev = self._coaffinity_device(task)
        if dev is not None and (allowed is None or dev.space in allowed):
            return dev
        for flow in task.task_class.flows:
            if not (flow.access & ACCESS_WRITE):
                continue
            copy = task.data.get(flow.name)
            if copy is None or copy.data is None:
                continue
            datum = copy.data
            # residency affinity: the accelerator already holding the
            # newest valid copy of the written datum wins, avoiding a
            # cross-device migration per write
            v = datum.newest_version()
            for sp, c in datum.copies().items():
                if sp >= 1 and sp < len(self.devices) \
                        and c.coherency != Coherency.INVALID \
                        and c.version == v and c.payload is not None \
                        and self.devices[sp].enabled \
                        and (allowed is None or sp in allowed):
                    return self.devices[sp]
        return min(accs, key=lambda d: d.load / d.weight)

    def _coaffinity_device(self, task: Task) -> Optional[Device]:
        """Panel co-location hint: a task class carrying a 'coaffinity'
        property (locals -> data ref) prefers the device holding that
        datum — e.g. TRSM(m,k)/TSQRT(m,k) follow their panel's diagonal
        tile A(k,k), so the POTRF->TRSM / TSQRT column chain stays on
        ONE device and cross-panel chain fusion (devices/xla.py) can
        trace it into a single launch."""
        coaff = task.task_class.properties.get("coaffinity")
        if coaff is None:
            return None
        try:
            datum = coaff(task.locals).resolve()
        except Exception:
            return None
        pref = datum.preferred_device
        if pref is not None and 1 <= pref < len(self.devices) \
                and self.devices[pref].enabled:
            return self.devices[pref]
        v = datum.newest_version()
        for sp, c in datum.copies().items():
            if 1 <= sp < len(self.devices) \
                    and c.coherency != Coherency.INVALID \
                    and c.version == v and c.payload is not None \
                    and self.devices[sp].enabled:
                return self.devices[sp]
        return None

    def flush_all(self) -> None:
        for d in self.devices[1:]:
            d.flush()

    def fini(self) -> None:
        for d in self.devices[1:]:
            d.fini()

    def dump_stats(self) -> Dict[str, Dict[str, int]]:
        """reference: parsec_mca_device_dump_and_reset_statistics."""
        return {d.name: d.stats.as_dict() for d in self.devices}
