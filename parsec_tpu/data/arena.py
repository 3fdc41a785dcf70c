"""Arenas: shaped freelist allocators for temporaries.

Rebuild of the reference's arena system (reference: parsec/arena.{c,h}):
an arena defines the "shape" (size/alignment/datatype) of the temporary
buffers a taskpool needs for network staging and NEW flows; allocation goes
through a freelist so steady-state execution allocates nothing.  Here the
shape is (shape, dtype) of a numpy buffer, and ``ArenaDatatype`` pairs an
arena with a layout tag the way parsec_arena_datatype_t pairs arena+MPI
datatype (reference: parsec/parsec_internal.h:41-45).
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional, Tuple

import numpy as np

from parsec_tpu.data.data import Coherency, Data, DataCopy


class Arena:
    #: guards DataCopy.arena_refs mutations: repo-entry holds are taken
    #: and dropped from different worker threads (release_deps vs a
    #: predecessor's retirement), and a lost update would either free a
    #: chained NEW-flow buffer early (corruption) or leak it.  One
    #: class-level lock — the critical sections are a few instructions
    _refs_lock = threading.Lock()

    def __init__(self, shape: Tuple[int, ...], dtype: Any = np.float32,
                 max_cached: int = 256):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.elt_size = int(np.prod(self.shape)) * self.dtype.itemsize
        self._lock = threading.Lock()
        self._free: List[np.ndarray] = []
        self._max = max_cached
        self.allocated = 0   # live stats (reference: arena used/released counts)
        self.released = 0

    def get_buffer(self) -> np.ndarray:
        with self._lock:
            if self._free:
                return self._free.pop()
            self.allocated += 1
        return np.empty(self.shape, self.dtype)

    def release_buffer(self, buf: np.ndarray) -> None:
        with self._lock:
            self.released += 1
            if len(self._free) < self._max:
                self._free.append(buf)

    def unbacked_buffer(self) -> np.ndarray:
        """What a copy holds whose first writer is expected on a device:
        the arena's shape, dtype and nbytes over ONE element (a
        zero-stride, read-only view), so a tile that is materialized in
        device memory (XlaDevice._stage_in) never costs a host buffer.
        A host body that comes to write it gets a real buffer at its
        stage-in (engine.stage_in_host)."""
        return np.broadcast_to(np.zeros((), self.dtype), self.shape)

    def get_copy(self, data: Optional[Data] = None, device: int = 0,
                 backed: bool = True) -> DataCopy:
        """Allocate a fresh arena-backed copy, optionally attached to a datum
        (reference: parsec_arena_get_copy, arena.h:136).  ``backed`` False
        hands out the copy over :meth:`unbacked_buffer`."""
        buf = self.get_buffer() if backed else self.unbacked_buffer()
        if data is None:
            data = Data(nb_elts=self.elt_size)
        copy = DataCopy(data, device, payload=buf,
                        coherency=Coherency.EXCLUSIVE, version=0)
        copy.arena = self
        if data.copy_on(device) is None:
            data.attach_copy(copy)
        return copy

    def release_copy(self, copy: DataCopy) -> None:
        if copy.arena is not self:
            raise ValueError("copy does not belong to this arena")
        # Swap payload->None under _refs_lock so racing releasers (repo
        # retirement vs device completer, both legitimately observing
        # refs==0) cannot both see a non-None payload and double-free the
        # buffer onto the freelist.
        with Arena._refs_lock:
            buf, copy.payload = copy.payload, None
            if buf is not None:
                copy.coherency = Coherency.INVALID
        if buf is None or (isinstance(buf, np.ndarray)
                           and not buf.flags.writeable):
            return    # already released (idempotent: multiple lifetime
                      # managers may race to the same conclusion), or
                      # never backed: nothing for the freelist
        self.release_buffer(buf)

    # -- repo-entry holds (reference: refcounted repo copies,
    # datarepo.h:50-58 — a NEW-flow buffer chained through several tasks
    # is registered in every producer's entry; only the LAST drop may
    # return it to the freelist) -----------------------------------------
    def retain_copy(self, copy: DataCopy) -> None:
        with Arena._refs_lock:
            copy.arena_refs += 1

    def drop_copy(self, copy: DataCopy) -> None:
        """Drop one hold; frees the buffer when the count reaches zero."""
        with Arena._refs_lock:
            copy.arena_refs -= 1
            free = copy.arena_refs <= 0
        if free:
            self.release_copy(copy)

    def release_unheld(self, copy: DataCopy) -> None:
        """Free only if NO entry holds the copy (supersede/remote-only
        paths, where the releasing site is not itself a hold owner)."""
        with Arena._refs_lock:
            held = copy.arena_refs > 0
        if not held:
            self.release_copy(copy)


class ArenaDatatype:
    """Arena + layout tag pair, registered per flow datatype
    (reference: parsec_arena_datatype_t)."""

    def __init__(self, arena: Arena, dtt: Any = None):
        self.arena = arena
        self.dtt = dtt if dtt is not None else (arena.shape, arena.dtype.str)
