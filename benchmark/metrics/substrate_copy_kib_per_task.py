"""Layer: data substrate.  KiB the device module copied a task:
``DeviceStats.bytes_in`` — what it staged in from the host or another
chip, and the private copies it made on the chip for pinned snapshots
and copy-on-write aliases — summed over the chips, over the tasks the
devices ran (``executed_tasks`` + ``held_tasks``), all since the Context
started (the counters are never reset, so warm jobs count on both
sides).  The program counts a snapshot or a COW copy in ``bytes_in``
AND in ``snapshot_bytes`` (the part of ``bytes_in`` made on the chip),
so ``bytes_in`` alone counts every copied byte once.  0 where tiles are
born on the chip and only halos cross a tile boundary as outputs of the
sweep itself; 131 072 where one 128 MiB tile is copied a task.  (The
program has no reshape step on this path, so no reshape bytes are
added: PERF.md section 3.)  None where the devices report no
``bytes_in`` or ran no task."""


def read(run):
    stats = [d["stats"] for d in run["devices"]]
    if not stats or any("bytes_in" not in s for s in stats):
        return None
    tasks = sum(s.get("executed_tasks", 0) + s.get("held_tasks", 0)
                for s in stats)
    if tasks <= 0:
        return None
    return sum(s["bytes_in"] for s in stats) / tasks / 1024.0
