"""Layer: ICI transport.  The most a chip held at once in replicas of
other chips' tiles: the largest ``replica_bytes_peak`` of the chips'
``DeviceStats`` (a high-water mark of the whole run, warm jobs included:
every job runs the same DAG) / 2^30.  Replicas leave at their last
consumer; without that a chip ends a job holding a copy of every tile it
ever read.  None where the program has no such counter (the parent of
PR 27) or has adopted no replica."""


def read(run):
    peaks = [d["stats"].get("replica_bytes_peak") for d in run["devices"]]
    peaks = [p for p in peaks if p]
    return max(peaks) / 2 ** 30 if peaks else None
