"""Host syncs billed to a request that ran on a lane (neither a cache hit
nor a coalesced follower): the mean of ``GraphResponse.stats
["host_syncs"]``, the megastep chunks its lane was in flight for."""
import numpy as np


def read(record):
    syncs = record.counters.get("lane_host_syncs")
    if not syncs:
        return None
    return float(np.mean(syncs))
