"""95th percentile of the time requests waited for a lane, in ms: the
server's own ``GraphResponse.stats["queue_wait_s"]`` (submit to
admission; a cache hit's wait for the delivery lane), over every answered
request of the window."""
import numpy as np


def read(record):
    waits = record.counters.get("queue_wait_s")
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits) * 1e3, 95,
                               method="higher"))
