"""Share of the HBM roofline the megastep program reaches, in %: the least
time for the bytes the traced batches' visits need (``roofline.py``, from
the per-partition visit counts and the partition's real edges and state)
at the chip's HBM bandwidth, over the device time of the ``jit_megastep``
program in the trace.  Nothing without a trace or without a megastep."""
from chipbench import roofline

PROGRAM = "jit_megastep"


def read(record):
    c, t = record.counters, record.trace
    if t is None or c.get("traced_visit_counts") is None:
        return None
    device_s = t.module_s.get(PROGRAM, 0.0)
    if device_s <= 0.0:
        return None
    need = roofline.needed_bytes(c["block_graph"], c["traced_visit_counts"],
                                 c["num_queries"], c["kind"])
    bw = roofline.peaks(record.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / device_s
