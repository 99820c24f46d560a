"""Share of the traced window the program spends turning the device state
into host results, in %: the union of its ``fpp.finalize`` spans (the
read-back, transposes and the original-id gather) over the window."""
from chipbench import spans


def read(record):
    s = spans.recorded()
    return None if s is None else s.share_pct(s.named(spans.FINALIZE))
