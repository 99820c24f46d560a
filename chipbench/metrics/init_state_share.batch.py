"""Share of the traced window the program spends building a batch's
initial state, in %: the union of its ``fpp.init_state`` spans (host
planes, their upload and the scheduler metadata) over the window."""
from chipbench import spans


def read(record):
    s = spans.recorded()
    return None if s is None else s.share_pct(s.named(spans.INIT_STATE))
