"""Share of the traced window the program spends in the NCP's sweep cuts,
in %: the union of its ``fpp.sweep`` spans (``FPPSession.ncp``'s sweep of
the batch's PPR vectors) over the window.  The name is spelled here, so a
program without the span gives nothing."""
from chipbench import spans

SWEEP = "fpp.sweep"


def read(record):
    s = spans.recorded()
    return None if s is None else s.share_pct(s.named(SWEEP))
