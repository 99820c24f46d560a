"""Share of the traced window the program spends building a batch's
engine and megastep program, in %: the union of its ``fpp.engine`` spans
(the Δ-window, device graph, visit and megastep programs) and of the
``fpp.dispatch`` spans whose call traced the megastep (``traces`` > 0)
over the window."""
from chipbench import spans


def read(record):
    s = spans.recorded()
    if s is None or not s.named(spans.ENGINE):
        return None
    traced = [d for d in s.named(spans.DISPATCH) if d.args.get("traces", 0)]
    return s.share_pct(s.named(spans.ENGINE) + traced)
