"""Share of the traced window in which no operation ran on the device, in
%: 1 - (union of the device's op intervals / window), from the trace."""
from chipbench.trace import idle_share_pct


def read(record):
    return idle_share_pct(record.trace)
