"""Host time per megastep chunk outside the blocking read, in ms: the
``fpp.harvest`` spans (the chunk's other device-to-host reads) and the
``fpp.dispatch`` spans that did not trace (``traces`` 0) inside the
window, over the window's chunks (one ``fpp.dispatch`` each)."""
from chipbench import spans


def read(record):
    s = spans.recorded()
    chunks = [] if s is None else s.named(spans.DISPATCH)
    if not chunks:
        return None
    host = s.named(spans.HARVEST) + [d for d in chunks
                                     if not d.args.get("traces", 0)]
    return sum(s.clipped_ns(x) for x in host) / len(chunks) / 1e6
