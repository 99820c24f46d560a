"""Device-to-host reads per megastep chunk: the ``chunk_reads`` over the
``chunks`` that the program's ``fpp.run`` spans in the window carry (its
``SessionResult.stats`` counts)."""
from chipbench import spans


def read(record):
    s = spans.recorded()
    runs = [] if s is None else [r for r in s.named(spans.RUN)
                                 if "chunks" in r.args]
    chunks = sum(r.args["chunks"] for r in runs)
    if not chunks:
        return None
    return sum(r.args["chunk_reads"] for r in runs) / chunks
