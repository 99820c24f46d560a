"""Edges relaxed per query: the program's exact per-query edge counters
(``SessionResult.edges_processed``) summed over the window, over the
queries completed.  Revisits show here as a rise over the edge count."""


def read(record):
    c = record.counters
    if "edges" not in c or not c.get("queries"):
        return None
    return c["edges"] / c["queries"]
