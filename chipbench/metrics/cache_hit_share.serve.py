"""Share of the window's requests answered from the result cache, in %:
``GraphServer.stats()["cache_hits"]`` over the window, over requests
sent."""


def read(record):
    sent = record.counters.get("requests")
    if not sent:
        return None
    return 100.0 * record.counters["cache_hits"] / sent
