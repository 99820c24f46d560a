"""Share of the query lanes the megastep's visits moved, in %: the
``lane_visits`` (lanes that processed at least one edge in a visit,
summed over the visits) over ``visits`` x ``queries`` that the program's
``fpp.run`` spans in the window carry (its ``SessionResult.stats``
counts).  A program whose runs carry no ``lane_visits`` gives nothing."""
from chipbench import spans


def read(record):
    s = spans.recorded()
    runs = [] if s is None else [r for r in s.named(spans.RUN)
                                 if "lane_visits" in r.args]
    slots = sum(r.args["visits"] * r.args["queries"] for r in runs)
    if not slots:
        return None
    return 100.0 * sum(r.args["lane_visits"] for r in runs) / slots
