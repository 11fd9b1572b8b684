"""95th percentile of the browse client's search.paths latency, in ms,
over every query due in the window, each timed from its due time (host
clock, browse.py). A failed query counts as an hour, over any limit."""

import math

FAILED_MS = 3_600_000.0


def read(run):
    if not run.queries:
        return None
    lat = sorted((done - due) * 1e3 if ok else FAILED_MS
                 for due, _sent, done, ok in run.queries)
    return lat[math.ceil(0.95 * len(lat)) - 1]
