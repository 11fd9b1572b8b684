"""Milliseconds per second of the window by which the node's event loop
woke its fixed 100 ms lag probe late: the sum of
sd_event_loop_lag_seconds (a program histogram, node.py LoopLagProbe)
over the window, per window second. Time the loop spent in callbacks such
as search.paths. None where the program has no such histogram."""


def read(run):
    lag = run.counter_delta("sd_event_loop_lag_seconds", None, "sum")
    if lag is None or run.t1 <= run.t0:
        return None
    return lag * 1e3 / (run.t1 - run.t0)
