"""Share of the traced window, in %, in which no operation ran on the
chip: 1 - busy union / window (device trace)."""


def read(run):
    red = run.trace_reduction
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
