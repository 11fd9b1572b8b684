"""Megabytes (1e6 bytes) of file content verified per second: all bytes
of all verify passes in the window over the time from the first pass's
start to the last one's end (host clock)."""


def read(run):
    rounds = [r for r in run.window_rounds() if r["kind"] == "verify"]
    if not rounds:
        return None
    return sum(r["file_bytes"] for r in rounds) / 1e6 / (
        rounds[-1]["t1"] - rounds[0]["t0"])
