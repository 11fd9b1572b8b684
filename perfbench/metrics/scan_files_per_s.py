"""Files identified per second: all files of all scan rounds in the
window over the time from the first round's start to the last one's end
(host clock)."""


def read(run):
    rounds = [r for r in run.window_rounds() if r["kind"] == "scan"]
    if not rounds:
        return None
    return sum(r["files"] for r in rounds) / (
        rounds[-1]["t1"] - rounds[0]["t0"])
