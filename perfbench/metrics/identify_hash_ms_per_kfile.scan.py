"""Milliseconds per 1,000 files of the window's scan rounds spent in
identifier hash-phase seconds, staging and device together
(sd_identifier_phase_seconds_total{phase="hash"}, a program counter);
the window delta."""


def read(run):
    files = sum(r["files"] for r in run.window_rounds()
                if r["kind"] == "scan")
    delta = run.counter_delta(
        "sd_identifier_phase_seconds_total", {"phase": "hash"}, "value")
    if not files or delta is None:
        return None
    return delta * 1e3 / (files / 1e3)
