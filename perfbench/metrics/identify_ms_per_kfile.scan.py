"""Milliseconds per 1,000 files of the window's scan rounds spent in
FileIdentifierJob run seconds
(sd_job_duration_seconds{name="file_identifier"}, a program counter);
the window delta."""


def read(run):
    files = sum(r["files"] for r in run.window_rounds()
                if r["kind"] == "scan")
    delta = run.counter_delta(
        "sd_job_duration_seconds", {"name": "file_identifier"}, "sum")
    if not files or delta is None:
        return None
    return delta * 1e3 / (files / 1e3)
