"""Milliseconds per 1,000 files of the window's scan rounds spent fetching
the digests into CAS-ID strings (unfinished device time included), in
the device CAS path (sd_cas_phase_seconds_total{phase="fetch"}, a
program counter taken in ops/staging.py and ops/blake3_jax.py); the
window delta. None where the program has no such counter."""


def read(run):
    files = sum(r["files"] for r in run.window_rounds()
                if r["kind"] == "scan")
    delta = run.counter_delta(
        "sd_cas_phase_seconds_total", {"phase": "fetch"}, "value")
    if not files or delta is None:
        return None
    return delta * 1e3 / (files / 1e3)
