"""Milliseconds per 1e9 file bytes of the window's verify passes the
validator's device path spent filling grid and window buffers
(bytearray and bytes copies included)
(sd_validator_phase_seconds_total{phase="pack"}, both paths, a program
counter taken in objects/validator.py, ops/blake3_jax.py and
ops/seqhash.py); the window delta. None where the program has no such
counter."""


def read(run):
    gb = sum(r["file_bytes"] for r in run.window_rounds()
             if r["kind"] == "verify") / 1e9
    delta = run.counter_delta(
        "sd_validator_phase_seconds_total", {"phase": "pack"}, "value")
    if not gb or delta is None:
        return None
    return delta * 1e3 / gb
