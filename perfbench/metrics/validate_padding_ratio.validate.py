"""Bytes of the padded grids the validator hashes its files in, over the
files' own bytes: sd_validator_bytes_total{kind="grid"} over
{kind="file"}, both paths (program counters taken in ops/blake3_jax.py
and ops/seqhash.py); window deltas. None where the program has no such
counter."""


def read(run):
    grid = run.counter_delta("sd_validator_bytes_total", {"kind": "grid"})
    file_bytes = run.counter_delta(
        "sd_validator_bytes_total", {"kind": "file"})
    if not grid or not file_bytes:
        return None
    return grid / file_bytes
