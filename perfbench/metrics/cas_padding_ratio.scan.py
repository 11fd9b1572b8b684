"""Bytes of the word grids the device CAS path hands to the device, after
row and batch padding, over the CAS message bytes inside them (8-byte size
prefix plus payload): sd_cas_device_bytes_total over
sd_cas_message_bytes_total, program counters taken in ops/blake3_jax.py;
window deltas. None where the program has no such counters."""


def read(run):
    grid = run.counter_delta("sd_cas_device_bytes_total")
    message = run.counter_delta("sd_cas_message_bytes_total")
    if not grid or not message:
        return None
    return grid / message
