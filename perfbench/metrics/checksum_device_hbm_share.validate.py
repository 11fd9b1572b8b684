"""The verify passes' share of the HBM roofline on the chip, in %: the
bytes of every file verified in the window over the chip's published
HBM bandwidth, divided by the device's busy time in the trace. The
checksum runs in two programs (the Pallas chunk kernel over batched
files up to 4 MiB, the sequence-sharded stream above), so this is the
share of all device work, not of one kernel."""

from perfbench import work


def read(run):
    red = run.trace_reduction
    if red is None:
        return None
    sizes = [s for r in run.window_rounds() if r["kind"] == "verify"
             for s in r["sizes"]]
    return work.roofline_percent(work.checksum_message_bytes(sizes),
                                 red["busy_s"] * red["devices"],
                                 run.device_kind)
