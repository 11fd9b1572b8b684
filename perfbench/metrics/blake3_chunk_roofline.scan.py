"""The Pallas BLAKE3 chunk kernel's share of the HBM roofline, in %:
the CAS message bytes of every file the window's rounds identified
(8-byte size prefix plus the whole file or its 57,344-byte sample; no
padding) over the chip's published HBM bandwidth, divided by the
kernel's device time in the trace (ops named _chunk_cvs_pallas*)."""

from perfbench import work

KERNEL = "_chunk_cvs_pallas"


def read(run):
    red = run.trace_reduction
    if red is None:
        return None
    secs = sum(s for name, s in red["kernel_s"].items()
               if name.startswith(KERNEL))
    files = [f for r in run.window_rounds() if r["kind"] == "scan"
             for f in r["sizes"]]
    return work.roofline_percent(work.cas_message_bytes(files), secs,
                                 run.device_kind)
