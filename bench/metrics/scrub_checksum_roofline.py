"""Roofline share of the scrubber's checksum program, %: the least time
the chip needs to read the bytes the scrubber checksummed in the window
(every word once, at the HBM peak; the checksum is bound by bytes), over
the device time of that program's ops in the trace.  The program is the
storage-word view of each leaf and its weighted sum, in XLA's own ops:
the block-hash kernel does not run on the scrub path.  The bytes are
those of the leaves each ``verify_state`` and ``scrub`` call of the
window's steps checksummed (``bench/drivers/train.py``)."""
from bench import trace
from bench.common import peak_flops_bytes


def read(view):
    tr = view.get("train")
    if not tr:
        return None
    seconds = view["trace"]["program_s"].get(trace.CHECKSUM_PROGRAM, 0.0)
    if seconds <= 0 or tr["checksummed_bytes"] <= 0:
        return None
    bw = peak_flops_bytes(view["device"]["kind"])[1]
    return 100.0 * (tr["checksummed_bytes"] / bw) / seconds
