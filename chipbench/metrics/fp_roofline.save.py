"""Share of its roofline that the Pallas fingerprint kernel
(``kernels/fingerprint.py`` ``_fingerprint_kernel``; in the trace, the
``custom-call`` named after ``fingerprint_chunks_pallas``) reaches over the traced
saves: each chunk byte read once and 16 bytes written per chunk, at the
chip's HBM bandwidth, over the kernel's device time. Padding of rows to the
largest chunk is the implementation's, not the algorithm's, and is not
counted."""

from chipbench.trace import op_seconds

KERNEL = ("%fingerprint_chunks_pallas", "custom-call(")


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    if t is None or not c.get("wave_bytes"):
        return None
    secs = op_seconds(t, KERNEL)
    if secs <= 0:
        return None
    least = (c["wave_bytes"] + 16 * c["chunks"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
