"""Share of its roofline that the cut stage of the save waves reaches over
the traced saves.

The stage is every device op of the wave program (``ops._wave_impl``; in
the trace, the module ``jit__wave_impl``) but the fingerprint kernel: the
byte split, the gear-table lookup, the Pallas cut kernel
(``kernels/cdc.py`` ``_cdc_cut_kernel``), the chunk table and the packing
of rows. Its time is the union of those ops' intervals. A change that
moves work between them, such as the lookup into the cut kernel, moves
this share the way ``save_s`` moves.

The bytes are those the algorithm needs, not today's layout: each byte of
the waves' segments read once, and 4 bytes written per cut. The cuts are
at most the chunks less one tail per leaf. Least time = bytes over the
chip's HBM bandwidth; the share is that over the stage's device time.
"""

from chipbench.trace import op_seconds

PROGRAM = "jit__wave_impl"
FP_KERNEL = ("%fingerprint_chunks_pallas", "custom-call(")


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    if t is None or not c.get("wave_bytes"):
        return None
    secs = t["module_s"].get(PROGRAM, 0.0) - op_seconds(t, FP_KERNEL)
    if secs <= 0:
        return None
    cuts = max(0, c["chunks"] - c["leaves_waved"])
    least = (c["wave_bytes"] + 4 * cuts) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
