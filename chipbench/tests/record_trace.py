"""Record the small trace that ``test_trace.py`` reads
(``data/small.xplane.pb.gz``): the tiny frozen-save cell (``tiny.py``)
traced on the chip.

    python3 -m chipbench.tests.record_trace <out_dir>
"""

import gzip
import shutil
import sys
import time

from chipbench import harness, trace
from chipbench.tests.tiny import tiny_parts


def main(out: str) -> int:
    cell = "qwen2.5-32b-2L.frozen-save"
    r = harness.run(cell, 7, 1.0, True, time.perf_counter(), parts=tiny_parts(cell),
                    trace_dir=f"{out}/raw")
    harness.print_result(r)
    with open(trace.find_xplane(f"{out}/raw"), "rb") as src, \
            gzip.open(f"{out}/small.xplane.pb.gz", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
