#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload tenants.refit --seed 7 \
        --seconds 20 --trace 0

Set-up (data from the seed, the service and its serving front, the
set-up refits) counts from process start; the window then measures for
`--seconds` and closes at the next cycle boundary; the plain reference
then checks what the window produced. The last line of standard output
is one JSON object (`correct`, `attempted`, `failed`, `metrics`,
`device`, with `--trace 1` also `breakdown`, and the compared numbers
under `checks`). Without enough TPU chips it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
