"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for.  Otherwise the last line of its output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, last, ``checks``: each number compared with its limit,
which also end its standard error.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
# the TPU runtime logs under /tmp unless told otherwise; a run writes only
# inside its checkout and its own HOME and TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from fdbbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], root=ROOT, t_start=T_START))
