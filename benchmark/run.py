#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs ONE cell of BENCHMARK.json in this process, on the machine it is started
on, and prints one JSON object as the last line of its standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` in a traced run).  With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.  It exits
non-zero, with no result line, where JAX finds no accelerator or not the
chips the cell is defined on: there is no CPU fallback.

benchmark/harness/manifest.py says how a cell's files are found by name.
"""

import time

T_START = time.perf_counter()      # set-up is counted from here

import os      # noqa: E402
import sys     # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.harness import cell

    return cell.main(sys.argv[1:], T_START)


if __name__ == "__main__":
    sys.exit(main())
