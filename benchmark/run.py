#!/usr/bin/env python3
"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: boots the node (m3_tpu.server.assembly.run_node) in the
process that holds the chip, warms every shape, measures, compares the
window's own answers with the plain reference and prints one JSON line
last.  Fails, with no result, where JAX finds no TPU.  See README.md.
"""

import sys
import time

T_PROCESS = time.monotonic()

if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmark import harness

    sys.exit(harness.main(t_process=T_PROCESS))
