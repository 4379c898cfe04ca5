#!/usr/bin/env python3
"""Stand the whole host still inside a run's window, by hand on the chip.

    python3 cellbench/tests/stall_probe.py <after_s> <stall_s> -- \
        --workload <cell> --seed <n> --seconds 30 --trace 0

Starts ``BENCHMARK.json``'s command with the arguments after ``--`` in a
process group of its own, waits for the harness's "window open" note,
and ``after_s`` seconds later stops every process of the group (the node
and its load generators together, as a host-wide stall does) for
``stall_s`` seconds.  The run's own output passes through; its last line
is the result.  QoS 1 has to hold over such a stall: the result has to
read ``correct`` with ``missing`` 0 (PERF.md 7).  This process never
imports JAX, so the chip stays the run's.
"""

import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv) -> int:
    cut = argv.index("--")
    after_s, stall_s = float(argv[0]), float(argv[1])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    p = subprocess.Popen(command + argv[cut + 1:], cwd=ROOT,
                         stderr=subprocess.PIPE, text=True,
                         start_new_session=True)
    stalled = False
    for line in p.stderr:
        sys.stderr.write(line)
        if not stalled and "window open" in line:
            stalled = True
            time.sleep(after_s)
            os.killpg(p.pid, signal.SIGSTOP)
            time.sleep(stall_s)
            os.killpg(p.pid, signal.SIGCONT)
            print(f"stall_probe: the group stood still for {stall_s:g} s, "
                  f"{after_s:g} s into the window", file=sys.stderr,
                  flush=True)
    rc = p.wait()
    if not stalled:
        print("stall_probe: no window was opened", file=sys.stderr)
        return rc or 1
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
