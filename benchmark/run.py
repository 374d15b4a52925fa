"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once, in this one process, which owns
the chip. Anything but a TPU with the chips the cell asks for ends the
run non-zero within seconds with no result line. It starts no child and
leaves nothing running. The last line of stdout is the result object.
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _main() -> int:
    # The script's own directory leads sys.path; the package is found
    # from the checkout's root.
    sys.path[0] = ROOT
    from benchmark import harness

    rc = harness.main(sys.argv[1:], T_START, ROOT)
    sys.stdout.flush()
    sys.stderr.flush()
    return rc


if __name__ == "__main__":
    # Daemon threads of the program (tickers, router workers) and the
    # accelerator runtime must not outlive the result line.
    try:
        rc = _main()
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        rc = 1
    os._exit(rc)
