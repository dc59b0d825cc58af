"""The control, on the chip at the cell's own size: a run that must come out
as not correct.

    python -m benchmark.control --fault stale_reads --workload <cell> --seed <n> --seconds <s>

The same run as ``benchmark.run`` makes, with one guarantee of the
configuration broken underneath (``benchmark/faults.py``).  ``stale_reads`` is
the control proper; the others are the faults the tests plant on the CPU.
Prints the same lines; ``correct`` has to read false.  No benchmark run uses
this module.
"""

from __future__ import annotations

import argparse
import sys

from benchmark import run
from benchmark.faults import FAULTS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", choices=FAULTS, default="stale_reads")
    args, rest = ap.parse_known_args(argv)
    return run.main(rest, fault=args.fault)


if __name__ == "__main__":
    sys.exit(main())
