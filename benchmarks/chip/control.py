"""The control of the correctness check: runs of a cell whose answers must
come out as not correct.

    python3 benchmarks/chip/control.py --workload paper_batch \\
        --seconds 10 --seeds 11 12 13

Each seed is one run of ``run.py`` in this process, with the program's
multiple-work-transfer path switched on where the configuration states
single transfers, and the answers' statistics taken in float32 where the
service states float64 (``check.py``). Every run prints its result line;
each must read ``"correct": false``. The benchmark's own runs never do
this. With ``--sound`` the same runs drive the program as it is, to read
the compared numbers of sound runs on more seeds for one set-up.
"""
from __future__ import annotations

import argparse
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", default="10")
    ap.add_argument("--seeds", nargs="+", required=True)
    ap.add_argument("--sound", action="store_true")
    args = ap.parse_args(argv)
    rc = 0
    for seed in args.seeds:
        rc |= run.main(["--workload", args.workload, "--seed", seed,
                        "--seconds", args.seconds], control=not args.sound)
    return rc


if __name__ == "__main__":
    sys.exit(main())
