"""Readings of the lower-precision control, beside the program's, for the
limits of a cell's comparison:

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process, it makes a whole run of the cell and replays
the reference twice: in float64, and in bfloat16 (the precision below the
configuration's float32) in the program's place.  Before
each run's result line it prints one JSON line with both sets of numbers
and the names of the numbers the control fails.  The benchmark's own runs
never run the control.
"""
import argparse
import sys

import run  # noqa: F401  -- fixes the compile cache and the import path, as for a run


def main(argv=None) -> int:
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        rc = harness.run(
            ["--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", "0"],
            control=True,
        )
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
