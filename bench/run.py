"""Benchmark entry point, run from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

(``python3 -m bench.run`` is the same).  It needs a TPU with the chips the
cell asks for, and exits non-zero, printing no result, without one.  JAX's
persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
and ``.jax_cache`` in the checkout otherwise.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# cache every program, so that only a cell's first run in a checkout compiles
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    from bench import harness

    return harness.run(sys.argv[1:], t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
