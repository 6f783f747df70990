"""Readings of the structure check (``bench/structure.py``) on a
configuration's network as built, and with each planted builder fault,
at the configuration's own size:

    python3 bench/tests/builder_fault_readings.py <config> [<fault> ...]

It builds on the host only (no device) and prints one JSON line per
network: the fault (``none`` for the network as built), the two numbers
and the parts of each.  These are the readings the limits of
``structure_diff`` and ``structure_z`` are set between.
"""
import json
import os
import sys
import time

import bench_helpers  # noqa: F401  -- puts the repository on the import path
from bench import harness, spec, structure


def main(argv) -> int:
    from repro.builder.procedural import build_network

    name, faults = argv[0], argv[1:] or ["none", *bench_helpers.BUILDER_FAULTS]
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    model = spec.load_model(spec.BENCH_DIR, cfg)
    for fault in faults:
        t = time.perf_counter()
        rules = harness.build_spec(cfg)
        if fault != "none":
            rules = bench_helpers.faulty_spec(rules, fault)
        net = build_network(rules, k=int(cfg.get("k", 1)))
        built = time.perf_counter() - t
        numbers, lines = structure.check(
            harness.reference_network(net, model, copy_weights=False), cfg, model)
        print(json.dumps(dict(config=name, fault=fault, m=int(net.m),
                              build_s=built, **numbers, parts=lines[:2])),
              flush=True)
        del net
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
