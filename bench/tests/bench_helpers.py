"""Shared set-up of the benchmark's CPU tests: a copy of the benchmark in
a temporary root with cells cut to a size a CPU runs in seconds."""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CPU_PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
# tiny cells: (workload, configuration, traffic, cell whose limits it takes)
TINY = (
    ("tiny_bal", "tiny_brunel", "steady_rates_c20", "bal_stdp_k1"),
    ("tiny_ckpt", "tiny_brunel", "ckpt_every20_restore", "bal_stdp_ckpt"),
    ("tiny_mc", "tiny_microcircuit", "steady_raster_c5", "mc_full_k1"),
)


def _tiny_configs(bench: str) -> None:
    from repro.builder.rules import microcircuit_rules

    with open(os.path.join(bench, "configs", "brunel2000_stdp.json")) as f:
        bal = json.load(f)
    bal["builder_args"]["n"] = bal["n"] = 400
    bal["populations"] = {"E": 320, "I": 80}
    bal["in_degree"] = {"E": 32, "I": 8}
    with open(os.path.join(bench, "configs", "pd14_microcircuit.json")) as f:
        mc = json.load(f)
    mc["builder_args"]["scale"] = 0.02
    spec = microcircuit_rules(scale=0.02)
    mc["n"] = spec.n
    mc["populations"] = {p.name: p.n for p in spec.populations}
    for name, cfg in (("tiny_brunel", bal), ("tiny_microcircuit", mc)):
        with open(os.path.join(bench, "configs", f"{name}.json"), "w") as f:
            json.dump(cfg, f)


def tiny_root(dst: str) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/`` into ``dst`` and add the
    tiny cells; returns ``dst``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    bench = os.path.join(dst, "bench")
    shutil.copytree(
        os.path.join(REPO, "bench"), bench,
        ignore=shutil.ignore_patterns("tests", "__pycache__"),
    )
    _tiny_configs(bench)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for name in ("tiny_brunel", "tiny_microcircuit"):
        bm["configs"].append(dict(
            name=name, source="test", file=f"bench/configs/{name}.json",
            reduced=[], why="test"))
    for work, cfg, traffic, like in TINY:
        bm["workloads"].append(dict(
            name=work, config=cfg, traffic=traffic, chips=1, why="test"))
        shutil.copy(os.path.join(bench, "limits", f"{like}.json"),
                    os.path.join(bench, "limits", f"{work}.json"))
        for m in bm["end_to_end"] + bm["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(work)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f, indent=1)
    return dst


def run_cell(root: str, workload: str, seed: int = 2**31 + 17,
             seconds: float = 1.0, trace: int = 0, capsys=None):
    """Drive a whole run on the CPU (the harness's look for a chip is
    skipped); returns (exit code, result dict or None)."""
    from bench import harness

    rc = harness.run(
        ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        require_tpu=False, root=root, peaks=CPU_PEAK,
    )
    result = None
    if capsys is not None:
        out = capsys.readouterr().out.strip().splitlines()
        result = json.loads(out[-1]) if out else None
    return rc, result


# faults of the network builder that the run's check has to catch
BUILDER_FAULTS = ("inhibitory_sign_lost", "rule_dropped", "delay_wrong",
                  "synapses_dropped")


def faulty_spec(spec, fault: str):
    """``spec`` (a RuleSpec) with one builder fault planted: every weight
    made excitatory, the last rule left out, every delay one step off
    (a uniform range one step short), or a tenth of the synapses dropped
    (one source fewer per row for fixed in-degrees)."""
    import dataclasses

    def each(change):
        return tuple(change(r) for r in spec.rules)

    if fault == "inhibitory_sign_lost":
        rules = each(lambda r: dataclasses.replace(
            r, weight_mu=abs(r.weight_mu), weight_scale=abs(r.weight_scale)))
    elif fault == "rule_dropped":
        rules = spec.rules[:-1]
    elif fault == "delay_wrong":
        rules = each(lambda r: dataclasses.replace(r, delay_uniform=r.delay_uniform - 1)
                     if r.delay_uniform else dataclasses.replace(r, delay=r.delay + 1))
    elif fault == "synapses_dropped":
        rules = each(lambda r: dataclasses.replace(r, p=0.9 * r.p)
                     if r.p else dataclasses.replace(r, fan_in=r.fan_in - 1))
    else:
        raise ValueError(fault)
    return dataclasses.replace(spec, rules=rules)


def plant_builder_fault(monkeypatch, fault: str) -> None:
    """Make the configurations' rule builders plant ``fault``."""
    from repro.builder import rules

    for name in ("balanced_ei_rules", "microcircuit_rules"):
        real = getattr(rules, name)
        monkeypatch.setattr(
            rules, name, lambda *a, _real=real, **kw: faulty_spec(_real(*a, **kw), fault))
