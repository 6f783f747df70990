"""Chip smoke test: the paper's workflow on the full-scale cortical
microcircuit (Potjans & Diesmann 2014: n = 77,169 neurons, ~0.28B
synapses), through ``Session``, on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the SPMD engine on four chips

One chip: build procedurally (``microcircuit_rules(scale=1.0)``, k=1),
simulate 50 steps (5 ms at dt = 0.1 ms) with rate monitors and check
that every population fires at a finite, non-silent, non-runaway rate;
snapshot, continue 10 steps, restore the snapshot in a second session,
run the same 10 steps and require bit-identical spike counts.

Four chips: build the same network at k=4 (uniform partitions, the SPMD
engine), check that its partition-sharded state sits on four distinct
devices, run 20 steps and compare with the k=1 run of the same network
on one chip, in this process.  ``--scale`` shrinks the microcircuit
(``microcircuit_rules(scale=...)``: neurons and in-degrees both scale)
for a cheaper run of either phase.

The step counts are short because the TPU step is slow: the XLA gather
over the ≈484M ELL slots of the scale-1.0 network takes ≈3.6 s per step
on one v5e chip, so a run of 1,000 steps alone would take an hour.  Both engines run the same XLA step; they
differ in each partition's ELL width, so the TPU's reduction order (and
from there the chaotic trajectory) may differ: bit-exact spike counts are
reported when they hold, otherwise the first diverging step, and
per-population rates must then agree within 5 standard deviations of the
Poisson count noise of two runs.  The index exchange must drop no spike.

The script exits non-zero, printing no result, unless JAX finds a TPU.
Every wall time it prints is a smoke timing, not a benchmark.  The last
line of its output is the JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

SNAPSHOT = os.path.join(REPO, ".smoke_snapshot")
SCALE = 1.0
STEPS = 50  # 5 ms of biological time at dt = 0.1 ms
CONTINUE_STEPS = 10
FOUR_CHIP_STEPS = 20
CHUNK = 10
# a population averaging under SILENT_HZ is silent; over
# RUNAWAY_HZ (half the 500 Hz ceiling of the 2 ms refractory period) it
# is running away
SILENT_HZ = 0.05
RUNAWAY_HZ = 250.0
RATE_SIGMAS = 5.0


def log(msg: str) -> None:
    print(msg, flush=True)


def host_memory() -> str:
    """This process's peak resident host memory so far."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss  # KiB
    return f"host peak RSS {peak / 1e6:.2f} GB"


class ChunkClock:
    """Monitor that stamps the host clock as each chunk's outputs land on
    the host (which waits for the device): the first chunk includes the
    step program's compilation."""

    requires = frozenset()

    def begin(self, session) -> None:
        self.t0 = time.perf_counter()
        self.stamps = []

    def on_chunk(self, t0, outs) -> None:
        self.stamps.append(time.perf_counter())

    def finalize(self) -> None:
        pass

    def chunk_seconds(self):
        edges = [self.t0] + self.stamps
        return [b - a for a, b in zip(edges, edges[1:])]


def population_rates(spec, ses, per_neuron_hz, steps):
    """Mean rate (Hz) and spike count per population, mapped from the
    session's row labelling to the spec's permanent ids (padding rows of
    uniform partitions have ids >= n and are dropped)."""
    ids = ses.permanent_ids
    real = ids < spec.n
    r = np.zeros(spec.n)
    r[ids[real]] = per_neuron_hz[real]
    seconds = steps * ses.dt * 1e-3
    out = {}
    for pop, (a, b) in zip(spec.populations, spec.offsets().values()):
        hz = float(r[a:b].mean())
        out[pop.name] = dict(hz=hz, spikes=hz * (b - a) * seconds)
    return out


def check_rates(rates, every_population_fires: bool = True) -> None:
    """Finite, non-runaway rates; non-silent per population, or (over
    windows too short for the smallest populations to fire reliably)
    for the network as a whole."""
    for name, r in rates.items():
        hz = r["hz"]
        if not np.isfinite(hz):
            raise AssertionError(f"population {name}: rate is not finite")
        if every_population_fires and hz < SILENT_HZ:
            raise AssertionError(f"population {name} is silent: {hz} Hz")
        if hz > RUNAWAY_HZ:
            raise AssertionError(f"population {name} runs away: {hz} Hz")
    if sum(r["spikes"] for r in rates.values()) == 0:
        raise AssertionError("the network is silent")


def simulate(spec, ses, label, steps):
    """``steps`` steps with rate monitors; prints and returns
    (per-population rates, spike_count per step, RunResult)."""
    from repro.snn.monitors import PerNeuronRateMonitor, RateMonitor

    rate, per_neuron, clock = RateMonitor(), PerNeuronRateMonitor(), ChunkClock()
    res = ses.run(steps, monitors=[rate, per_neuron, clock], chunk_size=CHUNK)
    secs = clock.chunk_seconds()
    steady = float(np.median(secs[1:])) if len(secs) > 1 else float("nan")
    log(f"[{label}] first chunk (compile + {CHUNK} steps): {secs[0]:.3f} s; "
        f"steady chunk: {steady:.4f} s "
        f"(smoke timing, not a benchmark)")
    if not np.all(np.isfinite(rate.rates)):
        raise AssertionError(f"[{label}] network rate is not finite")
    log(f"[{label}] network rate over {steps} steps: "
        f"{float(rate.rates.mean()):.4f} Hz; {host_memory()}")
    rates = population_rates(spec, ses, per_neuron.rates, steps)
    for name, r in rates.items():
        log(f"[{label}]   {name:5s} {r['hz']:9.4f} Hz")
    return rates, rate.counts, res


def config():
    from repro.snn import SimConfig

    # record the raster from the start: the per-neuron rate monitor needs
    # it, and a session whose recordings change rebuilds its engine
    return SimConfig(record_raster=True)


def build(spec, label, **kw):
    from repro.snn import Session

    t = time.perf_counter()
    ses = Session(spec, config(), **kw)
    d = ses.describe()
    log(f"[{label}] built n={d['n']} m={d['m']} k={d['k']} in "
        f"{time.perf_counter() - t:.1f} s (procedural build + ELL + device "
        f"placement; smoke timing); {host_memory()}")
    log(f"[{label}] engine={d['engine']} step_engine={d['step_engine']} "
        f"backend={d['backend']}: {d['backend_reason']}")
    return ses


def free_device_memory() -> None:
    """Drop compiled programs (which hold their engines) and collect, so
    a closed session's device arrays are freed before the next one."""
    gc.collect()
    jax.clear_caches()
    gc.collect()


def one_chip(spec) -> None:
    ses = build(spec, "k=1", k=1)
    rates, _, _ = simulate(spec, ses, "k=1", STEPS)
    check_rates(rates)

    shutil.rmtree(SNAPSHOT, ignore_errors=True)
    t = time.perf_counter()
    ses.save(SNAPSHOT)
    log(f"[k=1] snapshot at t={ses.t} saved in "
        f"{time.perf_counter() - t:.1f} s (smoke timing); {host_memory()}")
    ref = ses.run(CONTINUE_STEPS, chunk_size=CHUNK).spike_count
    ses.close()
    del ses
    free_device_memory()

    from repro.snn import Session

    t = time.perf_counter()
    ses2 = Session.restore(SNAPSHOT, k=1, cfg=config())
    log(f"[restore] k=1 session at t={ses2.t} restored in "
        f"{time.perf_counter() - t:.1f} s (smoke timing); {host_memory()}")
    got = ses2.run(CONTINUE_STEPS, chunk_size=CHUNK).spike_count
    ses2.close()
    del ses2
    free_device_memory()
    shutil.rmtree(SNAPSHOT, ignore_errors=True)
    if not np.array_equal(ref, got):
        bad = int(np.flatnonzero(ref != got)[0])
        raise AssertionError(
            f"restored run diverges at continued step {bad}: "
            f"{ref[bad]} vs {got[bad]} spikes"
        )
    log(f"[restore] {CONTINUE_STEPS} continued steps bit-identical "
        f"({int(ref.sum())} spikes)")


def check_sharded(ses, k: int) -> None:
    """Every partition-sharded leaf of the carry has one shard per
    partition, each on its own device."""
    for name, leaf in jax.tree_util.tree_leaves_with_path(ses.state):
        if leaf.ndim == 0:
            continue
        devs = {s.device for s in leaf.addressable_shards}
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        if len(devs) != k or rows != {leaf.shape[0] // k}:
            raise AssertionError(
                f"state {jax.tree_util.keystr(name)} is not sharded one "
                f"partition per device: {len(devs)} devices, rows {rows}"
            )
    log(f"[k={k}] partition-sharded state on {k} distinct devices")


def four_chips(spec) -> None:
    k = 4
    if len(jax.devices()) < k:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX sees "
                         f"{len(jax.devices())}")
    ses = build(spec, "k=4", k=k, engine="spmd")
    rates4, counts4, res4 = simulate(spec, ses, "k=4", FOUR_CHIP_STEPS)
    check_sharded(ses, k)
    dropped = int(res4.overflow.sum())
    log(f"[k=4] index-exchange overflow: {dropped} spikes dropped")
    if dropped:
        raise AssertionError(f"index exchange dropped {dropped} spikes")
    check_rates(rates4, every_population_fires=False)

    # the k=1 run of the same network: Session merges the partitions
    # into one (same labelling, same permanent ids) on one chip
    from repro.snn import Session

    net = ses.net
    ses.close()
    del ses
    free_device_memory()
    t = time.perf_counter()
    ses1 = Session(net, config(), engine="single")
    del net
    d = ses1.describe()
    log(f"[k=1] merged n={d['n']} k={d['k']} step_engine="
        f"{d['step_engine']} backend={d['backend']} in "
        f"{time.perf_counter() - t:.1f} s (smoke timing); {host_memory()}")
    rates1, counts1, _ = simulate(spec, ses1, "k=1", FOUR_CHIP_STEPS)
    ses1.close()
    del ses1
    free_device_memory()

    if np.array_equal(counts1, counts4):
        log(f"[compare] k=4 vs k=1: spike counts bit-identical over "
            f"{FOUR_CHIP_STEPS} steps")
        return
    first = int(np.flatnonzero(counts1 != counts4)[0])
    log(f"[compare] k=4 vs k=1: spike counts first differ at step {first} "
        f"({counts4[first]} vs {counts1[first]}); comparing rates")
    for name in rates1:
        a, b = rates4[name], rates1[name]
        sigma = np.sqrt(a["spikes"] + b["spikes"])
        diff = abs(a["spikes"] - b["spikes"])
        log(f"[compare]   {name:5s} k=4 {a['hz']:9.4f} Hz  k=1 "
            f"{b['hz']:9.4f} Hz  |diff| = {diff / max(sigma, 1e-9):.2f} sigma")
        if diff > RATE_SIGMAS * sigma:
            raise AssertionError(
                f"population {name}: k=4 and k=1 rates differ by more than "
                f"{RATE_SIGMAS} sigma"
            )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=float, default=SCALE,
                    help="microcircuit scale (default: full scale, 1.0)")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.builder.rules import microcircuit_rules

    spec = microcircuit_rules(scale=args.scale)
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"microcircuit scale {args.scale}: n={spec.n}")
    if args.chips == 4:
        four_chips(spec)
    else:
        one_chip(spec)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"{host_memory()}; device 0 peak "
        f"{'not reported' if peak is None else f'{peak / 1e9:.2f} GB'}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
