"""Kernel/simulator throughput: synaptic events processed per second and
per-step wall time for the microcircuit under the jitted scan loop.

Modes (``--mode``):
  * ``ref``     — the pure-jnp oracle path (CPU production path; default)
  * ``fused``   — k=1 fused single-kernel step vs. unfused three-kernel
                  step, both through the Pallas engine, side by side
  * ``dist``    — k>1 split-fused step (pre-exchange kernel, collective,
                  post-exchange kernel) vs. the unfused SPMD step, run in
                  a subprocess with ``k`` (fake, off-TPU) devices
  * ``plastic`` — STDP workload (balanced E/I net): the plastic fused
                  engines (STDP folded into the same panel pass as the
                  gathers) vs. the unfused three-kernel + ``stdp_update``
                  sequence, at k=1 (in-process) and k=2 (subprocess)
  * ``ckpt``    — checkpoint pipeline: per-checkpoint **run-loop stall**
                  of ``Session.run(checkpoint_every=...)`` with the
                  synchronous writer (``checkpoint_sync=True``) vs the
                  async background writer (the default).  The raw stalls
                  land as ``stall_us_per_ckpt`` on the ``ckpt_sync`` /
                  ``ckpt_async`` entries (informational, not gated); the
                  gated stat is ``ckpt_stall_ratio`` — async/sync median
                  stall, a dimensionless within-run ratio carried in
                  ``us_per_step`` with ``dimensionless: true`` (exempt
                  from ``--normalize``) and a wider ``gate_threshold``
  * ``event``   — activity sweep for the event-driven gather: a
                  bias-driven net (noise off) targets ~0.05% / 0.5% / 5%
                  spike rates and each point measures ``gather='dense'``
                  vs ``gather='event'`` us/step side by side — the data
                  behind ``EVENT_ACTIVITY_THRESHOLD``.  On CPU only the
                  skipped per-block *arithmetic* is real (interpret mode);
                  on TPU the event win is larger — the skipped HBM panel
                  fetches dominate
  * ``overlap`` — exchange/compute overlap for the split engines: the
                  k=2/k=4 split-fused step with ``overlap='off'``
                  (serialized exchange -> gather) vs ``overlap='local'``
                  (own-partition gather issued concurrently with the
                  collective), subprocess per point like ``dist``.  On
                  CPU interpret mode the collective is cheap and the
                  decomposition shows mostly its bookkeeping overhead
                  (wide gate band); on real multi-chip meshes the hidden
                  collective latency is the win the mode exists for
  * ``ingest``  — streamed vs eager snapshot ingest (merged k=3 -> k=1
                  load) at two network scales, wall-time and peak RSS
                  each measured in its own subprocess.  Raw numbers are
                  informational; the gated stats are the within-run
                  streamed/eager RSS and wall-time ratios
                  (``dimensionless: true``, like ``ckpt_stall_ratio``)
  * ``serialization`` — paper §3 on-disk scaling via
                  ``serialization_scaling.collect``: bytes-per-synapse
                  rows ride along informationally; the gated stat is the
                  max/min bytes-per-synapse linearity ratio
  * ``recovery`` — self-healing drill: a supervised run takes one
                  injected NaN, detects it, rolls back to the newest
                  valid checkpoint and re-runs to completion.  The
                  detect→rollback→resume wall-time overhead vs an
                  undisturbed supervised run is informational; the gated
                  stat is ``recovery_steps_lost_ratio`` = steps lost /
                  ``checkpoint_every`` (dimensionless, exactly 1.0 when
                  the rollback lands on the newest checkpoint)
  * ``all``     — fused + dist + plastic + overlap + ckpt + event +
                  ingest + serialization + recovery (+ ref): the full
                  fused-vs-unfused × k=1-vs-distributed ×
                  plain-vs-plastic grid plus the overlap pair, the
                  checkpoint-stall pair, the activity sweep, the IO-side
                  (ingest/serialization) stats, and the recovery drill

Every invocation also records its results into
``BENCH_spike_throughput.json`` (``--json`` to relocate), merging with any
modes already present, so the perf trajectory accumulates across runs:
per-mode us/step, synaptic events/s, engine and backend, plus
fused-vs-unfused speedups.

On CPU the Pallas engines run in interpret mode, so the fused-vs-unfused
numbers are an emulation proxy.  On a TPU the Pallas synapse kernels
other than the spike delivery do not compile
(``kernels.dispatch.PALLAS_GATHER_LIMIT``), and the
``dist``/``plastic``/``overlap``/``ingest`` modes start child processes
that would need the chip this process holds: there ``--mode`` accepts
only ``ref``, ``ckpt``, ``serialization`` and ``recovery``, which run
in-process, and refuses the rest with a message."""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from repro.snn import Session, SimConfig, microcircuit, to_dcsr

DEFAULT_JSON = "BENCH_spike_throughput.json"
# refused on a TPU: they compare compiled Pallas engines, or start child
# processes (fake-device distributed runs, RSS probes) that need the chip
OFF_TPU_MODES = ("fused", "dist", "plastic", "overlap", "event", "ingest",
                 "all")


def _time_session(ses, steps, n, m):
    """Warmup + compile with the SAME chunk length (the step program is
    jitted per chunk size), then time one chunked run."""
    ses.run(steps, chunk_size=steps)
    jax.block_until_ready(ses.state["vtx_state"])
    t0 = time.perf_counter()
    res = ses.run(steps, chunk_size=steps)
    jax.block_until_ready(ses.state["vtx_state"])
    dt = time.perf_counter() - t0
    rate = float(res.spike_count.mean()) / n
    info = ses.describe()
    out = dict(
        n=n, m=m,
        us_per_step=dt / steps * 1e6,
        syn_events_per_s=m * rate * steps / dt,
        mean_activity=rate,
        engine=info["step_engine"],
        backend=info["backend"],
        k=info["k"],
    )
    if "ell_fill" in info:
        out["fill"] = info["ell_fill"]
    if "exchange" in info:
        out["exchange"] = info["exchange"]
    return out


def run(scale=0.02, steps=200, backend="ref", fused=None):
    """k=1 measurement in-process."""
    net = microcircuit(scale=scale, seed=0)
    d = to_dcsr(net, k=1)
    # compiled Pallas needs 128-lane-aligned panels; interpret/ref runs use
    # 32 to keep the CPU emulation panels small
    align_k = 128 if backend == "pallas" else 32
    # gather pinned dense: the k1/dist/plastic modes measure the dense
    # engines; 'auto' would let a quiet run swap to the event gather
    # mid-measurement (the sweep in main_event measures that on purpose)
    ses = Session(
        d, SimConfig(align_k=align_k, backend=backend, fused=fused,
                     gather="dense")
    )
    return _time_session(ses, steps, d.n, d.m)


def _plastic_net(n):
    """The STDP benchmark workload: balanced E/I net with E->E plasticity,
    driven hard enough that the STDP pass does real work every step."""
    from repro.snn import balanced_ei

    net = balanced_ei(n, stdp=True, seed=0, delay_steps=5)
    net.vtx_state[:, 2] += 6.0
    return net


def run_plastic(n=200, steps=100, backend="ref", fused=None):
    """k=1 plastic measurement in-process (fused_plastic vs unfused)."""
    net = _plastic_net(n)
    d = to_dcsr(net, k=1)
    align_k = 128 if backend == "pallas" else 32
    ses = Session(
        d, SimConfig(align_k=align_k, backend=backend, fused=fused,
                     gather="dense")
    )
    return _time_session(ses, steps, d.n, d.m)


def _event_net(scale, frac):
    """The activity-sweep workload: microcircuit topology, noise off, a
    ``frac`` fraction of neurons driven by a suprathreshold bias.  A
    driven LIF fires right after each refractory exit (a 21-step cycle at
    the default params), so the realized per-step spike rate is
    ~0.047*frac — frac 0.0105/0.105/1.0 lands near the 0.05%/0.5%/5%
    sweep targets.  Initial refractory counters stagger the firing phases
    across the cycle (no biological net fires in lockstep): with few
    driven neurons most steps are fully silent — the event engines' best
    case — while at the ``hi`` point spikes land every step and the event
    path honestly pays its selection overhead."""
    net = microcircuit(scale=scale, seed=0)
    net.meta["noise_sigma"] = 0.0
    net.vtx_state[:, 2] = 0.0
    n_drive = max(int(round(frac * net.n)), 1)
    net.vtx_state[:n_drive, 2] = 2000.0
    net.vtx_state[:n_drive, 1] = np.arange(n_drive) % 21
    return net


def run_event_point(scale, steps, frac, gather, backend):
    """One sweep point: k=1 fused engine with the requested gather mode."""
    net = _event_net(scale, frac)
    d = to_dcsr(net, k=1)
    align_k = 128 if backend == "pallas" else 32
    ses = Session(d, SimConfig(
        align_k=align_k, backend=backend, fused=True, gather=gather,
    ))
    r = _time_session(ses, steps, d.n, d.m)
    r["target_frac"] = frac
    return r


def main_event(scale, steps, json_path):
    """Dense vs event-driven gather across the activity sweep; the data
    that justifies (and re-validates) the auto-threshold constant."""
    from repro.kernels.dispatch import platform_default

    backend = platform_default()
    entries = {}
    for label, frac in (("lo", 0.0105), ("mid", 0.105), ("hi", 1.0)):
        dense = run_event_point(scale, steps, frac, "dense", backend)
        event = run_event_point(scale, steps, frac, "event", backend)
        assert dense["engine"] == "fused", dense["engine"]
        assert event["engine"] == "fused_event", event["engine"]
        # the sweep points are deliberately tiny (quick mode: 30 steps on
        # a sub-400-neuron net) so their us_per_step is noisy across
        # runners — gate them with the same wider band as the ckpt stall
        # ratio; a lost skip-machinery win shows up far past 2x
        dense["gate_threshold"] = 2.0
        event["gate_threshold"] = 2.0
        speedup = dense["us_per_step"] / max(event["us_per_step"], 1e-9)
        print(
            f"spike_throughput_event_{label},{event['us_per_step']:.0f},"
            f"dense_us={dense['us_per_step']:.0f};"
            f"speedup={speedup:.2f}x;"
            f"activity={event['mean_activity']:.5f};"
            f"backend={backend};n={event['n']};m={event['m']}"
        )
        entries[f"event_{label}_dense"] = dense
        entries[f"event_{label}_event"] = event
    _record(json_path, entries)


def run_dist(scale, steps, k, backend, fused, exchange="auto",
             plastic=False, overlap="auto"):
    """k>1 measurement in THIS process (caller provides >= k devices).
    ``plastic`` swaps the microcircuit for the STDP workload (``scale``
    is then the neuron count)."""
    from repro.core import block_partition

    if plastic:
        net = _plastic_net(int(scale))
    else:
        net = microcircuit(scale=scale, seed=0)
    d = to_dcsr(net, assignment=block_partition(net.n, k), uniform=True)
    align_k = 128 if backend == "pallas" else 32
    ses = Session(d, SimConfig(
        align_k=align_k, backend=backend, fused=fused, exchange=exchange,
        gather="dense", overlap=overlap,
    ))
    assert ses.describe()["engine"] == "spmd"
    r = _time_session(ses, steps, d.n, d.m)
    r["overlap"] = ses.describe().get("overlap", overlap)
    return r


def _dist_worker_main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--k", type=int, required=True)
    ap.add_argument("--backend", required=True)
    ap.add_argument("--fused", type=int, required=True)
    ap.add_argument("--plastic", type=int, default=0)
    ap.add_argument("--overlap", default="auto")
    args = ap.parse_args(argv)
    r = run_dist(
        args.scale, args.steps, args.k, args.backend, bool(args.fused),
        plastic=bool(args.plastic), overlap=args.overlap,
    )
    print("RESULT " + json.dumps(r))


def _run_dist_subprocess(scale, steps, k, backend, fused, plastic=False,
                         overlap="auto"):
    """Run one distributed measurement in a subprocess with k fake host
    devices (off-TPU the host platform must be forced BEFORE jax
    initializes, so the parent process stays clean)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={k}"
    ).strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_dist-worker",
         "--scale", str(scale), "--steps", str(steps), "--k", str(k),
         "--backend", backend, "--fused", str(int(fused)),
         "--plastic", str(int(plastic)), "--overlap", overlap],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"dist benchmark worker failed:\n{out.stdout}\n{out.stderr[-2000:]}"
        )
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def _record(json_path, entries):
    """Merge per-mode entries into the JSON report (accumulates across
    invocations; fused/unfused pairs gain a speedup entry)."""
    data = {}
    if os.path.exists(json_path):
        try:
            with open(json_path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
    modes = data.setdefault("modes", {})
    modes.update(entries)
    speedups = data.setdefault("speedup_unfused_over_fused", {})
    for name in list(modes):
        if name.endswith("_fused"):
            pair = name[: -len("_fused")] + "_unfused"
            if pair in modes:
                speedups[name[: -len("_fused")]] = round(
                    modes[pair]["us_per_step"]
                    / max(modes[name]["us_per_step"], 1e-9), 3
                )
    ev_speedups = data.setdefault("speedup_dense_over_event", {})
    for name in list(modes):
        if name.startswith("event_") and name.endswith("_event"):
            pair = name[: -len("_event")] + "_dense"
            if pair in modes:
                ev_speedups[name[: -len("_event")]] = round(
                    modes[pair]["us_per_step"]
                    / max(modes[name]["us_per_step"], 1e-9), 3
                )
    data["backend_default"] = jax.default_backend()
    with open(json_path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    return json_path


def main_ref(scale, steps, json_path):
    r = run(scale=scale, steps=steps)
    print(
        f"spike_throughput,{r['us_per_step']:.0f},"
        f"m={r['m']};events/s={r['syn_events_per_s']:.2e};"
        f"ell_fill={r['fill']:.2f}"
    )
    _record(json_path, {"ref": r})


def main_fused(scale, steps, json_path):
    """k=1: fused single-kernel vs unfused step latency (Pallas engine)."""
    from repro.kernels.dispatch import platform_default

    backend = platform_default()
    fused = run(scale=scale, steps=steps, backend=backend, fused=True)
    unfused = run(scale=scale, steps=steps, backend=backend, fused=False)
    assert fused["engine"] == "fused" and unfused["engine"] == "unfused"
    speedup = unfused["us_per_step"] / max(fused["us_per_step"], 1e-9)
    print(
        f"spike_throughput_fused,{fused['us_per_step']:.0f},"
        f"unfused_us={unfused['us_per_step']:.0f};"
        f"speedup={speedup:.2f}x;backend={backend};"
        f"n={fused['n']};m={fused['m']}"
    )
    _record(json_path, {"k1_fused": fused, "k1_unfused": unfused})


def main_dist(scale, steps, k, json_path):
    """k>1: split-fused (pre kernel, collective, post kernel) vs unfused
    SPMD step latency."""
    from repro.kernels.dispatch import platform_default

    backend = platform_default()
    fused = _run_dist_subprocess(scale, steps, k, backend, True)
    unfused = _run_dist_subprocess(scale, steps, k, backend, False)
    assert fused["engine"] == "fused_split", fused["engine"]
    assert unfused["engine"] == "unfused", unfused["engine"]
    speedup = unfused["us_per_step"] / max(fused["us_per_step"], 1e-9)
    print(
        f"spike_throughput_dist_k{k},{fused['us_per_step']:.0f},"
        f"unfused_us={unfused['us_per_step']:.0f};"
        f"speedup={speedup:.2f}x;backend={backend};"
        f"exchange={fused.get('exchange')};n={fused['n']};m={fused['m']}"
    )
    _record(json_path, {
        f"dist_k{k}_fused": fused, f"dist_k{k}_unfused": unfused,
    })


def main_plastic(n, steps, k, json_path):
    """STDP workload: the plastic fused engines (one pass per synapse
    panel, STDP folded in) vs the unfused three-kernel + stdp_update
    sequence, at k=1 and distributed k."""
    from repro.kernels.dispatch import platform_default

    backend = platform_default()
    fused = run_plastic(n=n, steps=steps, backend=backend, fused=True)
    unfused = run_plastic(n=n, steps=steps, backend=backend, fused=False)
    assert fused["engine"] == "fused_plastic", fused["engine"]
    assert unfused["engine"] == "unfused", unfused["engine"]
    speedup = unfused["us_per_step"] / max(fused["us_per_step"], 1e-9)
    print(
        f"spike_throughput_plastic_k1,{fused['us_per_step']:.0f},"
        f"unfused_us={unfused['us_per_step']:.0f};"
        f"speedup={speedup:.2f}x;backend={backend};"
        f"n={fused['n']};m={fused['m']}"
    )
    entries = {"plastic_k1_fused": fused, "plastic_k1_unfused": unfused}
    dist_f = _run_dist_subprocess(n, steps, k, backend, True, plastic=True)
    dist_u = _run_dist_subprocess(n, steps, k, backend, False, plastic=True)
    assert dist_f["engine"] == "fused_split_plastic", dist_f["engine"]
    assert dist_u["engine"] == "unfused", dist_u["engine"]
    speedup_d = dist_u["us_per_step"] / max(dist_f["us_per_step"], 1e-9)
    print(
        f"spike_throughput_plastic_dist_k{k},{dist_f['us_per_step']:.0f},"
        f"unfused_us={dist_u['us_per_step']:.0f};"
        f"speedup={speedup_d:.2f}x;backend={backend};"
        f"exchange={dist_f.get('exchange')};n={dist_f['n']};m={dist_f['m']}"
    )
    entries[f"plastic_dist_k{k}_fused"] = dist_f
    entries[f"plastic_dist_k{k}_unfused"] = dist_u
    _record(json_path, entries)


def main_overlap(scale, steps, ks, json_path):
    """Split-fused step with the exchange serialized (``overlap='off'``)
    vs overlapped with the local gather (``overlap='local'``), at the
    k=2/k=4 proxy points.  The pair shares the workload with ``dist`` so
    the columns line up in the JSON grid.  Both entries carry a wide
    ``gate_threshold``: off-TPU the collective costs ~nothing, so the
    decomposed gather mostly exposes its own bookkeeping — the gate
    protects against the machinery rotting (a lost kernel fusion or an
    accidental serialization shows up far past 2x), not against losing a
    win CPU interpret mode cannot show."""
    from repro.kernels.dispatch import platform_default

    backend = platform_default()
    entries = {}
    for k in ks:
        ser = _run_dist_subprocess(scale, steps, k, backend, True,
                                   overlap="off")
        ovl = _run_dist_subprocess(scale, steps, k, backend, True,
                                   overlap="local")
        assert ser["engine"] == "fused_split", ser["engine"]
        assert ser["overlap"] == "off", ser["overlap"]
        assert ovl["engine"] == "fused_split", ovl["engine"]
        assert ovl["overlap"] == "local", ovl["overlap"]
        for e in (ser, ovl):
            e["gate_threshold"] = 2.0
        speedup = ser["us_per_step"] / max(ovl["us_per_step"], 1e-9)
        print(
            f"spike_throughput_overlap_k{k},{ovl['us_per_step']:.0f},"
            f"serialized_us={ser['us_per_step']:.0f};"
            f"speedup={speedup:.2f}x;backend={backend};"
            f"exchange={ovl.get('exchange')};n={ovl['n']};m={ovl['m']}"
        )
        entries[f"overlap_k{k}_serialized"] = ser
        entries[f"overlap_k{k}_overlapped"] = ovl
    _record(json_path, entries)


def run_ckpt(scale, steps, every, sync):
    """One checkpointed run; returns the mean run-loop stall per
    checkpoint (what the async pipeline is supposed to shrink: the save
    call's blocking time inside ``Session.run``)."""
    import shutil
    import tempfile

    from repro.snn import Session, SimConfig, microcircuit, to_dcsr

    net = microcircuit(scale=scale, seed=0)
    d = to_dcsr(net, k=1)
    ses = Session(d, SimConfig(align_k=32, gather="dense"))
    ses.run(every, chunk_size=every)  # compile the chunk program once
    td = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        t0 = time.perf_counter()
        res = ses.run(steps, chunk_size=every, checkpoint_every=every,
                      checkpoint_dir=td, checkpoint_sync=sync)
        loop_s = time.perf_counter() - t0
        stalls = ses.last_ckpt_stalls
        ses.wait()  # queued writes must land before the dir is removed
    finally:
        ses.close()
        shutil.rmtree(td, ignore_errors=True)
    info = ses.describe()
    return dict(
        n=d.n, m=d.m, k=info["k"],
        engine=info["step_engine"], backend=info["backend"],
        # every mode's entry carries mean_activity under the same name, so
        # the activity sweep and the gate key off one field
        mean_activity=float(res.spike_count.mean()) / d.n,
        n_checkpoints=len(stalls),
        # informational (deliberately NOT us_per_step, so the raw
        # IO-bound stall is never CPU-normalized by the regression gate):
        # MEDIAN over the checkpoints, robust to one filesystem hiccup
        stall_us_per_ckpt=statistics.median(stalls) * 1e6,
        mean_stall_us=sum(stalls) / max(len(stalls), 1) * 1e6,
        metric="run_loop_stall_per_checkpoint_us",
        run_s=loop_s,
    )


def main_ckpt(scale, steps, every, json_path):
    """Checkpoint-pipeline stall: synchronous writer vs the async
    background writer (host-snapshot + enqueue only).

    The *gated* entry is ``ckpt_stall_ratio`` — async/sync stall measured
    in the same process on the same disk, so it is machine-invariant
    (raw stalls are IO-bound and would be distorted by the gate's
    CPU-time ``--normalize ref``; they ride along unvalidated)."""
    sync = run_ckpt(scale, steps, every, sync=True)
    asyn = run_ckpt(scale, steps, every, sync=False)
    ratio = asyn["stall_us_per_ckpt"] / max(sync["stall_us_per_ckpt"], 1e-9)
    print(
        f"spike_throughput_ckpt,{asyn['stall_us_per_ckpt']:.0f},"
        f"sync_stall_us={sync['stall_us_per_ckpt']:.0f};"
        f"stall_drop={1.0 / max(ratio, 1e-9):.2f}x;"
        f"ckpts={asyn['n_checkpoints']};n={asyn['n']};m={asyn['m']}"
    )
    ratio_entry = dict(
        us_per_step=ratio,  # the gated stat (dimensionless: async/sync)
        dimensionless=True,  # check_regression: exempt from --normalize
        # both stalls are CPU/page-cache bound here (no fsync), but the
        # CPU/disk balance still varies across runners — give this stat a
        # wider band; a regression to blocking writes is ~6x, far past it
        gate_threshold=2.0,
        metric="async_over_sync_stall_ratio",
        sync_stall_us=sync["stall_us_per_ckpt"],
        async_stall_us=asyn["stall_us_per_ckpt"],
        n_checkpoints=asyn["n_checkpoints"],
        n=asyn["n"], m=asyn["m"], k=asyn["k"],
        mean_activity=asyn["mean_activity"],
    )
    _record(json_path, {
        "ckpt_sync": sync, "ckpt_async": asyn,
        "ckpt_stall_ratio": ratio_entry,
    })


def run_recovery_once(scale, steps, every, faulted, seed=0):
    """One supervised run (fresh session + checkpoint dir); ``faulted``
    injects a single NaN after the second chunk — the canonical recovery
    drill: detect at t=2*every, roll back to the t=every checkpoint,
    re-run to completion.  Returns ``(wall_s, result, net)``."""
    import warnings

    from repro.testing import Fault, FaultPlan

    net = microcircuit(scale=scale, seed=0)
    d = to_dcsr(net, k=1)
    ses = Session(d, SimConfig(align_k=32, gather="dense"))
    td = tempfile.mkdtemp(prefix="recovery_bench_")
    plan = FaultPlan(
        [Fault("supervisor:state", "nan", after=1, count=1)]
        if faulted else [],
        seed=seed,
    )
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with plan:
                t0 = time.perf_counter()
                res = ses.run_supervised(
                    steps, chunk_size=every, checkpoint_every=every,
                    checkpoint_dir=td,
                )
                wall = time.perf_counter() - t0
    finally:
        ses.close()
        shutil.rmtree(td, ignore_errors=True)
    return wall, res, d


def main_recovery(scale, steps, every, repeats, json_path):
    """Self-healing drill: median detect→rollback→resume overhead (the
    faulted supervised run's wall time minus the undisturbed one's) and
    steps lost.  The wall times are IO/compile bound and ride along
    informationally; the gated stat is dimensionless —
    ``recovery_steps_lost_ratio`` = steps_lost / checkpoint_every, which
    is exactly 1.0 when the rollback lands on the NEWEST valid
    checkpoint.  A restore walker that falls back further than it must,
    or a checkpoint cadence that silently stops, pushes it past the
    gate."""
    clean_w, fault_w, losts, acts = [], [], [], []
    n = m = None
    for rep in range(repeats):
        wc, rc, d = run_recovery_once(scale, steps, every, False, rep)
        wf, rf, _ = run_recovery_once(scale, steps, every, True, rep)
        assert rc.rollbacks == 0 and rf.rollbacks == 1, (
            rc.rollbacks, rf.rollbacks
        )
        # the healed run's committed outputs must be bit-identical to the
        # undisturbed run — otherwise the "recovery" being timed is fake
        assert np.array_equal(rf.spike_count, rc.spike_count)
        clean_w.append(wc)
        fault_w.append(wf)
        losts.append(rf.steps_lost)
        acts.append(float(rf.spike_count.mean()) / d.n)
        n, m = d.n, d.m
    clean_us = statistics.median(clean_w) * 1e6
    fault_us = statistics.median(fault_w) * 1e6
    recovery_us = max(fault_us - clean_us, 0.0)
    lost = statistics.median(losts)
    ratio = lost / every
    act = sum(acts) / len(acts)
    print(
        f"spike_throughput_recovery,{recovery_us:.0f},"
        f"steps_lost={lost:.0f};ratio={ratio:.2f};every={every};"
        f"clean_us={clean_us:.0f};faulted_us={fault_us:.0f};"
        f"repeats={repeats};n={n};m={m}"
    )
    info = dict(
        # informational (deliberately NOT us_per_step: wall times are
        # IO/recompile bound and must never be CPU-normalized): MEDIAN
        # over the repeats, robust to one runner hiccup
        recovery_us=recovery_us,
        clean_run_us=clean_us,
        faulted_run_us=fault_us,
        steps_lost=lost,
        checkpoint_every=every,
        repeats=repeats,
        metric="detect_rollback_resume_overhead_us",
        n=n, m=m, k=1,
        mean_activity=act,
    )
    gated = dict(
        us_per_step=ratio,   # the gated stat (dimensionless)
        dimensionless=True,  # check_regression: exempt from --normalize
        # exactly 1.0 by construction; 1.5 flags a walker falling back a
        # whole extra checkpoint (2.0) without tripping on jitter
        gate_threshold=1.5,
        metric="steps_lost_over_checkpoint_every",
        steps_lost=lost,
        checkpoint_every=every,
        n=n, m=m, k=1,
        mean_activity=act,
    )
    _record(json_path, {
        "recovery": info, "recovery_steps_lost_ratio": gated,
    })


_INGEST_CHILD = r"""
import json, resource, sys, time

def peak_rss_kb():
    # VmHWM is per-process (reset on exec); ru_maxrss is inherited
    # across fork+exec on some kernels and would report the parent's
    # peak — only fall back to it off-Linux
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1])
    except OSError:
        pass
    kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return kb // 1024 if sys.platform == "darwin" else kb

snap, mode = sys.argv[1], sys.argv[2]
t0 = time.perf_counter()
if mode == "eager":
    from repro.io.dcsr_binary import load_binary
    from repro.core.dcsr import merge_to_single
    net, sim, t = load_binary(snap)
    net1 = merge_to_single(net)
else:
    from repro.builder.ingest import load_merged_streamed
    net1, sim, t = load_merged_streamed(snap)
print(json.dumps({"load_s": time.perf_counter() - t0,
                  "peak_rss_mb": peak_rss_kb() / 1024.0,
                  "m": int(net1.m)}))
"""


def _run_ingest_child(snap, mode):
    """One merged-load measurement in a fresh interpreter, so ru_maxrss
    captures exactly that loader's footprint (imports numpy, not jax)."""
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    out = subprocess.run(
        [sys.executable, "-c", _INGEST_CHILD, snap, mode],
        env=env, capture_output=True, text=True, timeout=900,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"ingest child failed:\n{out.stdout}\n{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def main_ingest(json_path, quick):
    """Streamed vs eager snapshot ingest at two network scales: merged
    (k=3 -> k=1) load wall-time and peak RSS, each measured in its own
    subprocess.  Raw numbers are informational (IO/alloc bound, never
    CPU-normalized); the gated stats are the within-run streamed/eager
    ratios at the larger scale — dimensionless and machine-invariant."""
    from repro.builder import RuleSpec, Population, ConnectRule
    from repro.builder.procedural import build_network
    from repro.io import save_binary

    sizes = (40_000, 100_000) if quick else (100_000, 250_000)
    entries = {}
    ratios = {}
    for label, n in zip(("small", "large"), sizes):
        spec = RuleSpec(
            (Population("x", n, bias_mu=14.8, bias_sigma=0.5),),
            (ConnectRule("x", "x", fan_in=8, weight_mu=0.4,
                         weight_sigma=0.05, delay=2),),
            seed=1,
        )
        td = tempfile.mkdtemp()
        try:
            net = build_network(spec, k=3)
            save_binary(net, os.path.join(td, "snap"), t_now=0)
            del net
            res = {
                mode: _run_ingest_child(os.path.join(td, "snap"), mode)
                for mode in ("eager", "stream")
            }
        finally:
            shutil.rmtree(td, ignore_errors=True)
        for mode, r in res.items():
            print(
                f"spike_throughput_ingest[{mode}_{label}],"
                f"{r['load_s'] * 1e6:.0f},"
                f"rss_mb={r['peak_rss_mb']:.0f};n={n};m={r['m']}"
            )
            entries[f"ingest_{mode}_{label}"] = dict(
                # informational: raw load time is IO-bound, deliberately
                # NOT us_per_step so the gate never CPU-normalizes it
                load_us=r["load_s"] * 1e6,
                peak_rss_mb=r["peak_rss_mb"],
                metric="merged_snapshot_load",
                n=n, m=r["m"], k=3,
                mean_activity=0.0,  # pure-IO workload, nothing spikes
            )
        ratios[label] = dict(
            rss=res["stream"]["peak_rss_mb"] / res["eager"]["peak_rss_mb"],
            time=res["stream"]["load_s"] / max(res["eager"]["load_s"], 1e-9),
            n=n, m=res["stream"]["m"],
        )
    big = ratios["large"]
    entries["ingest_rss_ratio"] = dict(
        us_per_step=big["rss"],  # gated: streamed/eager peak RSS
        dimensionless=True,
        # streaming holds one net + one chunk vs eager's two nets +
        # edge-list transients; allocations are deterministic so the
        # ratio is stable — a regression to eager materialization
        # (ratio ~1.0 from a ~0.7 baseline) must land past the band
        gate_threshold=1.3,
        metric="streamed_over_eager_peak_rss",
        small_ratio=ratios["small"]["rss"],
        n=big["n"], m=big["m"], k=3,
        mean_activity=0.0,
    )
    entries["ingest_time_ratio"] = dict(
        us_per_step=big["time"],  # gated: streamed/eager load wall-time
        dimensionless=True,
        # chunked reads cost a little over one eager read but far less
        # than eager load + merge; disk caching still varies -> wide band
        gate_threshold=2.0,
        metric="streamed_over_eager_load_time",
        small_ratio=ratios["small"]["time"],
        n=big["n"], m=big["m"], k=3,
        mean_activity=0.0,
    )
    print(
        f"spike_throughput_ingest,0,"
        f"rss_ratio={big['rss']:.2f};time_ratio={big['time']:.2f};"
        f"m={big['m']}"
    )
    _record(json_path, entries)


def main_serialization(json_path, quick):
    """Paper §3 on-disk scaling, wired into the shared JSON report: the
    gated stat is the bytes-per-synapse linearity ratio (max/min across
    scales) — pure format arithmetic, so it is dimensionless and must
    stay ~1.0 on any machine."""
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    from serialization_scaling import collect

    rows, lin, kinv = collect(quick=quick)
    last = rows[-1]
    print(
        f"spike_throughput_serialization,0,linearity={lin:.3f};"
        f"text_B_per_syn={last['text_bytes_per_syn']:.1f};"
        f"bin_B_per_syn={last['bin_bytes_per_syn']:.1f}"
    )
    entries = {
        "serialization_linearity": dict(
            us_per_step=lin,  # gated: max/min bytes-per-synapse
            dimensionless=True,
            # on-disk cost must stay linear in synapses (paper's table);
            # fixed-size headers give small nets a little slack
            gate_threshold=1.25,
            metric="text_bytes_per_syn_linearity",
            text_bytes_per_syn=last["text_bytes_per_syn"],
            bin_bytes_per_syn=last["bin_bytes_per_syn"],
            n=last["n"], m=last["m"], k=4,
            mean_activity=0.0,  # serialization-only workload
        ),
    }
    for r in rows:
        entries[f"serialization_scale_{r['scale']}"] = dict(
            # informational: save wall-times are IO-bound
            save_text_us=r["save_text_s"] * 1e6,
            save_bin_us=r["save_bin_s"] * 1e6,
            text_bytes_per_syn=r["text_bytes_per_syn"],
            bin_bytes_per_syn=r["bin_bytes_per_syn"],
            metric="on_disk_bytes_per_synapse",
            n=r["n"], m=r["m"], k=4,
            mean_activity=0.0,
        )
    # partition-count invariance of the state/adjcy payloads rides along
    entries["serialization_linearity"]["state_bytes_by_k"] = {
        str(r["k"]): r["state_bytes"] for r in kinv
    }
    _record(json_path, entries)


def main(argv=None, quick=None):
    if quick is not None and argv is None:  # benchmarks/run.py entry
        argv = ["--quick"] if quick else []
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "--_dist-worker":
        _dist_worker_main(argv[1:])
        return
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode",
                    choices=("ref", "fused", "dist", "plastic", "overlap",
                             "ckpt", "event", "ingest", "serialization",
                             "recovery", "all"),
                    default="ref")
    ap.add_argument("--scale", type=float, default=None,
                    help="microcircuit scale (default per mode)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--k", type=int, default=None,
                    help="partitions for --mode dist/all")
    ap.add_argument("--json", default=DEFAULT_JSON,
                    help="perf-report path (merged across invocations)")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    if args.mode in OFF_TPU_MODES and jax.default_backend() == "tpu":
        ap.error(
            f"--mode {args.mode} does not run on a TPU: it compares the "
            "Pallas engines, which the TPU compiler refuses, or starts "
            "child processes that would need the chip this process holds; "
            "use --mode ref, ckpt, serialization or recovery"
        )
    # fused and dist share one workload so the k=1 vs distributed columns
    # of the JSON grid measure the same net
    pallas_scale = args.scale if args.scale is not None else (
        0.005 if args.quick else 0.01
    )
    pallas_steps = args.steps if args.steps is not None else (
        30 if args.quick else 100
    )
    if args.mode in ("fused", "all"):
        main_fused(pallas_scale, pallas_steps, args.json)
    if args.mode in ("dist", "all"):
        k = args.k if args.k is not None else (2 if args.quick else 4)
        main_dist(pallas_scale, pallas_steps, k, args.json)
    if args.mode in ("plastic", "all"):
        n_plastic = 160 if args.quick else 400
        k = args.k if args.k is not None else 2
        main_plastic(n_plastic, pallas_steps, k, args.json)
    if args.mode in ("overlap", "all"):
        ks = (args.k,) if args.k is not None else (
            (2,) if args.quick else (2, 4)
        )
        main_overlap(pallas_scale, pallas_steps, ks, args.json)
    if args.mode in ("event", "all"):
        ev_scale = args.scale if args.scale is not None else (
            0.005 if args.quick else 0.01
        )
        ev_steps = args.steps if args.steps is not None else (
            30 if args.quick else 100
        )
        main_event(ev_scale, ev_steps, args.json)
    if args.mode in ("ckpt", "all"):
        ck_scale = args.scale if args.scale is not None else (
            0.01 if args.quick else 0.02
        )
        # 10 checkpoints either way: the gated stat is a median, which
        # needs enough samples to shrug off CI-runner IO hiccups
        ck_steps = 120 if args.quick else 200
        main_ckpt(ck_scale, ck_steps, 12 if args.quick else 20, args.json)
    if args.mode in ("recovery", "all"):
        rc_scale = args.scale if args.scale is not None else (
            0.01 if args.quick else 0.02
        )
        rc_every = 12 if args.quick else 20
        rc_reps = 3 if args.quick else 5
        main_recovery(rc_scale, rc_every * 5, rc_every, rc_reps, args.json)
    if args.mode in ("ingest", "all"):
        main_ingest(args.json, args.quick)
    if args.mode in ("serialization", "all"):
        main_serialization(args.json, args.quick)
    if args.mode in ("ref", "all"):
        scale = args.scale if args.scale is not None else (
            0.01 if args.quick else 0.03
        )
        steps = args.steps if args.steps is not None else (
            100 if args.quick else 300
        )
        main_ref(scale, steps, args.json)


if __name__ == "__main__":
    main()
