"""One benchmark run of one cell: build the configuration's network, draw
the step noise from the seed, warm up, measure ``--seconds`` of whole
chunks through ``Session.run``, run the traffic's epilogue (save, restore,
continue), then check the window's output against the plain reference
and print one JSON result line.

Standard output ends with the result line; standard error ends with the
numbers compared, each beside its limit.  ``--trace 1`` measures the same
window under the profiler and reports the per-layer metrics instead of the
end-to-end ones.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import json
import os
import resource
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from . import reference, spec, structure, trace as trace_mod, work

PEAKS_FILE = os.path.join(spec.BENCH_DIR, "peaks.json")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def host_rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def peak_of(device_kind: str, peaks_file: str = PEAKS_FILE) -> dict:
    with open(peaks_file) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {peaks_file}; add its "
            "published peaks with their source"
        )
    return table[device_kind]


@dataclasses.dataclass
class Run:
    """What one run measured, for the metric readers."""

    cell: spec.Cell
    dt_ms: float
    steps: int = 0  # steps in the window
    window_s: float = 0.0  # host clock, whole chunks
    setup_s: float = 0.0
    build_s: float = 0.0
    ckpt_stalls: List[float] = dataclasses.field(default_factory=list)
    restore_s: Optional[float] = None
    panel_shapes: List[tuple] = dataclasses.field(default_factory=list)
    least_bytes: float = 0.0  # over the window
    least_ops: float = 0.0
    peak: dict = dataclasses.field(default_factory=dict)
    trace: Optional[trace_mod.TraceSummary] = None
    gather_seconds: float = 0.0  # device time of the ELL gathers (traced)
    gather_bytes: float = 0.0  # bytes those gathers moved


class _Compiles:
    """Counts compile requests (each program JAX compiles or loads) and
    persistent-cache hits among them, through JAX's monitoring events;
    requests less hits were compiled."""

    def __init__(self):
        self.compiles = 0  # requests
        self.cache_hits = 0

    def duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class _Recorder:
    """Monitor that keeps each chunk's raster (the reference replays it)
    and marks the host readout as a span of the benchmark."""

    requires = frozenset({"raster"})

    def __init__(self):
        self.chunks: List[np.ndarray] = []

    def begin(self, session) -> None:
        pass

    def on_chunk(self, t0, outs) -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench.monitor"):
            self.chunks.append(np.asarray(outs["raster"]))

    def finalize(self) -> None:
        pass

    def raster(self) -> np.ndarray:
        return np.concatenate(self.chunks)


def _monitors(names):
    """The program's monitors a traffic file names: ``per_neuron_rate``
    is ``PerNeuronRateMonitor``."""
    from repro.snn import monitors as m

    return [
        getattr(m, "".join(w.capitalize() for w in n.split("_")) + "Monitor")()
        for n in names
    ]


def build_spec(config: dict):
    """The configuration's RuleSpec.  Its network (connectivity, weights,
    biases, initial membrane potentials) comes from the configuration's
    ``network_seed``: a run's ``--seed`` draws the step noise, so every
    seed runs the same panel shapes and the same work per spike."""
    from repro.builder import rules

    builder = getattr(rules, config["builder"])
    return builder(**config["builder_args"], seed=int(config["network_seed"]))


def _joined(parts, get):
    """One array of every partition's ``get(part)``, in row order (a view
    where there is one partition)."""
    arrays = [get(p) for p in parts]
    return arrays[0] if len(arrays) == 1 else np.concatenate(arrays)


def reference_network(net, model, copy_weights: bool) -> reference.Network:
    """What the reference reads out of the dCSR network: the partitions
    joined in row order, their columns being row indices of the whole,
    and the per-neuron part as ``model`` (the configuration's model file)
    reads it.  A save rewrites the weights in place, so a run that saves
    copies them first; the topology is never rewritten."""
    parts = net.parts
    first_edge = np.cumsum([0] + [p.row_ptr[-1] for p in parts])
    row_ptr = np.concatenate(
        [[0]] + [p.row_ptr[1:] + e for p, e in zip(parts, first_edge)]
    ).astype(np.int64)
    weight = _joined(parts, lambda p: p.edge_state[:, 0])
    plastic_ids = [net.registry.edge_id(name) for name in model.PLASTIC_EDGES]
    return reference.Network(
        row_ptr=row_ptr,
        col=_joined(parts, lambda p: p.col_idx),
        weight=np.array(weight) if copy_weights else weight,
        delay=_joined(parts, lambda p: p.edge_state[:, 1]),
        plastic=np.isin(_joined(parts, lambda p: p.edge_model), plastic_ids),
        noise_ids=np.array(_joined(parts, lambda p: p.global_ids)),
        neurons=model.neurons(_joined(parts, lambda p: p.vtx_state)),
    )


def _exact_diff(a: dict, b: dict, ra: np.ndarray, rb: np.ndarray) -> int:
    """Entries that differ between two sessions' rasters and carries
    (every leaf of the carry: state, ring, history, traces, weights)."""
    import jax

    diff = int(np.count_nonzero(ra != rb))
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        diff += int(np.count_nonzero(np.asarray(x) != np.asarray(y)))
    return diff


def _by_id(ses, n: int):
    """Rows of the session's first ``n`` permanent neuron ids, in id
    order (padding rows, whose ids come after, left out)."""
    return np.argsort(ses.permanent_ids, kind="stable")[:n]


def _edges_by_id(net, model) -> np.ndarray:
    """Every synapse as (target id, source id, delay, weight), sorted: the
    network's edge state whatever its partitioning."""
    ids = _joined(net.parts, lambda p: p.global_ids)
    ref = reference_network(net, model, copy_weights=False)
    tgt = np.repeat(ids, np.diff(ref.row_ptr))
    rows = np.stack([tgt, ids[ref.col], ref.delay.astype(np.int64),
                     ref.weight.view(np.int32).astype(np.int64)])
    return rows[:, np.lexsort(rows[::-1])]


def _diff_by_id(ses, ses2, ra, rb, n: int, tmp: str, model) -> int:
    """``_exact_diff`` for two sessions of different partition counts: the
    rasters and per-neuron carries by permanent id (``model.program_state``),
    and the weights as each session's save writes them back into its dCSR."""
    sa, sb = _by_id(ses, n), _by_id(ses2, n)
    diff = int(np.count_nonzero(ra[:, sa] != rb[:, sb]))
    pa, pb = model.program_state(ses.state), model.program_state(ses2.state)
    for key in pa:
        diff += int(np.count_nonzero(pa[key][..., sa] != pb[key][..., sb]))
    diff += int(ses.t != ses2.t)
    for i, s in enumerate((ses, ses2)):
        s.save(os.path.join(tmp, f"continued{i}"), wait=True)
    ea, eb = _edges_by_id(ses.net, model), _edges_by_id(ses2.net, model)
    if ea.shape != eb.shape:
        return diff + max(ea.shape[1], eb.shape[1])
    return diff + int(np.count_nonzero((ea != eb).any(axis=0)))


def _population_rates(config, raster, dt_ms) -> Dict[str, float]:
    """Rate (Hz) of each population over a raster by permanent id."""
    seconds = max(raster.shape[0], 1) * dt_ms * 1e-3
    out, at = {}, 0
    for name, size in config["populations"].items():
        out[name] = float(raster[:, at:at + size].sum()) / (size * seconds)
        at += size
    return out


def run(argv=None, *, require_tpu: bool = True, root: Optional[str] = None,
        peaks: Optional[dict] = None, t_start: Optional[float] = None,
        control: bool = False) -> int:
    """One run (see the module docstring); returns the exit code.

    With ``control`` the reference is also replayed in bfloat16, in the
    program's place, and its numbers are printed beside the program's
    (``control.py``; the benchmark's own runs never do)."""
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="bench/run.py", description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, root)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        log(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"found {len(devices)} {dev.platform} device(s)")
        return 2
    peak = peaks if peaks is not None else peak_of(dev.device_kind)
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles.duration)
    jax.monitoring.register_event_listener(compiles.event)
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        return _run(args, cell, dev, devices, peak, compiles, tmp, t_start,
                    control)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        jax.monitoring.unregister_event_duration_listener(compiles.duration)
        jax.monitoring.unregister_event_listener(compiles.event)


def _run(args, cell, dev, devices, peak, compiles, tmp, t_start,
         control) -> int:
    import jax
    from repro.snn import Session, SimConfig

    cfg, traffic, seed, model = cell.config, cell.traffic, args.seed, cell.model

    chunk = int(traffic["chunk_steps"])
    ck_every = traffic.get("checkpoint_every")
    if ck_every is not None and int(ck_every) != chunk:
        raise ValueError("traffic: checkpoint_every must equal chunk_steps")
    k = int(cfg.get("k", 1))
    restore_k = int(traffic.get("restore_k", k))
    if max(k, restore_k) > len(devices):
        raise ValueError(f"cell {cell.name} runs k={k} and restores at "
                         f"k={restore_k}, on {len(devices)} device(s)")
    simcfg = SimConfig(seed=seed, record_raster=True)
    r = Run(cell=cell, dt_ms=float(cfg["dt_ms"]), peak=peak)

    # -- set-up: build, place, warm up ------------------------------------
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.build"):
        ses = Session(build_spec(cfg), simcfg, k=k)
    r.build_s = time.perf_counter() - t
    desc = ses.describe()
    log(f"[setup] built n={desc['n']} m={desc['m']} k={desc['k']} in "
        f"{r.build_s:.3f} s; engine={desc['engine']} "
        f"step_engine={desc['step_engine']} backend={desc['backend']} "
        f"ell_fill={desc.get('ell_fill')}; host peak RSS {host_rss_gb():.2f} GB")
    departures = model.departures(ses.net, cfg)
    saves = traffic.get("restore") or ck_every is not None
    net0 = reference_network(ses.net, model, copy_weights=saves)
    plastic = bool(net0.plastic.any())
    if plastic and not saves:
        # the epilogue saves a plastic network's weights back in place
        net0.weight = np.array(net0.weight)
    by_id = _by_id(ses, int(cfg["n"]))
    r.panel_shapes = [tuple(w.shape) for w in ses.state["weights"]]
    log(f"[setup] ELL panels {r.panel_shapes}")

    rec = _Recorder()
    mons = _monitors(traffic.get("monitors", ())) + [rec]
    kw = dict(chunk_size=chunk)
    if ck_every is not None:
        kw.update(checkpoint_every=chunk, checkpoint_dir=os.path.join(tmp, "ckpt"),
                  max_to_keep=traffic.get("max_to_keep"))
    # the save Session.run makes at each checkpoint, as a span of its own
    save = ses.save

    def traced_save(*a, **k):
        with jax.profiler.TraceAnnotation("bench.save"):
            return save(*a, **k)

    ses.save = traced_save

    def one_chunk():
        with jax.profiler.TraceAnnotation("bench.chunk"):
            ses.run(chunk, monitors=mons, **kw)
        return list(ses.last_ckpt_stalls) if ck_every is not None else []

    t = time.perf_counter()
    one_chunk()
    log(f"[setup] warm-up chunk of {chunk} steps in {time.perf_counter() - t:.3f} s")
    setup_compiles, setup_hits = compiles.compiles, compiles.cache_hits
    warm_chunks, warm_steps = len(rec.chunks), chunk

    # -- the measured window ---------------------------------------------
    prof_dir = os.path.join(tmp, "trace")
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    t0 = time.perf_counter()
    r.setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            r.ckpt_stalls.extend(one_chunk())
            if time.perf_counter() - t0 >= args.seconds:
                break
    t_window_end = time.perf_counter()
    r.window_s = t_window_end - t0
    if args.trace:
        jax.profiler.stop_trace()
    r.steps = (len(rec.chunks) - warm_chunks) * chunk
    if r.ckpt_stalls:
        log("[window] checkpoint stalls (s): "
            + ", ".join(f"{x:.6f}" for x in r.ckpt_stalls))
    window_compiles = compiles.compiles - setup_compiles
    log(f"[window] {r.steps} steps in {r.window_s:.6f} s "
        f"({len(rec.chunks) - warm_chunks} chunks); programs compiled or "
        f"loaded: {setup_compiles} in set-up ({setup_hits} from the cache, "
        f"{setup_compiles - setup_hits} compiled), {window_compiles} in the "
        f"window")
    end_state = model.program_state(ses.state)
    raster = rec.raster()
    t_end = warm_steps + r.steps
    # the step counter of the carry has to have advanced by every step run
    checks: Dict[str, tuple] = {
        "t_diff": (float(abs(ses.t - t_end)), cell.limits["t_diff"])
    }

    # -- epilogue of the traffic: save, restore, continue -----------------
    w_end = None
    if plastic or traffic.get("restore"):
        final = os.path.join(tmp, "final")
        ses.save(final, wait=True)  # syncs the weights back into the dCSR
        w_end = np.array(_joined(ses.net.parts, lambda p: p.edge_state[:, 0]),
                         dtype=np.float64)
    if traffic.get("restore"):
        # one restore, timed alone: the one a user resuming pays
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.restore"):
            ses2 = Session.restore(final, k=restore_k, cfg=simcfg)
            jax.block_until_ready(ses2.state)
        r.restore_s = time.perf_counter() - t
        live, again = _Recorder(), _Recorder()
        ses.run(chunk, monitors=[live], chunk_size=chunk)
        ses2.run(chunk, monitors=[again], chunk_size=chunk)
        if restore_k == k:
            diff = _exact_diff(ses.state, ses2.state, live.raster(), again.raster())
        else:
            diff = _diff_by_id(ses, ses2, live.raster(), again.raster(),
                               int(cfg["n"]), tmp, model)
        checks["restore_diff"] = (float(diff), cell.limits["restore_diff"])
        log(f"[restore] restored at k={restore_k} in {r.restore_s:.6f} s; one "
            f"chunk continued in both sessions: {diff} entries differ")
        ses2.close()
        del ses2
    ses.close()
    # the peak of the fullest chip the cell used
    mem_peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices[:max(k, restore_k)]), default=0) or None
    del ses, mons
    gc.collect()
    jax.clear_caches()
    gc.collect()

    # -- counts for the per-layer metrics --------------------------------
    # by permanent id, padding rows left out: the work the network needs
    out_deg, p_out, p_in = (
        None if x is None else x[by_id] for x in work.degrees(
            net0.row_ptr, net0.col, net0.n, net0.plastic if plastic else None))
    win = raster[warm_steps:t_end][:, by_id]
    r.least_bytes, r.least_ops = work.least_step_work(win, out_deg, p_out, p_in)
    spikes_per_step = float(win.sum()) / max(r.steps, 1)
    events = float(win.sum(axis=0) @ out_deg.astype(np.float64))
    log(f"[counts] {spikes_per_step:.4f} spikes per step, "
        f"{events / r.window_s:.6g} synaptic events per second in the window; "
        f"host peak RSS {host_rss_gb():.2f} GB")
    rates = _population_rates(cfg, win, r.dt_ms)
    log("[counts] population rates (Hz): "
        + ", ".join(f"{k} {v:.4f}" for k, v in rates.items()))

    if args.trace:
        xp = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"), recursive=True)
        r.trace = trace_mod.reduce_xplane(xp[0])
        r.gather_seconds, r.gather_bytes = work.gather_traffic(
            r.trace.op_seconds, r.trace.op_counts,
            # one chip's panel: the last two axes of a k-chip stack
            [int(np.prod(shape[-2:])) for shape in r.panel_shapes],
        )
        log(f"[trace] device busy {r.trace.busy_s:.6f} s of "
            f"{r.trace.window_s:.6f} s; ELL gathers {r.gather_seconds:.6f} s, "
            f"{r.gather_bytes:.6g} bytes")

    # -- the built network against the configuration's own numbers -------
    t = time.perf_counter()
    built, lines = structure.check(net0, cfg, model)
    for line in lines:
        log(f"[structure] {line}")
    for name, value in built.items():
        checks[name] = (value, cell.limits[name])
    log(f"[structure] checked in {time.perf_counter() - t:.3f} s")

    # -- the comparison with the plain reference -------------------------
    t = time.perf_counter()
    step_input = model.step_input(seed, net0, cfg)
    replayed = model.replay(net0, cfg, raster[:t_end], step_input, control)
    replay_s = time.perf_counter() - t
    ref_state = replayed.state
    got = dict(end_state)
    if plastic:
        got["w"] = w_end
    numbers = model.gaps(got, ref_state, replayed.thr_gap, net0, by_id)
    if control:
        c_numbers = model.gaps(replayed.control.state, ref_state,
                               replayed.control.thr_gap, net0, by_id)
        c_failed = [k for k, v in c_numbers.items()
                    if k in cell.limits and not v <= cell.limits[k]]
        print(json.dumps(dict(control=control, seed=seed, program=numbers,
                              control_numbers=c_numbers, control_failed=c_failed)),
              flush=True)
    for name, value in numbers.items():
        if name in cell.limits:
            checks[name] = (value, cell.limits[name])
        else:
            log(f"[check] {name} {value!r} (not compared in this cell)")
    log(f"[check] reference replay of {t_end} steps, {replayed.events} "
        f"synaptic events, in {replay_s:.3f} s "
        f"({replayed.events / max(replay_s, 1e-9):.6g} events/s)")

    for d in departures:
        log(f"[check] the program departs from the configuration: {d}")
    checks["config_departures"] = (float(len(departures)),
                                   cell.limits["config_departures"])
    failed = [k for k, (v, lim) in checks.items() if not v <= lim]
    correct = not failed

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = m.read(r)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices), "memory_peak_bytes": mem_peak,
    }
    result = dict(correct=correct, attempted=len(checks), failed=len(failed),
                  metrics=metrics, device=device)
    if args.trace:
        device.update(busy_s=r.trace.busy_s, window_s=r.trace.window_s)
        result["breakdown"] = dict(
            device_ops=[[trace_mod.short_name(n), s] for n, s in r.trace.top_ops(10)],
            idle_gaps=[[n, s] for n, s in r.trace.idle_gaps[:10]],
        )
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    now = time.perf_counter()
    log(f"[run] post-window {now - t_window_end:.3f} s, whole run "
        f"{now - t_start:.3f} s")
    for k, (v, lim) in checks.items():
        log(f"{k} {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0
