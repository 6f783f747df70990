"""Unified ``Session`` API: one entry point for build → simulate →
checkpoint → restart, elastic across k.

``Session(net_or_path, cfg)`` is the single supported way to simulate a
dCSR network.  It auto-selects a step engine (the legacy ``Simulator`` /
``DistSimulator`` classes are demoted to internal engines behind the
:class:`StepEngine` protocol), runs the scan **chunked** so recordings
stream to host-side monitors instead of materializing ``(steps, n)`` on
device, and makes the paper's partition-parallel serialization one call.

Engine selection (``engine="auto"``):

  * ``k == 1``                         → single-partition engine;
  * ``k > 1``, uniform partitions and  → SPMD engine: one partition per
    ``len(jax.devices()) >= k``          device via ``shard_map``;
  * otherwise                          → single engine over
    ``merge_to_single(net)`` (same global labelling, bit-identical
    trajectory — asserted in tests), so a partitioned network runs
    anywhere.

The kernel backend defaults to ``ref`` — the XLA-compiled step — on every
platform, TPU included; on a TPU that step delivers its spikes through the
compiled Pallas kernel over packed spike bits, the one synapse kernel the
TPU compiler accepts (``kernels.dispatch.PALLAS_GATHER_LIMIT``).  An
explicit ``SimConfig(backend="pallas")`` raises at construction unless
the step is the unfused one of a network without STDP.  ``describe()``
names the engine, backend and delivery kernel that run, and why.

Both engines share one output contract (see :mod:`repro.snn.monitors`):
``spike_count`` ``(steps,)`` int32 summed over partitions, ``raster``
``(steps, n)`` uint8 in the global labelling, ``v_mean`` ``(steps,)``
float32.

Serialization contract (``session.save`` / ``Session.restore``)
---------------------------------------------------------------

One simulation step ``t`` performs, in order: (1) deliver ``ring[t % D]``,
(2) neuron update → spikes ``s_t``, (3) trace decay+bump, (4) exchange,
(5) propagate into ``ring[(t + d) % D]``, (6) STDP, (7) record
``hist[t % D] = s_t``, then ``t += 1``.  ``save`` captures the state
*between* steps: after step ``t_now - 1`` completed and before ``t_now``
begins.  It writes, atomically (staged in a ``.tmp`` dir, previous snapshot
renamed aside before the swap, CRC32 per shard in the manifest — at every
instant a complete snapshot exists on disk):

  * the dCSR network itself with vertex state and synaptic weights synced
    back from the device (``part<p>.npz`` per partition — each process
    touches only its own rows, the paper's partition-parallel property);
  * the in-flight runtime per partition: future-current ring buffer
    (``ring``), recent spike history (``hist``, needed for event-level
    interop), and STDP traces (``tr_plus``/``tr_minus``);
  * ``t_now`` and the model dictionary in ``manifest.json``.

Checkpoint writes are **asynchronous**: ``save`` synchronously syncs the
device state and captures a host-side *copy* of everything the snapshot
needs (``io.dcsr_binary.snapshot_network`` — race-free against continued
simulation, which keeps mutating the live ``net.parts``), then enqueues
the file write on a background :class:`repro.io.AsyncWriter`; the
``part<p>.npz`` shards are written by a thread pool, one writer per
partition (the paper's "performed largely independently between parallel
processes").  ``save(wait=True)`` (the default) drains the queue before
returning — the snapshot, and every previously queued one, is durable.
``run(checkpoint_every=...)`` saves with ``wait=False`` so the simulation
loop keeps advancing while the previous snapshot flushes; call
:meth:`Session.wait` (or ``close()``, or leave a ``with Session(...)``
block) to make queued checkpoints durable.  A background write failure is
re-raised on the caller's thread at the next checkpoint boundary or in
``wait()``/``close()`` — never swallowed.  Sync and async writes share
one serializer, so the bytes on disk are identical.

``Session.restore(path, k=...)`` is **elastic**: because simulation noise
is a pure function of ``(seed, t, permanent neuron id)`` and runtime arrays
are row-aligned, a snapshot taken at one k restores onto any other k
(routed through :mod:`repro.snn.reshard`) and continues **bit-identically**
— the paper's "repartitioning ... to optimally fit different backends",
asserted end-to-end in ``tests/test_session.py``.  One caveat: the
compressed index exchange (the ``exchange='auto'`` default for non-plastic
k > 1) has a per-partition capacity, which is k-dependent — a *lossy* run
(``RunResult.overflow`` nonzero, always accompanied by a ``UserWarning``)
is therefore only bit-reproducible at the same k.  Lossless runs (dense,
or index with zero overflow — the designed operating point) keep the
cross-k guarantee.  ``restore`` also accepts
a root of ``step_XXXXXXXX`` snapshots (as written by
``session.run(checkpoint_every=...)``) and walks newest-first past
corrupt/truncated steps.

Typical use::

    from repro.snn import Session, SimConfig, microcircuit, to_dcsr
    from repro.snn.monitors import RasterMonitor

    net = to_dcsr(microcircuit(scale=0.01), k=4)
    with Session(net, SimConfig()) as ses:  # exit drains queued writes
        raster = RasterMonitor()
        res = ses.run(1000, monitors=[raster], checkpoint_every=200,
                      checkpoint_dir="ckpts")   # async, non-blocking
        ses.save("final")                   # one-call snapshot (durable)
    ses2 = Session.restore("final", k=2)    # elastic restart on k=2
"""
from __future__ import annotations

import collections.abc
import dataclasses
import os
import shutil
import threading
import time
import warnings
import weakref
from typing import Dict, Iterable, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.dcsr import DCSRNetwork, merge_to_single
from ..core.partition import block_partition
from ..io.async_writer import AsyncWriter
from ..io.dcsr_binary import (
    load_latest_valid, snapshot_network, snapshot_steps, write_snapshot,
)
from ..kernels.dispatch import EVENT_ACTIVITY_THRESHOLD, SIM_BACKEND_REASON
from .dist_sim import DistSimulator
from .reshard import RUNTIME_KEYS, concat_runtime, reshard_sim_state
from .simulator import SimConfig, Simulator

_DEFAULT_CHUNK = 128


class StepEngine(Protocol):
    """What the session needs from an engine: init/advance a carry, sync it
    back to dCSR, and export/import the in-flight runtime per partition.
    ``run_chunk`` returns host-side outputs in the unified contract."""

    kind: str
    net: DCSRNetwork

    def init_state(self, t0: int = 0) -> Dict: ...

    def run_chunk(self, state: Dict, steps: int) -> Tuple[Dict, Dict]: ...

    def sync_to_dcsr(self, state: Dict) -> None: ...

    def runtime_state(self, state: Dict) -> Dict[int, Dict]: ...

    def load_runtime(self, state: Dict, sim_state: Dict[int, Dict]) -> Dict: ...


class _SingleEngine:
    """k=1 engine (wraps the legacy ``Simulator``).  Also serves k>1
    networks through their merged single-partition view."""

    kind = "single"

    def __init__(self, net: DCSRNetwork, cfg: SimConfig):
        self.net = net
        self.sim = Simulator(net, cfg)

    @property
    def engine_choice(self):
        return self.sim.engine_choice

    @property
    def dt(self) -> float:
        return self.sim.dt

    @property
    def d_ring(self) -> int:
        return self.sim.d_ring

    def init_state(self, t0: int = 0) -> Dict:
        return self.sim.init_state(t0)

    def run_chunk(self, state: Dict, steps: int) -> Tuple[Dict, Dict]:
        with obs.span(obs.DISPATCH):
            state, outs = self.sim.run(state, steps)
        with obs.span(obs.FETCH):
            host = dict(
                spike_count=np.asarray(outs["spike_count"]).astype(np.int32),
                overflow=np.asarray(outs["overflow"]).astype(np.int32),
            )
            if "raster" in outs:
                host["raster"] = np.asarray(outs["raster"])
            if "v_mean" in outs:
                host["v_mean"] = np.asarray(outs["v_mean"])
        return state, host

    def sync_to_dcsr(self, state: Dict) -> None:
        self.sim.state_to_dcsr(state)

    def runtime_state(self, state: Dict) -> Dict[int, Dict]:
        return self.sim.runtime_state(state)

    def load_runtime(self, state: Dict, sim_state: Dict[int, Dict]) -> Dict:
        # a k>1 snapshot concatenates (partition order == merged labelling)
        merged = concat_runtime(sim_state)
        return dict(
            state, **{k: jnp.asarray(v) for k, v in merged.items()}
        )


class _SPMDEngine:
    """k>1 engine (wraps the legacy ``DistSimulator``): one partition per
    device, single spike-exchange collective per step."""

    kind = "spmd"

    def __init__(self, net: DCSRNetwork, cfg: SimConfig, mesh=None):
        self.net = net
        self.sim = DistSimulator(net, cfg, mesh=mesh)

    @property
    def engine_choice(self):
        return self.sim.engine_choice

    @property
    def dt(self) -> float:
        return self.sim.dt

    @property
    def d_ring(self) -> int:
        return self.sim.stacked.d_ring

    def init_state(self, t0: int = 0) -> Dict:
        return self.sim.init_state(t0)

    def run_chunk(self, state: Dict, steps: int) -> Tuple[Dict, Dict]:
        with obs.span(obs.DISPATCH):
            state, outs = self.sim.run(state, steps)
        with obs.span(obs.FETCH):
            sc = np.asarray(outs["spike_count"])  # (steps, k)
            host = dict(
                spike_count=sc.sum(axis=1).astype(np.int32),
                overflow=np.asarray(outs["overflow"]).sum(axis=1).astype(
                    np.int32
                ),
            )
            if "raster" in outs:
                r = np.asarray(outs["raster"])  # (steps, k, n_p)
                host["raster"] = r.reshape(r.shape[0], -1)
            if "v_mean" in outs:
                host["v_mean"] = (
                    np.asarray(outs["v_mean"]).mean(axis=1).astype(np.float32)
                )
        return state, host

    def sync_to_dcsr(self, state: Dict) -> None:
        self.sim.state_to_dcsr(state)

    def runtime_state(self, state: Dict) -> Dict[int, Dict]:
        return self.sim.runtime_state(state)

    def load_runtime(self, state: Dict, sim_state: Dict[int, Dict]) -> Dict:
        if not sim_state:
            return state
        k = self.net.k
        parts = [sim_state.get(p, {}) for p in range(k)]
        keys = set(RUNTIME_KEYS).intersection(*(set(p) for p in parts))
        upd = {
            key: jnp.asarray(np.stack([p[key] for p in parts]))
            for key in RUNTIME_KEYS
            if key in keys
        }
        return dict(state, **upd)


@dataclasses.dataclass(frozen=True, eq=False)
class RunResult(collections.abc.Mapping):
    """Host-side result of ``Session.run``.  Mapping access exposes
    ``result["spike_count"]`` so post-hoc helpers (``monitors.summary``)
    accept it like legacy output dicts; richer recordings live on the
    monitor objects passed to ``run``.

    ``overflow`` counts spikes DROPPED per step by a lossy exchange
    (compressed index lists past ``SimConfig.index_cap_frac``), summed over
    partitions; all-zero for dense/identity exchanges.  A nonzero total
    also emits a ``UserWarning`` from ``Session.run``."""

    spike_count: np.ndarray  # (steps,) int32, summed over partitions
    t_final: int
    chunks: Tuple[int, ...]  # chunk lengths actually executed
    overflow: np.ndarray = None  # (steps,) int32, summed over partitions

    def __getitem__(self, key):
        if key == "spike_count":
            return self.spike_count
        if key == "overflow":
            return self.overflow
        raise KeyError(key)

    def __iter__(self):
        return iter(("spike_count", "overflow"))

    def __len__(self):
        return 2


class Session:
    """One object for the paper's whole workflow; see the module docstring
    for the engine-selection rules and the serialization contract."""

    # advanced by the AsyncWriter worker, read on the run loop's thread
    _guarded_by_ = {"_last_good_ckpt_step": "_ckpt_mark_lock"}

    def __init__(
        self,
        net_or_path,
        cfg: Optional[SimConfig] = None,
        *,
        engine: str = "auto",
        mesh=None,
        k: Optional[int] = None,
        build_chunk_rows: Optional[int] = None,
        build_path: str = "auto",
    ):
        from ..builder.rules import RuleSpec

        if isinstance(net_or_path, RuleSpec):
            # procedural one-call build: each partition's dCSR rows are
            # emitted directly (chunked, counter-based seeding) — no
            # whole-network NetworkDef is ever materialized
            from ..builder.procedural import DEFAULT_CHUNK_ROWS, build_network

            kk = 1 if k is None else int(k)
            net = build_network(
                net_or_path, k=kk, uniform=kk > 1,
                chunk_rows=build_chunk_rows or DEFAULT_CHUNK_ROWS,
                path=build_path,
            )
            sim_state, t_now = None, 0
        elif k is not None:
            raise ValueError(
                "Session(k=...) only applies when building from a RuleSpec; "
                "use Session.restore(path, k=...) for snapshots"
            )
        elif isinstance(net_or_path, (str, os.PathLike)):
            with obs.span(obs.RESTORE_READ):
                net, sim_state, t_now = load_latest_valid(
                    os.fspath(net_or_path)
                )
        elif isinstance(net_or_path, DCSRNetwork):
            net, sim_state, t_now = net_or_path, None, 0
        else:
            raise TypeError(
                "Session expects a DCSRNetwork, a RuleSpec or a snapshot "
                f"path, got {type(net_or_path).__name__}"
            )
        self.cfg = cfg if cfg is not None else SimConfig()
        self.source_k = net.k
        self._mesh = mesh
        self.engine_kind = self._select_engine_kind(net, engine, mesh)
        self.net = (
            merge_to_single(net)
            if (self.engine_kind == "single" and net.k > 1)
            else net
        )
        self._engine_obj: Optional[StepEngine] = None
        self._engine_flags: Optional[Tuple[bool, bool, str]] = None
        self._state: Optional[Dict] = None
        self._t0 = int(t_now)
        self._pending_runtime = sim_state if sim_state else None
        # gather='auto' starts on the dense sweep; run()'s chunk loop swaps
        # to the event engine (and back) from the observed spike rate
        self._gather_mode = (
            "dense" if self.cfg.gather == "auto" else self.cfg.gather
        )
        # gather mode each chunk of the last run() actually executed with
        self.last_gather_modes: Tuple[str, ...] = ()
        # run-loop stall (seconds) of each checkpoint taken by the last
        # run(checkpoint_every=...): what --mode ckpt benchmarks
        self.last_ckpt_stalls: Tuple[float, ...] = ()
        # step of the newest snapshot whose background write LANDED —
        # the operator's actual rollback point when a later write fails
        self._last_good_ckpt_step: Optional[int] = None
        self._ckpt_mark_lock = threading.Lock()
        self._writer: Optional[AsyncWriter] = None
        # eager engine build: surfaces SimConfig/backend errors at
        # construction and fixes dt/d_ring for save()
        self._engine(self.cfg.record_raster, self.cfg.record_v)

    # -- engine selection --------------------------------------------------
    @staticmethod
    def _select_engine_kind(net: DCSRNetwork, engine: str, mesh) -> str:
        if engine not in ("auto", "single", "spmd"):
            raise ValueError(
                f"engine={engine!r}: expected 'auto', 'single' or 'spmd'"
            )
        uniform = len({p.n for p in net.parts}) == 1
        enough = mesh is not None or len(jax.devices()) >= net.k
        if engine == "spmd":
            if net.k == 1:
                raise ValueError("engine='spmd' needs a k>1 network")
            if not uniform:
                raise ValueError(
                    "engine='spmd' needs uniform partitions; build with "
                    "to_dcsr(..., uniform=True)"
                )
            if not enough:
                raise ValueError(
                    f"engine='spmd' needs >= {net.k} devices "
                    f"(have {len(jax.devices())})"
                )
            return "spmd"
        if engine == "single" or net.k == 1:
            return "single"
        return "spmd" if (uniform and enough) else "single"

    def _engine(self, record_raster: bool, record_v: bool) -> StepEngine:
        """Engine with exactly the requested recordings.  At most ONE
        engine instance is kept (device-resident constants and jit caches
        are not duplicated per flag combination); changing the recording
        set replaces it — the carry pytree is engine-independent, so state
        survives the swap, at the cost of a recompile when recordings
        toggle."""
        key = (bool(record_raster), bool(record_v), self._gather_mode)
        if self._engine_obj is None or self._engine_flags != key:
            cfg = dataclasses.replace(
                self.cfg, record_raster=key[0], record_v=key[1],
                gather=self._gather_mode,
            )
            if self.engine_kind == "spmd":
                eng: StepEngine = _SPMDEngine(self.net, cfg, mesh=self._mesh)
            else:
                eng = _SingleEngine(self.net, cfg)
            self._engine_obj = eng
            self._engine_flags = key
        return self._engine_obj

    @property
    def _current_engine(self) -> StepEngine:
        if self._engine_obj is None:
            self._engine(self.cfg.record_raster, self.cfg.record_v)
        return self._engine_obj

    def _ensure_state(self, engine: StepEngine) -> None:
        if self._state is None:
            st = engine.init_state(self._t0)
            if self._pending_runtime is not None:
                st = engine.load_runtime(st, self._pending_runtime)
                self._pending_runtime = None
            self._state = st

    # -- introspection -----------------------------------------------------
    @property
    def n(self) -> int:
        return self.net.n

    @property
    def m(self) -> int:
        return self.net.m

    @property
    def k(self) -> int:
        """Partitions actually simulated (1 for the merged fallback)."""
        return self.net.k

    @property
    def dt(self) -> float:
        return self._current_engine.dt

    @property
    def d_ring(self) -> int:
        return self._current_engine.d_ring

    @property
    def t(self) -> int:
        """Next step index (steps completed since t=0)."""
        return (
            int(self._state["t"]) if self._state is not None else self._t0
        )

    @property
    def state(self) -> Dict:
        """The device-side carry, materialized on first access (restored
        pending runtime included)."""
        self._ensure_state(self._current_engine)
        return self._state

    @property
    def engine_choice(self):
        """Fused/unfused step-engine decision of the kernel layer."""
        return self._current_engine.engine_choice

    @property
    def permanent_ids(self) -> np.ndarray:
        """Permanent (pre-partitioning) neuron id per current global row —
        the invariant labelling for cross-k trajectory comparison."""
        return np.concatenate([p.global_ids for p in self.net.parts])

    def describe(self) -> Dict:
        d = dict(
            n=self.n, m=self.m, k=self.k, source_k=self.source_k,
            engine=self.engine_kind, t=self.t,
            step_engine=self.engine_choice.engine,
            gather=self._gather_mode,
            overlap=self.engine_choice.overlap,
        )
        d["backend"] = self._current_engine.sim.backend
        d["backend_reason"] = (
            "set by SimConfig(backend=...) or REPRO_BACKEND"
            if self.cfg.backend or os.environ.get("REPRO_BACKEND")
            else SIM_BACKEND_REASON
        )
        d["delivery"] = dict(self._current_engine.sim.delivery)
        if isinstance(self._current_engine, _SingleEngine):
            d["ell_fill"] = self._current_engine.sim.ell.fill_factor
        else:
            d["exchange"] = self._current_engine.sim.exchange
        return d

    # -- simulate ----------------------------------------------------------
    def run(
        self,
        steps: int,
        monitors: Iterable = (),
        *,
        chunk_size: Optional[int] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        max_to_keep: Optional[int] = None,
        checkpoint_sync: bool = False,
    ) -> RunResult:
        """Advance the simulation ``steps`` steps as a chunked scan.

        ``monitors`` are streaming accumulators (see
        :mod:`repro.snn.monitors`); the needed recordings (raster, v_mean)
        are enabled automatically from their ``requires`` sets.
        ``checkpoint_every`` writes an atomic snapshot under
        ``checkpoint_dir/step_XXXXXXXX`` every that-many steps (chunks are
        aligned to checkpoint boundaries); ``max_to_keep`` garbage-collects
        older step snapshots.  Chunking is bit-transparent: the trajectory
        is identical for any ``chunk_size``.

        Checkpoints are taken **asynchronously** by default: the loop only
        pays for the device→host sync plus a host-side snapshot copy, and
        keeps simulating while the background writer flushes the previous
        snapshot's ``part<p>.npz`` shards (a thread pool, one writer per
        partition).  After ``run`` returns the last checkpoints may still
        be in flight — ``Session.wait()`` / ``close()`` make them durable;
        a background write error is re-raised at the next checkpoint
        boundary or in ``wait()``.  ``checkpoint_sync=True`` restores the
        fully blocking behaviour (each snapshot durable before the next
        chunk runs); both paths produce bit-identical snapshots.  The
        per-checkpoint run-loop stall is recorded in
        ``self.last_ckpt_stalls`` (seconds) either way —
        ``benchmarks/spike_throughput.py --mode ckpt`` measures exactly
        this.
        """
        if steps <= 0:
            raise ValueError(f"steps must be positive, got {steps}")
        if checkpoint_every is not None:
            if checkpoint_every <= 0:
                raise ValueError("checkpoint_every must be positive")
            if checkpoint_dir is None:
                raise ValueError("checkpoint_every requires checkpoint_dir")
        monitors = tuple(monitors)
        need = set()
        for mon in monitors:
            need |= set(getattr(mon, "requires", ()))
        rec_raster = self.cfg.record_raster or "raster" in need
        rec_v = self.cfg.record_v or "v_mean" in need
        engine = self._engine(rec_raster, rec_v)
        self._ensure_state(engine)
        # activity-threshold dispatcher: with gather='auto' on an
        # event-capable partition, each chunk's observed spike rate feeds
        # an EMA; crossing EVENT_ACTIVITY_THRESHOLD swaps the gather mode
        # for the NEXT chunk (the carry pytree is engine-independent, so
        # the swap is a recompile, never a trajectory change)
        adaptive = self.cfg.gather == "auto" and bool(
            getattr(getattr(engine, "sim", None), "event_capable", False)
        )
        rate_ema: Optional[float] = None
        if chunk_size is None:
            chunk_size = min(steps, _DEFAULT_CHUNK)
        chunk_size = max(1, int(chunk_size))

        t_run0 = self.t
        for mon in monitors:
            mon.begin(self)
        counts, overflows, chunks, stalls = [], [], [], []
        gather_modes = []
        done = 0
        next_ckpt = checkpoint_every
        while done < steps:
            c = min(chunk_size, steps - done)
            if next_ckpt is not None:
                c = min(c, next_ckpt - done)
            with obs.span(obs.CHUNK):
                state, outs = engine.run_chunk(self._state, c)
            self._state = state
            with obs.span(obs.READOUT):
                for mon in monitors:
                    mon.on_chunk(t_run0 + done, outs)
            counts.append(outs["spike_count"])
            overflows.append(outs["overflow"])
            chunks.append(c)
            gather_modes.append(self._gather_mode)
            done += c
            if adaptive:
                rate = float(
                    np.mean(outs["spike_count"])
                ) / max(self.n, 1)
                rate_ema = (
                    rate if rate_ema is None
                    else 0.5 * rate_ema + 0.5 * rate
                )
                desired = (
                    "event" if rate_ema < EVENT_ACTIVITY_THRESHOLD
                    else "dense"
                )
                if desired != self._gather_mode:
                    self._gather_mode = desired
                    engine = self._engine(rec_raster, rec_v)
            if next_ckpt is not None and done == next_ckpt:
                t_ck = time.perf_counter()
                with obs.span(obs.CKPT):
                    self._checkpoint(
                        checkpoint_dir, t_run0 + done, max_to_keep,
                        checkpoint_sync,
                    )
                stalls.append(time.perf_counter() - t_ck)
                next_ckpt += checkpoint_every
        for mon in monitors:
            mon.finalize()
        self.last_gather_modes = tuple(gather_modes)
        if checkpoint_every is not None:
            self.last_ckpt_stalls = tuple(stalls)
        overflow = np.concatenate(overflows)
        dropped = int(overflow.sum())
        if dropped:
            # the engine owns the effective-cap formula (incl. its floor)
            cap = getattr(engine.sim, "index_cap", None)
            warnings.warn(
                f"compressed index exchange dropped {dropped} spikes over "
                f"{done} steps (effective cap: {cap} spike ids per "
                "partition per step); raise SimConfig(index_cap_frac=...) "
                "or use exchange='dense' for a lossless run",
                UserWarning,
                stacklevel=2,
            )
        return RunResult(
            spike_count=np.concatenate(counts),
            t_final=t_run0 + done,
            chunks=tuple(chunks),
            overflow=overflow,
        )

    def _checkpoint(self, root: str, step: int,
                    max_to_keep: Optional[int], sync: bool) -> None:
        """One checkpoint boundary of ``run``: save ``root/step_XXXXXXXX``
        and queue the retention GC behind it."""
        try:
            self.save(os.path.join(root, f"step_{step:08d}"), wait=sync)
        except OSError as e:
            with self._ckpt_mark_lock:
                last = self._last_good_ckpt_step
            raise OSError(
                f"checkpoint at step {step} failed "
                "(writer retries exhausted); last successful "
                "checkpoint: "
                + (f"step {last}" if last is not None else
                   "none from this session")
                + " — that is your rollback point"
            ) from e
        if max_to_keep:
            # retention rides the same FIFO queue as the writes, so GC
            # can never run ahead of an in-flight older step
            if sync:
                self._gc_checkpoints(root, max_to_keep)
            else:
                with obs.span(obs.CKPT_ENQUEUE):
                    self._writer_obj().submit(
                        self._gc_checkpoints, root, max_to_keep,
                    )

    def run_supervised(
        self,
        steps: int,
        monitors: Iterable = (),
        *,
        chunk_size: Optional[int] = None,
        checkpoint_every: int,
        checkpoint_dir: str,
        max_to_keep: Optional[int] = None,
        health=None,
        retry=None,
    ):
        """Self-healing ``run``: per-chunk health checks (non-finite
        membranes, spike-storm ceiling, escalating exchange overflow),
        automatic rollback to the newest valid checkpoint with bounded
        retries + exponential backoff, and corrupt-shard quarantine with
        RuleSpec-keystream topology regeneration on restore.  See
        :mod:`repro.snn.supervisor` for the policies (``health``:
        :class:`~repro.snn.supervisor.HealthConfig`, ``retry``:
        :class:`~repro.snn.supervisor.RetryPolicy`) and the exact
        rollback/replay semantics."""
        from .supervisor import run_supervised

        return run_supervised(
            self, steps, monitors, chunk_size=chunk_size,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir, max_to_keep=max_to_keep,
            health=health, retry=retry,
        )

    # -- checkpoint / restart ----------------------------------------------
    def _reload_from_snapshot(self, net: DCSRNetwork, sim_state,
                              t_now: int) -> None:
        """In-place rollback: replace the network and carry with a
        restored snapshot (same layout this session saves at) and drop
        the engine — device constants rebuild lazily from the restored
        arrays, and the next ``run`` continues from ``t_now``."""
        if self.engine_kind == "single" and net.k > 1:
            net = merge_to_single(net)
        if net.k != self.net.k or net.n != self.net.n:
            raise ValueError(
                f"rollback snapshot is k={net.k}, n={net.n}; this "
                f"session runs k={self.net.k}, n={self.net.n}"
            )
        self.net = net
        self._engine_obj = None
        self._engine_flags = None
        self._state = None
        self._t0 = int(t_now)
        self._pending_runtime = sim_state if sim_state else None

    def _writer_obj(self) -> AsyncWriter:
        if self._writer is None:
            # bounded queue = backpressure: when the disk falls behind the
            # checkpoint cadence, save() blocks instead of accumulating an
            # unbounded number of full host-state snapshots (each boundary
            # submits a write + optionally a GC job, so 4 pending jobs
            # ≈ two queued snapshots + the one being written)
            self._writer = AsyncWriter(
                name="dcsr-ckpt-writer", max_pending=4
            )
            # reclaim the worker thread when a Session is dropped without
            # close(): queued jobs still flush (FIFO before the sentinel),
            # but the thread exits instead of leaking one blocked daemon
            # per abandoned Session
            weakref.finalize(self, self._writer.close, drain=False)
        return self._writer

    def save(self, path: str, *, wait: bool = True) -> str:
        """One-call snapshot: sync device state back into the dCSR
        partitions, capture a host-side copy, and write network +
        in-flight runtime + ``t`` atomically (see the module docstring for
        exactly what is captured).

        What is guaranteed at return:

        * always — the snapshot content is *captured*: a later step, GC,
          or another ``save`` cannot change what this snapshot will hold,
          and any background error from a previous ``save`` has been
          re-raised here;
        * ``wait=True`` (default) — this snapshot and every previously
          enqueued one are durable on disk (the write queue is drained in
          FIFO order, so no newer step ever lands before an older one);
        * ``wait=False`` — the write is in flight on the background
          writer; ``Session.wait()`` / ``close()`` make it durable.
        """
        eng = self._current_engine
        self._ensure_state(eng)
        if self._writer is not None:
            self._writer.check()  # surface earlier background failures
        with obs.span(obs.CKPT_SYNC, bytes=obs.nbytes(
                self._state["vtx_state"], self._state["weights"])):
            eng.sync_to_dcsr(self._state)
        step = self.t
        with obs.span(obs.CKPT_CAPTURE) as sp:
            snap = snapshot_network(
                self.net, eng.runtime_state(self._state), step
            )
            sp.set_metadata(bytes=snap.copied_bytes)
        w = self._writer_obj()
        with obs.span(obs.CKPT_ENQUEUE):
            w.submit(self._write_and_mark, snap, path, step,
                     context=dict(step=step, path=path))
        if wait:
            w.wait()
        return path

    def _write_and_mark(self, snap, path: str, step: int) -> None:
        """Background write body: only a write that fully landed advances
        ``_last_good_ckpt_step`` (the rollback point named in errors)."""
        write_snapshot(snap, path, atomic=True)
        with self._ckpt_mark_lock:
            self._last_good_ckpt_step = step

    def wait(self) -> None:
        """Drain the background checkpoint writer: block until every
        enqueued snapshot (and retention GC) has landed, re-raising any
        background write error."""
        if self._writer is not None:
            self._writer.wait()

    def close(self) -> None:
        """Drain the checkpoint queue and stop the background writer
        (re-raising any pending background error).  The session remains
        usable afterwards — a later ``save`` starts a fresh writer."""
        if self._writer is not None:
            w, self._writer = self._writer, None
            w.close()

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            self.close()
        else:
            try:  # don't mask the in-flight exception with a drain error
                self.close()
            except Exception as drain_err:
                # ...but never swallow it silently either: the user must
                # learn their checkpoints did not land
                warnings.warn(
                    "background checkpoint write failed while unwinding "
                    f"another exception: {drain_err!r}",
                    RuntimeWarning,
                    stacklevel=2,
                )
        return False

    @classmethod
    def restore(
        cls,
        path: str,
        *,
        k: Optional[int] = None,
        cfg: Optional[SimConfig] = None,
        assignment: Optional[np.ndarray] = None,
        engine: str = "auto",
        mesh=None,
        streaming: bool = False,
        chunk_rows: Optional[int] = None,
    ) -> "Session":
        """Restore a session from ``session.save`` output (or a
        ``checkpoint_every`` root, walking past corrupt steps).

        ``k``/``assignment`` trigger **elastic** restore: the network and
        its in-flight runtime are re-partitioned (``snn/reshard.py``) before
        the engine is built, and the continued trajectory is bit-identical
        to an uninterrupted run.

        ``streaming=True`` reads the snapshot chunk-by-chunk
        (``repro.builder.ingest``, ``chunk_rows`` rows at a time) through
        the same CRC/``.old``-fallback walk, bit-identical to the eager
        path: restoring at the snapshot's native k (or merging to k=1)
        never materializes more than one chunk plus one partition of
        intermediate state.  Elastic restore onto any *other* k still
        re-partitions eagerly — it is the only path that moves
        whole-network state."""
        if streaming:
            from ..builder.ingest import (
                DEFAULT_CHUNK_ROWS, make_streaming_loader,
            )

            loader = make_streaming_loader(
                k=1 if (k == 1 and assignment is None) else None,
                chunk_rows=chunk_rows or DEFAULT_CHUNK_ROWS,
            )
        else:
            loader = None
        with obs.span(obs.RESTORE_READ):
            net, sim_state, t_now = load_latest_valid(
                os.fspath(path), loader=loader
            )
        if assignment is not None or (k is not None and k != net.k):
            asn = (
                np.asarray(assignment, np.int64)
                if assignment is not None
                else block_partition(net.n, k)
            )
            with obs.span(obs.RESTORE_RESHARD):
                net, sim_state = reshard_sim_state(net, sim_state, asn)
        ses = cls(net, cfg, engine=engine, mesh=mesh)
        ses._t0 = int(t_now)
        ses._pending_runtime = sim_state if sim_state else None
        return ses

    @staticmethod
    def _gc_checkpoints(root: str, keep: int) -> None:
        for step in snapshot_steps(root)[:-keep]:
            d = os.path.join(root, f"step_{step:08d}")
            shutil.rmtree(d, ignore_errors=True)
            shutil.rmtree(d + ".old", ignore_errors=True)
