"""Clock-driven SNN simulator over a dCSR partition (JAX, scan-based).

One step (documented order — the serialization contract depends on it):

  1. deliver: ``i_syn = ring[t % D]``; clear slot.
  2. neuron update with ``i_syn + bias + noise(t, global_id)`` -> spikes s_t.
  3. traces (if plastic): x' = x * exp(-dt/tau) + s_t   (inclusive variant).
  4. exchange: act/pre-trace become global vectors (identity for k = 1,
     all-gather in the distributed wrapper).
  5. propagate with *pre-update* weights: per delay bucket b,
     ``ring[(t + d_b) % D] += spike_gather(act, cols_b, w_b)`` (on a TPU
     the spikes are packed into bits once and every bucket's panel is
     delivered by the Pallas kernel over them).
  6. STDP: w' from the fused kernel (plastic slots only).
  7. history: ``hist[t % D] = s_t`` (for in-flight event serialization).

Noise is a pure function of (seed, t, global neuron id) so that any
partitioning, restart, or resharding reproduces bit-identical trajectories —
the property the dCSR checkpoint tests assert.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.dcsr import DCSRNetwork, DCSRPartition
from ..core.ell import DelayELL, build_delay_ell
from ..core.state import EDGE_WEIGHT
from ..kernels import ops
from ..kernels.dispatch import (
    BACKENDS, StepEngineChoice, event_id_cap, require_compilable,
    resolve_delivery_backend, resolve_sim_backend, select_step_engine,
)
from ..kernels.event_step import EventPlan
from ..kernels.spike_gather import WORD_BITS, pack_spikes
from .neurons import (
    LIF_BIAS, LIF_PARAM_KEYS, LIF_REF, LIF_V, make_neuron_step,
)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """User-facing simulation knobs.

    The engine-affecting knobs (``backend``, ``fused``, ``exchange``,
    ``gather``, ``overlap``) feed :func:`kernels.dispatch.select_step_engine`,
    which picks one of the step engines — ``fused`` / ``fused_plastic``
    (identity exchange, one kernel), ``fused_split`` /
    ``fused_split_plastic`` (split at the exchange), ``fused_event`` /
    ``fused_split_event`` (event-driven gather), or ``unfused`` — plus an
    orthogonal exchange/compute ``overlap`` mode for the split engines.
    The full eligibility table and every ``auto`` resolution rule live in
    ``docs/ARCHITECTURE.md``."""

    backend: Optional[str] = None  # None=auto, 'ref', 'pallas_interpret', 'pallas'
    fused: Optional[bool] = None  # None=auto, True=require fused step, False=off
    align_k: int = 128
    align_rows: int = 8
    max_k: Optional[int] = None  # heavy-row split cap (single-partition only)
    record_raster: bool = False
    record_v: bool = False
    # 'auto' | 'dense' | 'index' (distributed only): 'auto' resolves to the
    # compressed index exchange for non-plastic multi-partition nets (the
    # fused-split hot path — collective bytes stay at spike-count scale)
    # and the paper-faithful dense all-gather otherwise
    exchange: str = "auto"
    index_cap_frac: float = 0.25  # K cap for compressed exchange, frac of n_p
    # 'auto' | 'dense' | 'event': panel-traversal flavour of the fused
    # engines.  'event' restricts each step's gather to synapse row blocks
    # with at least one active presynaptic spike (fused_event /
    # fused_split_event); 'auto' starts dense and lets Session's chunk loop
    # switch on the event gather when the observed spike rate stays under
    # kernels.dispatch.EVENT_ACTIVITY_THRESHOLD (and back when it rises)
    gather: str = "auto"
    event_cap_frac: float = 0.05  # compressed spike-id capacity, frac of n
    # 'auto' | 'off' | 'local' | 'double_buffer': exchange/compute overlap
    # for the split engines (k>1 — an identity exchange has no collective
    # to hide).  'local' splits the post-exchange gather into an
    # own-partition pass that is data-independent of the collective (so
    # the all-gather runs concurrently with it) plus a remote pass behind
    # it; 'double_buffer' additionally defers the remote pass of step t to
    # the top of step t+1 so the collective pipelines against a full
    # step's compute.  'auto' resolves to 'local' on the compiled pallas
    # backend and 'off' elsewhere (interpreted/ref backends gain nothing)
    overlap: str = "auto"
    seed: int = 42

    def __post_init__(self):
        # fail at construction with an actionable message, not deep inside
        # resolve_sim_backend / the exchange builder
        if self.backend is not None and self.backend not in BACKENDS:
            raise ValueError(
                f"SimConfig(backend={self.backend!r}): unknown backend; "
                f"expected one of {BACKENDS} or None for platform "
                "auto-detection (REPRO_BACKEND env also applies)"
            )
        if self.exchange not in ("auto", "dense", "index"):
            raise ValueError(
                f"SimConfig(exchange={self.exchange!r}): expected 'auto' "
                "(index for non-plastic k>1, dense otherwise), 'dense' "
                "(all-gathered activity vector, paper-faithful) or 'index' "
                "(compressed fixed-capacity spike-id lists)"
            )
        if not 0.0 < self.index_cap_frac <= 1.0:
            raise ValueError(
                f"SimConfig(index_cap_frac={self.index_cap_frac}): the "
                "compressed-exchange capacity is a fraction of the "
                "partition size and must lie in (0, 1]"
            )
        if self.gather not in ("auto", "dense", "event"):
            raise ValueError(
                f"SimConfig(gather={self.gather!r}): expected 'auto' "
                "(dense until the running spike rate drops under the "
                "event threshold), 'dense' (every synapse panel every "
                "step) or 'event' (event-driven gather over row blocks "
                "with active presynaptic spikes)"
            )
        if not 0.0 < self.event_cap_frac <= 1.0:
            raise ValueError(
                f"SimConfig(event_cap_frac={self.event_cap_frac}): the "
                "compressed spike-id capacity is a fraction of the "
                "activity-vector width and must lie in (0, 1]"
            )
        if self.overlap not in ("auto", "off", "local", "double_buffer"):
            raise ValueError(
                f"SimConfig(overlap={self.overlap!r}): expected 'auto' "
                "('local' on the compiled pallas backend, 'off' "
                "elsewhere), 'off' (serialized exchange -> gather), "
                "'local' (own-partition gather concurrent with the "
                "collective) or 'double_buffer' (remote gather of step t "
                "pipelined against the collective of step t+1)"
            )
        if self.align_k < 1 or self.align_rows < 1:
            raise ValueError(
                f"SimConfig(align_k={self.align_k}, "
                f"align_rows={self.align_rows}): ELL alignments must be >= 1"
            )


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "vtx_model", "vtx_state0", "cols", "weights0", "plastic", "valid",
        "row_maps", "cols_local", "weights_local", "cols_remote",
        "weights_remote",
    ],
    meta_fields=["n_p", "row_start", "delays", "identity_rows", "any_plastic"],
)
@dataclasses.dataclass
class PartitionDeviceData:
    """Constants + initial state for one partition.

    A pytree, so the engines hand it to their jitted programs as
    *arguments*: closed-over arrays would be embedded in the program as
    literals, and at full scale (gigabytes of synapse panels) compiling
    such a program exhausts host memory.  ``plastic`` is empty for a
    partition without STDP synapses (nothing reads it then)."""

    n_p: int
    row_start: int
    vtx_model: jnp.ndarray
    vtx_state0: jnp.ndarray
    delays: Tuple[int, ...]
    cols: List[jnp.ndarray]  # per bucket (R, K) int32 (global ids)
    weights0: List[jnp.ndarray]  # per bucket (R, K) f32
    plastic: List[jnp.ndarray]  # per bucket (R, K) f32 mask (stdp slots)
    valid: List[jnp.ndarray]
    row_maps: List[jnp.ndarray]
    identity_rows: Tuple[bool, ...]
    any_plastic: bool
    # overlap sub-panels (non-plastic split engines only; None otherwise):
    # per bucket, the panel columns split by ownership.  Local panels hold
    # LOCAL ids (col - row_start) gathered from the own (n_p,) spike
    # vector before any collective; remote panels hold global ids that
    # reference only remote partitions (padding col 0 carries weight 0)
    cols_local: Optional[List[jnp.ndarray]] = None
    weights_local: Optional[List[jnp.ndarray]] = None
    cols_remote: Optional[List[jnp.ndarray]] = None
    weights_remote: Optional[List[jnp.ndarray]] = None


@obs.spanned(obs.BUILD_PLACE)
def partition_device_data(
    part: DCSRPartition,
    net: DCSRNetwork,
    ell: DelayELL,
) -> PartitionDeviceData:
    """The partition's constants as host arrays (callers place them)."""
    stdp_id = net.registry.edge_id("syn_stdp")
    any_plastic = bool(np.any(part.edge_model == stdp_id))
    cols, w0, plastic, valid, rmaps, ident = [], [], [], [], [], []
    for b in ell.buckets:
        cols.append(b.cols)
        w0.append(b.weights)
        if any_plastic:
            is_stdp = np.zeros(b.cols.shape, dtype=np.float32)
            sel = b.edge_index >= 0
            is_stdp[sel] = (
                part.edge_model[b.edge_index[sel]] == stdp_id
            ).astype(np.float32)
            plastic.append(is_stdp)
        valid.append(b.valid.astype(np.float32))
        rmaps.append(b.row_map)
        ident.append(b.identity_rows)
    return PartitionDeviceData(
        n_p=part.n,
        row_start=part.row_start,
        vtx_model=part.vtx_model,
        vtx_state0=part.vtx_state,
        delays=tuple(b.delay for b in ell.buckets),
        cols=cols, weights0=w0, plastic=plastic, valid=valid,
        row_maps=rmaps, identity_rows=tuple(ident),
        any_plastic=any_plastic,
    )


def _models_present(net: DCSRNetwork) -> Tuple[str, ...]:
    names = []
    for i, spec in enumerate(net.registry.vertex_models()):
        if any(np.any(p.vtx_model == i) for p in net.parts):
            names.append(spec.name)
    return tuple(names)


def _probe_event_capable(**sel_kw) -> bool:
    """Would ``gather='event'`` actually land on an event engine for this
    partition?  Session's auto-threshold dispatcher consults this before
    swapping gather modes mid-run, so an adaptive swap can never trip the
    ``fused=True`` + event-blocked ValueError or silently re-select the
    engine it already runs."""
    try:
        return select_step_engine(gather="event", **sel_kw).event
    except ValueError:
        return False


def delivery_path(
    choice: StepEngineChoice, deliver_backend: str, n_global: int
) -> Dict:
    """What delivers the step's spikes (``Session.describe()['delivery']``):
    a fused engine's own kernel, XLA's element-wise gather (``xla_take``),
    or the Pallas kernel over ``words`` packed spike words
    (``pallas_bits``)."""
    if choice.fused:
        return {"kernel": choice.engine}
    if deliver_backend == "ref":
        return {"kernel": "xla_take"}
    return {"kernel": "pallas_bits", "words": -(-n_global // WORD_BITS)}


def make_core_step(
    *,
    registry,
    models_present: Sequence[str],
    dt: float,
    noise_sigma: float,
    base_key: jnp.ndarray,
    d_ring: int,
    n_global: int,
    dev: PartitionDeviceData,
    backend: str,
    stdp_params: Optional[Dict[str, float]],
    exchange: Callable,
    noise_ids: Optional[jnp.ndarray] = None,
    record_raster: bool = False,
    record_v: bool = False,
    fused: Optional[bool] = None,
    gather: str = "dense",
    event_cap_frac: float = 0.05,
    event_plan: Optional[EventPlan] = None,
    identity_exchange: Optional[bool] = None,
    engine_choice: Optional[StepEngineChoice] = None,
    overlap: str = "off",
    overlap_ctx: Optional[Dict[str, Callable]] = None,
    deliver_backend: Optional[str] = None,
) -> Callable:
    """The shared per-partition step; ``exchange`` injects the collective.

    ``deliver_backend`` (default: ``backend``) runs the unfused engine's
    spike delivery: ``ref`` gathers the activity element-wise in XLA, a
    Pallas backend packs the exchanged 0/1 activity into bits once per
    step and delivers every bucket's panel through the packed-bit kernel
    (``kernels.dispatch.resolve_delivery_backend``).

    ``exchange(spikes, tr_plus)`` returns ``(act, pre_trace, overflow)``
    where ``overflow`` is the number of local spikes the collective
    *dropped* (compressed index exchange past its capacity; 0 for dense /
    identity exchanges) — every step emits it in ``outs['overflow']`` so
    lossy exchanges are counted and surfaced, never silent.

    ``noise_ids`` are the *permanent* (pre-partitioning) neuron ids of the
    local rows: noise is a pure function of (seed, t, permanent id), so a
    trajectory is invariant under any partitioning/relabelling — the
    property that makes elastic resharding (snn/reshard.py) bit-exact.

    The step engine (fused single-kernel vs fused-split-at-the-exchange —
    each with a ``*_plastic`` variant that folds the STDP pass into the
    same panel traversal — vs the event-gather variants vs unfused
    three-kernel) is chosen by ``kernels.dispatch.select_step_engine``;
    the choice is attached to the returned step as ``step.engine_choice``.

    ``overlap_ctx`` (required whenever the resolved overlap mode is not
    ``'off'``) supplies the three partition-geometry closures the overlap
    engines need — ``local(spikes) -> (n_p,)`` the own-partition activity
    slice *as the collective would deliver it* (a compressed index
    exchange truncates at its cap, so this is not always ``spikes``
    itself), ``embed(v) -> (n,)`` the own slice placed into a zeroed
    global vector, and ``mask_remote(act) -> (n,)`` the exchanged vector
    with the own slice zeroed.  With ``overlap='double_buffer'`` the
    returned step carries a ``'_pending'`` entry holding step t's deferred
    remote contribution; callers add ``step.pending_init()`` to the carry
    before the scan and must call ``step.pending_flush(carry)`` after it
    so no spikes are lost at the scan boundary."""
    D = d_ring
    n_p = dev.n_p
    any_plastic = dev.any_plastic and stdp_params is not None
    tau_plus = stdp_params["tau_plus"] if any_plastic else 1.0
    tau_minus = stdp_params["tau_minus"] if any_plastic else 1.0
    if engine_choice is not None:
        choice = engine_choice  # caller pre-selected (DistSimulator)
    else:
        if identity_exchange is None:
            # single-partition default; distributed callers pass an
            # explicit value (a k=1 *compressed-index* exchange still
            # truncates at its cap, so same-size is not a sufficient
            # proxy there)
            identity_exchange = n_global == n_p
        choice = select_step_engine(
            backend=backend,
            models_present=models_present,
            any_plastic=any_plastic,
            identity_exchange=identity_exchange,
            identity_rows=all(dev.identity_rows),
            n_delay_buckets=len(dev.delays),
            n_p=n_p,
            n_global=n_global,
            fused=fused,
            gather="dense" if gather == "auto" else gather,
            event_cap_frac=event_cap_frac,
            overlap=overlap,
        )
    if choice.overlap != "off" and overlap_ctx is None:
        raise ValueError(
            f"engine {choice.engine!r} resolved overlap="
            f"{choice.overlap!r} but no overlap_ctx was provided; the "
            "distributed driver must supply the local/embed/mask_remote "
            "partition-geometry closures"
        )
    overlap_on = choice.overlap in ("local", "double_buffer")
    if deliver_backend is None:
        deliver_backend = backend
    deliver_bits = deliver_backend != "ref"
    # the XLA STDP pass reads each slot's presynaptic spike from the
    # delivery kernel instead of gathering it a second time
    fired_to_stdp = deliver_bits and any_plastic and backend == "ref"
    if choice.event and event_plan is None:
        event_plan = EventPlan.build(
            dev.cols, dev.valid, n_global, D,
            event_id_cap(n_global, event_cap_frac),
            interpret=backend != "pallas",
        )
    if choice.fused:
        neuron_step = None
        lif_p = dict(registry.spec("lif").params)
        lif_params = {
            "dt": dt, **{k: lif_p[k] for k in LIF_PARAM_KEYS},
        }
    else:
        neuron_step = make_neuron_step(registry, models_present, dt, backend)

    overlap_plastic = choice.engine == "fused_split_plastic"

    def _pending_init() -> Dict[str, jnp.ndarray]:
        """Zeroed deferred-remote-contribution record for double_buffer.

        ``valid`` gates the apply: a zero-pending apply is NOT a bitwise
        no-op (w * 0.0 = -0.0 for negative w; +0.0 + -0.0 = +0.0), so the
        applied arrays are selected with ``jnp.where`` instead of relying
        on zero activity being inert."""
        pend = dict(
            valid=jnp.zeros((), jnp.int32),
            onehot=jnp.zeros((len(dev.delays), D), jnp.float32),
            act=jnp.zeros((n_global,), jnp.float32),
        )
        if overlap_plastic:
            pend.update(
                pre_trace=jnp.zeros((n_global,), jnp.float32),
                post_trace=jnp.zeros((n_p,), jnp.float32),
                post_spike=jnp.zeros((n_p,), jnp.float32),
            )
        return pend

    def _apply_pending(ring, weights, pend):
        """Apply step t-1's deferred remote gather to (ring, weights).

        Runs at the top of step t BEFORE the slot delivery/clear, so a
        delay-1 remote contribution emitted at t-1 still lands in the
        slot delivered at t — the per-slot add sequence is identical to
        overlap='local', hence bit-exact by construction."""
        valid = pend["valid"] > 0
        if overlap_plastic:
            act_remote = overlap_ctx["mask_remote"](pend["act"])
            new_ring, new_w = ops.fused_post_exchange_remote_plastic(
                act_remote, pend["act"], pend["pre_trace"], ring,
                pend["onehot"], pend["post_trace"], pend["post_spike"],
                dev.cols, weights, dev.plastic,
                stdp=stdp_params, backend=backend,
            )
            ring = jnp.where(valid, new_ring, ring)
            weights = tuple(
                jnp.where(valid, nw, w) for nw, w in zip(new_w, weights)
            )
        elif choice.event:
            act_remote = overlap_ctx["mask_remote"](pend["act"])
            sel, flags = event_plan.select(act_remote)
            new_ring = ops.event_post_exchange(
                act_remote, ring, jnp.ones((D,), jnp.float32),
                pend["onehot"], sel, flags, dev.cols, weights,
                backend=backend,
            )
            ring = jnp.where(valid, new_ring, ring)
        else:
            new_ring = ops.fused_post_exchange_remote(
                pend["act"], ring, pend["onehot"],
                dev.cols_remote, dev.weights_remote, backend=backend,
            )
            ring = jnp.where(valid, new_ring, ring)
        return ring, weights

    def step(carry, _):
        # every op of the step sits in one obs scope (its top-level name
        # in the op metadata): noise, neuron, exchange, deliver, stdp
        t = carry["t"]
        if choice.overlap == "double_buffer":
            # flush step t-1's deferred remote gather before this step
            # reads or clears any slot (a delay-1 contribution from t-1
            # lands in exactly the slot delivered now)
            with obs.scope(obs.DELIVER):
                ring0, weights0 = _apply_pending(
                    carry["ring"], carry["weights"], carry["_pending"]
                )
        else:
            ring0, weights0 = carry["ring"], carry["weights"]
        new_pending = None
        with obs.scope(obs.NEURON):
            slot = jnp.mod(t, D)
            i_syn = jax.lax.dynamic_index_in_dim(
                ring0, slot, axis=0, keepdims=False
            )
            if not (choice.split or choice.event):
                # the split/event post-exchange kernels rotate the ring
                # themselves; the other engines clear the delivered slot
                ring = jax.lax.dynamic_update_index_in_dim(
                    ring0, jnp.zeros((ring0.shape[1],), ring0.dtype),
                    slot, axis=0,
                )
        # deterministic noise keyed by (seed, t, permanent neuron id)
        with obs.scope(obs.NOISE):
            if noise_sigma > 0:
                key_t = jax.random.fold_in(base_key, t)
                noise_g = noise_sigma * jax.random.normal(
                    key_t, (n_global,), dtype=jnp.float32
                )
                noise = jnp.take(noise_g, noise_ids, axis=0)
            else:
                noise = jnp.zeros((n_p,), jnp.float32)

        overflow = jnp.zeros((), jnp.int32)
        if choice.split or choice.event:
            # the split/event engines precompute the slot arithmetic into
            # masks so their post-exchange kernel needs no dynamic indexing
            # — the write rows are data, not control flow
            with obs.scope(obs.DELIVER):
                d_rows = jnp.arange(D)
                clear_mask = (d_rows != slot).astype(jnp.float32)
                write_slots = jnp.stack(
                    [jnp.mod(t + d, D) for d in dev.delays]
                )
                write_onehot = (
                    write_slots[:, None] == d_rows[None, :]
                ).astype(jnp.float32)
        if choice.fused:
            # the fused kernels take the summed input current; each kernel
            # that traverses the synapse panels is scoped as delivery
            vtx = carry["vtx_state"]
            with obs.scope(obs.NEURON):
                i_tot = i_syn + noise + vtx[:, LIF_BIAS]
        if choice.engine == "fused":
            # one Pallas launch: LIF advance + spike emission + per-bucket
            # gather; the spike vector never round-trips through HBM
            # between emission and propagation (identity exchange)
            with obs.scope(obs.DELIVER):
                v2, r2, spikes, currents = ops.fused_step(
                    vtx[:, LIF_V], vtx[:, LIF_REF], i_tot,
                    dev.cols, weights0,
                    params=lif_params, backend=backend,
                )
                for i, d in enumerate(dev.delays):
                    ring = ring.at[jnp.mod(t + d, D)].add(currents[i][:n_p])
            new_weights = weights0
            tr_plus, tr_minus = carry["tr_plus"], carry["tr_minus"]
        elif choice.engine == "fused_plastic":
            # the single-kernel step grown by the STDP pass: trace decay
            # rides the LIF advance, and every synapse panel is traversed
            # ONCE — the gather reads the pre-update weights and the
            # plastic-masked update writes back in the same grid step
            # (identity exchange: act == spikes, pre-trace == tr_plus')
            with obs.scope(obs.DELIVER):
                (v2, r2, spikes, tr_plus, tr_minus, currents,
                 new_weights) = ops.fused_step_plastic(
                    vtx[:, LIF_V], vtx[:, LIF_REF], i_tot,
                    carry["tr_plus"], carry["tr_minus"],
                    dev.cols, weights0, dev.plastic,
                    params=lif_params, taus=(tau_plus, tau_minus),
                    stdp=stdp_params, backend=backend,
                )
                for i, d in enumerate(dev.delays):
                    ring = ring.at[jnp.mod(t + d, D)].add(currents[i][:n_p])
            new_weights = tuple(new_weights)
        elif choice.engine == "fused_split_plastic":
            # plastic split step: the pre-exchange kernel advances LIF AND
            # the e-traces, the exchange carries spikes + pre-traces, and
            # the post-exchange kernel folds ring rotate + all gathers +
            # the STDP weight update into one pass over the panels
            with obs.scope(obs.NEURON):
                v2, r2, spikes, tr_plus, tr_minus = ops.fused_pre_exchange(
                    vtx[:, LIF_V], vtx[:, LIF_REF], i_tot,
                    carry["tr_plus"], carry["tr_minus"],
                    params=lif_params, taus=(tau_plus, tau_minus),
                    backend=backend,
                )
            if overlap_on:
                # plastic panels are never split (weights are state):
                # the local pass gathers the full panels against the own
                # slice embedded in a zeroed global vector, issued AFTER
                # the collective in program order but data-independent of
                # it; the remote pass carries the STDP update (elementwise
                # in the full act/pre-trace, so weights stay bit-exact
                # against the serialized engine)
                with obs.scope(obs.DELIVER):
                    act_local = overlap_ctx["embed"](
                        overlap_ctx["local"](spikes)
                    )
                with obs.scope(obs.EXCHANGE):
                    act, pre_trace, overflow = exchange(spikes, tr_plus)
                with obs.scope(obs.DELIVER):
                    ring = ops.fused_post_exchange_local(
                        act_local, ring0, clear_mask, write_onehot,
                        dev.cols, weights0, backend=backend,
                    )
                    if choice.overlap == "double_buffer":
                        new_pending = dict(
                            valid=jnp.ones((), jnp.int32),
                            onehot=write_onehot, act=act,
                            pre_trace=pre_trace, post_trace=tr_minus,
                            post_spike=spikes,
                        )
                        new_weights = weights0  # updated at the t+1 flush
                    else:
                        act_remote = overlap_ctx["mask_remote"](act)
                        ring, new_weights = (
                            ops.fused_post_exchange_remote_plastic(
                                act_remote, act, pre_trace, ring,
                                write_onehot, tr_minus, spikes,
                                dev.cols, weights0, dev.plastic,
                                stdp=stdp_params, backend=backend,
                            )
                        )
                        new_weights = tuple(new_weights)
            else:
                with obs.scope(obs.EXCHANGE):
                    act, pre_trace, overflow = exchange(spikes, tr_plus)
                with obs.scope(obs.DELIVER):
                    ring, new_weights = ops.fused_post_exchange_plastic(
                        act, pre_trace, ring0, clear_mask, write_onehot,
                        tr_minus, spikes, dev.cols, weights0, dev.plastic,
                        stdp=stdp_params, backend=backend,
                    )
                new_weights = tuple(new_weights)
        elif choice.engine == "fused_split" or choice.event:
            # the same fusion split at the exchange: fused {LIF + emit}
            # kernel, the collective, then a fused {ring rotate + every
            # delay-bucket gather} kernel — state arrays and the exchanged
            # activity vector each cross HBM exactly once per step.  The
            # event variants compress the activity to spike ids on-device
            # and gather ONLY synapse row blocks flagged as touched by an
            # active presynaptic id — bit-equal to the dense sweep
            # (fused_event: identity exchange, the activity is the
            # partition's own spike vector)
            with obs.scope(obs.NEURON):
                v2, r2, spikes = ops.fused_pre_exchange(
                    vtx[:, LIF_V], vtx[:, LIF_REF], i_tot,
                    params=lif_params, backend=backend,
                )
            if overlap_on:
                # the collective is issued first in program order; the
                # local gather that follows reads only the own spike
                # vector and the build-time local sub-panels, so XLA's
                # latency hiding runs it under the all-gather (the event
                # variants gather the small local sub-panels densely and
                # compress the remote ids only, so the touched-block flags
                # never wait on the own slice)
                with obs.scope(obs.DELIVER):
                    act_local = overlap_ctx["local"](spikes)
                with obs.scope(obs.EXCHANGE):
                    act, _, overflow = exchange(spikes, carry["tr_plus"])
                with obs.scope(obs.DELIVER):
                    ring = ops.fused_post_exchange_local(
                        act_local, ring0, clear_mask, write_onehot,
                        dev.cols_local, dev.weights_local, backend=backend,
                    )
                    if choice.overlap == "double_buffer":
                        new_pending = dict(
                            valid=jnp.ones((), jnp.int32),
                            onehot=write_onehot, act=act,
                        )
                    elif choice.event:
                        act_remote = overlap_ctx["mask_remote"](act)
                        sel, flags = event_plan.select(act_remote)
                        ring = ops.event_post_exchange(
                            act_remote, ring, jnp.ones((D,), jnp.float32),
                            write_onehot, sel, flags,
                            dev.cols, weights0, backend=backend,
                        )
                    else:
                        ring = ops.fused_post_exchange_remote(
                            act, ring, write_onehot,
                            dev.cols_remote, dev.weights_remote,
                            backend=backend,
                        )
            else:
                with obs.scope(obs.EXCHANGE):
                    act, _, overflow = exchange(spikes, carry["tr_plus"])
                with obs.scope(obs.DELIVER):
                    if choice.event:
                        sel, flags = event_plan.select(act)
                        ring = ops.event_post_exchange(
                            act, ring0, clear_mask, write_onehot, sel,
                            flags, dev.cols, weights0, backend=backend,
                        )
                    else:
                        ring = ops.fused_post_exchange(
                            act, ring0, clear_mask, write_onehot,
                            dev.cols, weights0, backend=backend,
                        )
            new_weights = weights0
            tr_plus, tr_minus = carry["tr_plus"], carry["tr_minus"]
        else:
            with obs.scope(obs.NEURON):
                vtx_state, spikes = neuron_step(
                    dev.vtx_model, carry["vtx_state"], i_syn + noise
                )
                if any_plastic:
                    tr_plus = carry["tr_plus"] * jnp.exp(
                        -dt / tau_plus
                    ).astype(jnp.float32) + spikes
                    tr_minus = carry["tr_minus"] * jnp.exp(
                        -dt / tau_minus
                    ).astype(jnp.float32) + spikes
                else:
                    tr_plus = carry["tr_plus"]
                    tr_minus = carry["tr_minus"]

            with obs.scope(obs.EXCHANGE):
                act, pre_trace, overflow = exchange(spikes, tr_plus)

            weights = weights0
            new_weights = []
            if deliver_bits:
                # one packing of the exchanged activity serves every bucket
                with obs.scope(obs.DELIVER):
                    words = pack_spikes(act)
            for i, d in enumerate(dev.delays):
                with obs.scope(obs.DELIVER), obs.delay_scope(d):
                    pre_fired = None
                    if deliver_bits:
                        cur = ops.spike_gather_bits(
                            words, dev.cols[i], weights[i],
                            backend=deliver_backend, fired=fired_to_stdp,
                        )
                        if fired_to_stdp:
                            cur, pre_fired = cur
                    else:
                        cur = ops.spike_gather(
                            act, dev.cols[i], weights[i],
                            backend=deliver_backend,
                        )
                    if dev.identity_rows[i]:
                        cur_rows = cur[:n_p]
                    else:
                        cur_rows = jax.ops.segment_sum(
                            cur, dev.row_maps[i], num_segments=n_p
                        )
                    wslot = jnp.mod(t + d, D)
                    ring = ring.at[wslot].add(cur_rows)
                if not any_plastic:
                    new_weights.append(weights[i])
                    continue
                with obs.scope(obs.STDP), obs.delay_scope(d):
                    pad_r = dev.cols[i].shape[0] - n_p
                    post_t = jnp.pad(tr_minus, (0, pad_r)) if pad_r \
                        else tr_minus
                    post_s = jnp.pad(spikes, (0, pad_r)) if pad_r else spikes
                    if not dev.identity_rows[i]:
                        post_t = jnp.take(tr_minus, dev.row_maps[i], axis=0)
                        post_s = jnp.take(spikes, dev.row_maps[i], axis=0)
                    stdp_kw = {} if pre_fired is None else {
                        "pre_fired": pre_fired
                    }
                    new_weights.append(
                        ops.stdp_update(
                            weights[i], dev.plastic[i], dev.cols[i],
                            pre_trace, act, post_t, post_s,
                            params=stdp_params, backend=backend, **stdp_kw,
                        )
                    )
            new_weights = tuple(new_weights)

        with obs.scope(obs.NEURON):
            if choice.fused:
                vtx_state = vtx.at[:, LIF_V].set(v2).at[:, LIF_REF].set(r2)
            hist = jax.lax.dynamic_update_index_in_dim(
                carry["hist"], spikes.astype(jnp.uint8), slot, axis=0
            )
            new_carry = dict(
                t=t + 1, vtx_state=vtx_state, ring=ring, hist=hist,
                weights=new_weights, tr_plus=tr_plus, tr_minus=tr_minus,
            )
            if choice.overlap == "double_buffer":
                new_carry["_pending"] = (
                    new_pending if new_pending is not None
                    else _pending_init()
                )
            out = dict(spike_count=jnp.sum(spikes), overflow=overflow)
            if record_raster:
                out["raster"] = spikes.astype(jnp.uint8)
            if record_v:
                out["v_mean"] = jnp.mean(vtx_state[:, 0])
        return new_carry, out

    def _pending_flush(carry):
        """Apply and drop a trailing '_pending' entry (scan epilogue)."""
        carry = dict(carry)
        pend = carry.pop("_pending")
        with obs.scope(obs.DELIVER):
            ring, weights = _apply_pending(
                carry["ring"], carry["weights"], pend
            )
        carry["ring"] = ring
        carry["weights"] = weights
        return carry

    step.engine_choice = choice
    step.pending_init = _pending_init
    step.pending_flush = _pending_flush
    return step


class Simulator:
    """Single-partition (k = 1) step engine — also the bit-exact oracle the
    distributed engine is tested against.

    .. deprecated::
        ``Simulator`` is an internal engine behind :class:`repro.snn.Session`
        (the single supported entry point); importing it from ``repro.snn``
        emits a ``DeprecationWarning``.
    """

    def __init__(self, net: DCSRNetwork,
                 cfg: Optional[SimConfig] = None):
        assert net.k == 1, "Simulator takes k=1 nets; see dist_sim for k>1"
        cfg = SimConfig() if cfg is None else cfg
        self.net = net
        self.cfg = cfg
        self.dt = float(net.meta.get("dt", 0.1))
        self.noise_sigma = float(net.meta.get("noise_sigma", 0.0))
        part = net.parts[0]
        self.ell = build_delay_ell(
            part, net.n, align_k=cfg.align_k, align_rows=cfg.align_rows,
            max_k=cfg.max_k,
        )
        self.d_ring = max(self.ell.max_delay, 1)
        host = partition_device_data(part, net, self.ell)
        self.backend = resolve_sim_backend(cfg.backend)
        self.deliver_backend = resolve_delivery_backend(cfg.backend)
        stdp = (
            dict(net.registry.spec("syn_stdp").params)
            if host.any_plastic
            else None
        )
        models = _models_present(net)
        sel_kw = dict(
            backend=self.backend,
            models_present=models,
            any_plastic=host.any_plastic and stdp is not None,
            identity_exchange=True,
            identity_rows=all(host.identity_rows),
            n_delay_buckets=len(host.delays),
            n_p=host.n_p,
            n_global=net.n,
            fused=cfg.fused,
            event_cap_frac=cfg.event_cap_frac,
        )
        # k=1 is an identity exchange: overlap 'auto' resolves to 'off', an
        # explicit mode is still validated by the selector (raises with
        # fused=True — there is no collective to overlap)
        self.engine_choice: StepEngineChoice = select_step_engine(
            gather="dense" if cfg.gather == "auto" else cfg.gather,
            overlap="off" if cfg.overlap == "auto" else cfg.overlap,
            **sel_kw,
        )
        require_compilable(
            self.backend, self.engine_choice, sel_kw["any_plastic"]
        )
        self.delivery = delivery_path(
            self.engine_choice, self.deliver_backend, net.n
        )
        self.event_capable = _probe_event_capable(**sel_kw)
        # the event schedule is built from the host panels; only what the
        # step reads goes to the device (validity masks stay on the host)
        self._event_plan = (
            EventPlan.build(
                host.cols, host.valid, net.n, self.d_ring,
                event_id_cap(net.n, cfg.event_cap_frac),
                interpret=self.backend != "pallas",
            )
            if self.engine_choice.event else None
        )
        with obs.span(obs.BUILD_PLACE):
            self.dev = jax.device_put(dataclasses.replace(host, valid=[]))
            self._noise_ids = jnp.asarray(part.global_ids, jnp.int32)
        self._step_kw = dict(
            registry=net.registry,
            models_present=models,
            dt=self.dt,
            noise_sigma=self.noise_sigma,
            base_key=jax.random.PRNGKey(cfg.seed),
            d_ring=self.d_ring,
            n_global=net.n,
            backend=self.backend,
            stdp_params=stdp,
            exchange=lambda s, tr: (s, tr, jnp.zeros((), jnp.int32)),
            record_raster=cfg.record_raster,
            record_v=cfg.record_v,
            engine_choice=self.engine_choice,
            deliver_backend=self.deliver_backend,
        )
        # the step over the resident constants, for program analysis
        # (repro.analysis.contracts traces it); run() rebuilds it over
        # traced arguments instead
        self._step = self._core_step(self.dev, self._noise_ids, self._touch())

    def _touch(self):
        return self._event_plan.touch if self._event_plan is not None else []

    def _core_step(self, dev, noise_ids, touch):
        plan = (
            self._event_plan.with_touch(touch)
            if self._event_plan is not None else None
        )
        return make_core_step(
            dev=dev, noise_ids=noise_ids, event_plan=plan, **self._step_kw
        )

    def init_state(self, t0: int = 0) -> Dict:
        n_p = self.dev.n_p
        return dict(
            t=jnp.asarray(t0, jnp.int32),
            vtx_state=self.dev.vtx_state0,
            ring=jnp.zeros((self.d_ring, n_p), jnp.float32),
            hist=jnp.zeros((self.d_ring, n_p), jnp.uint8),
            weights=tuple(self.dev.weights0),
            tr_plus=jnp.zeros((n_p,), jnp.float32),
            tr_minus=jnp.zeros((n_p,), jnp.float32),
        )

    def run(self, state: Dict, steps: int):
        return self._run(
            self.dev, self._noise_ids, self._touch(), state, steps=steps
        )

    @functools.partial(jax.jit, static_argnames=("self", "steps"))
    def _run(self, dev, noise_ids, touch, state: Dict, steps: int):
        step = self._core_step(dev, noise_ids, touch)
        return jax.lax.scan(step, state, None, length=steps)

    # -- dCSR sync (simulation state -> serializable network) -------------
    def state_to_dcsr(self, state: Dict) -> None:
        """Write simulation state back into the dCSR partition in place
        (weights via ELL edge_index, vertex tuples directly).  In place
        means the partition arrays are NOT stable across a later sync —
        callers handing them to a background writer must snapshot-copy
        first (``io.dcsr_binary.snapshot_network``)."""
        part = self.net.parts[0]
        part.vtx_state = np.asarray(state["vtx_state"])
        self.ell.update_bucket_weights(
            [np.asarray(w) for w in state["weights"]]
        )
        self.ell.scatter_weights_back(part)

    def runtime_state(self, state: Dict) -> Dict[int, Dict[str, np.ndarray]]:
        """In-flight runtime arrays (ring/hist/traces) keyed per partition —
        the serialization side-channel next to the dCSR snapshot.  The
        arrays may be zero-copy views of device buffers; the snapshot
        layer copies them before any background write."""
        from .reshard import RUNTIME_KEYS

        return {
            0: {k: np.asarray(state[k]) for k in RUNTIME_KEYS if k in state}
        }
