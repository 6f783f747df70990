"""Backend dispatch registry for the kernel layer.

Every kernel op registers one implementation per backend; the public entry
points in ``kernels/ops.py`` resolve a backend and dispatch through here.

Backends:
  * ``ref``              — pure-jnp oracles (XLA-fused; the correctness
                           contract and the CPU production path)
  * ``pallas``           — compiled Pallas kernels (TPU)
  * ``pallas_interpret`` — the same kernel bodies in interpret mode
                           (CPU validation of the TPU path)

Resolution order: explicit ``backend=`` argument > ``REPRO_BACKEND``
environment variable > platform default (``pallas`` on TPU, otherwise
``pallas_interpret`` for direct kernel calls; the simulators default to
``ref`` on every platform, and on a TPU their default step delivers spikes
through the compiled ``spike_gather`` kernel over packed spike bits —
the one synapse kernel the TPU compiler accepts, see
``PALLAS_GATHER_LIMIT`` and ``resolve_delivery_backend``).

Separately from the *kernel* backend, ``select_step_engine`` decides the
*step engine*:

  * ``fused``         — single ``pallas_call`` for the whole local step
                        (kernels/fused_step.py); only when the exchange is
                        an identity (k = 1 dense), so the spike vector
                        never leaves VMEM between emission and propagation;
  * ``fused_plastic`` — the same single-kernel step grown by the STDP
                        pass: trace decay rides the LIF advance, and the
                        per-bucket gather-accumulate applies the plastic
                        weight update in the same pass over each synapse
                        panel (one VMEM crossing per panel per step);
  * ``fused_split``   — the fusion *split at the exchange boundary*:
                        a fused pre-exchange kernel (LIF advance + spike
                        emission), the ``parts``-axis collective, then a
                        fused post-exchange kernel (ring-buffer rotate +
                        every delay-bucket gather in one pass).  This is
                        the distributed hot path;
  * ``fused_split_plastic`` — the split engine for plastic partitions:
                        pre-exchange additionally decays+bumps the traces,
                        the exchange carries the pre-trace vector, and the
                        post-exchange kernel folds the STDP weight update
                        into the same panel pass as the gathers;
  * ``fused_event`` / ``fused_split_event`` — the event-driven gather
                        variants of the fused engines: the activity vector
                        is compressed to spike ids on-device and the
                        post-exchange kernel touches only synapse row
                        blocks flagged by a build-time touch bitmap
                        (kernels/event_step.py); bit-equal to the dense
                        sweep, selected by ``gather="event"`` (Session's
                        ``gather="auto"`` swaps on the running spike rate);
  * ``unfused``       — the three-kernel sequence (one launch per op and
                        per delay bucket, plus a separate ``stdp_update``
                        pass for plastic nets); the fallback for
                        heterogeneous / heavy-row-split partitions.

Orthogonally to the engine, the *split* engines carry an **overlap mode**
(``StepEngineChoice.overlap``, from ``SimConfig(overlap=...)``): ``"off"``
serializes pre-exchange → collective → post-exchange (the legacy
bit-path); ``"local"`` decomposes the post-exchange gather into a local
pass over build-time sub-panels of own-partition synapses — issued after
the collective so it runs *under* it — plus a remote pass on the gathered
activity; ``"double_buffer"`` additionally defers the remote pass of step
t to the start of step t+1 (applied before that step's slot delivery, so
the trajectory is bit-exact vs ``"local"``), pipelining the collective
against a whole step of compute.  Overlap needs a collective to hide
(identity exchanges resolve to ``"off"``) and, for plastic partitions,
three VMEM-resident global vectors (``FUSED_SPLIT_OVERLAP_PLASTIC_MAX_N_GLOBAL``).

Fusion (any variant) is only sound for a homogeneous LIF partition with
identity ELL rows; neither the *identity of the exchange* (placement of
the split) nor *plasticity* (selection of the ``*_plastic`` variant) is an
eligibility gate.  The selector encodes those rules so both simulators and
the benchmarks share one policy.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax

BACKENDS = ("ref", "pallas", "pallas_interpret")

_REGISTRY: Dict[Tuple[str, str], Callable] = {}


def register(op: str, backend: str) -> Callable:
    """Decorator: register ``fn`` as the ``backend`` implementation of
    ``op``.  Implementations of one op must share a call signature."""
    assert backend in BACKENDS, f"unknown backend {backend!r}"

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(op, backend)] = fn
        return fn

    return deco


def _ensure_registered() -> None:
    # registrations live in ops.py; importing it is idempotent and avoids
    # an empty registry when dispatch is imported standalone
    from . import ops  # noqa: F401


def backends_for(op: str) -> Tuple[str, ...]:
    _ensure_registered()
    return tuple(
        b for (o, b) in sorted(_REGISTRY) if o == op
    )


@functools.lru_cache(maxsize=None)
def _platform_default() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"


def platform_default() -> str:
    """Env-independent platform default backend (always a Pallas variant:
    compiled on TPU, interpret mode elsewhere).  Public entry point for
    callers that must bypass REPRO_BACKEND, e.g. the fused-vs-unfused
    benchmark, which is meaningless on the ref oracles."""
    return _platform_default()


def resolve_backend(
    backend: Optional[str] = None, *, default: Optional[str] = None
) -> str:
    """Explicit flag > REPRO_BACKEND env var > ``default`` (falls back to
    the platform default: pallas on TPU, interpret mode elsewhere)."""
    if backend is not None:
        return backend
    env = os.environ.get("REPRO_BACKEND")
    if env:
        return env
    return default if default is not None else _platform_default()


# What the TPU compiler refuses in the synapse kernels that gather
# activity by presynaptic id from a VMEM-resident vector,
# ``jnp.take(act, cols)``: stdp_update and every fused_* and event_*
# kernel.  Compiled for a TPU v5e with jax/jaxlib 0.9.0, Mosaic lowers
# only 2-D gathers whose indices have the source's shape, so these raise
# ``NotImplementedError: Only 2D gather is supported`` and run only in
# interpret mode.  The spike delivery kernel (``spike_gather``) is written
# to that rule: it reads the spikes packed into (S, 128) words and gathers
# along the lanes of one words row at a time, so it compiles.  The compile
# tests (tests/test_tpu_compile.py) pin both.
PALLAS_GATHER_LIMIT = (
    "the TPU compiler (Mosaic, jax 0.9) lowers no in-kernel 1-D gather: "
    "jnp.take(activity, cols) from a VMEM-resident vector raises "
    "'NotImplementedError: Only 2D gather is supported', and stdp_update "
    "and every fused and event kernel gather that way; only the spike "
    "delivery kernel (spike_gather, a lane gather over packed spike bits) "
    "compiles"
)

# Why the simulators default to 'ref' on every platform (shown by
# Session.describe()): the XLA-compiled step composes the jnp oracles,
# apart from the one synapse kernel the TPU compiler accepts
# (PALLAS_GATHER_LIMIT), which delivers the spikes of the default step on
# a TPU; on CPU XLA's fusion of the oracles is the fast path.
SIM_BACKEND_REASON = (
    "simulators default to 'ref' (the XLA-compiled step) on every "
    "platform; on TPU that step delivers spikes through the compiled "
    "Pallas kernel over packed spike bits, and the other synapse kernels "
    "do not compile (" + PALLAS_GATHER_LIMIT + "); on CPU XLA's fusion "
    "of the oracles is the fast path"
)


def resolve_sim_backend(backend: Optional[str] = None) -> str:
    """Backend resolution for the simulators: explicit flag >
    ``REPRO_BACKEND`` > ``ref`` on every platform, TPU included — see
    ``SIM_BACKEND_REASON``.  Direct kernel calls keep the platform default
    (``pallas`` on TPU, interpret mode elsewhere)."""
    return resolve_backend(backend, default="ref")


def resolve_delivery_backend(backend: Optional[str] = None) -> str:
    """Backend of the unfused step's spike delivery (``spike_gather``):
    the simulators' backend, except that the default step on a TPU (no
    explicit backend, no ``REPRO_BACKEND``) delivers through the compiled
    Pallas kernel.  An explicit ``'ref'`` keeps the all-XLA oracle step
    on every platform."""
    if (backend is None and not os.environ.get("REPRO_BACKEND")
            and _platform_default() == "pallas"):
        return "pallas"
    return resolve_sim_backend(backend)


def require_compilable(
    backend: str, choice: "StepEngineChoice", plastic: bool
) -> None:
    """Refuse, at engine construction, a compiled-Pallas step the TPU
    compiler cannot build (``PALLAS_GATHER_LIMIT``): every fused and
    event engine, and the unfused engine's ``stdp_update`` pass.  With
    ``backend='pallas'`` only the unfused step of a partition without
    STDP compiles.  Raised, never swapped for another backend."""
    if backend != "pallas":
        return
    if choice.fused:
        blocked = f"the {choice.engine!r} step engine"
    elif plastic:
        blocked = "the unfused step's stdp_update pass"
    else:
        return
    raise ValueError(
        f"backend='pallas' cannot run {blocked}: {PALLAS_GATHER_LIMIT}; "
        "use the default backend (the XLA-compiled step, spikes "
        "delivered by the Pallas kernel on a TPU), fused=False without "
        "STDP, or 'pallas_interpret' (CPU validation of the kernels)"
    )


def lookup(op: str, backend: Optional[str] = None) -> Callable:
    _ensure_registered()
    b = resolve_backend(backend)
    try:
        return _REGISTRY[(op, b)]
    except KeyError:
        raise KeyError(
            f"no implementation of kernel op {op!r} for backend {b!r}; "
            f"available: {backends_for(op) or '(none)'}"
        ) from None


# -- step-engine selection (fused vs unfused) -----------------------------


STEP_ENGINES = (
    "fused", "fused_plastic", "fused_split", "fused_split_plastic",
    "fused_event", "fused_split_event",
    "unfused",
)


# exchange/compute overlap modes of the split engines ('auto' is resolved
# by the simulators before selection: 'local' on the compiled pallas
# backend — where the collective has real latency to hide — 'off' elsewhere)
OVERLAP_MODES = ("off", "local", "double_buffer")


@dataclasses.dataclass(frozen=True)
class StepEngineChoice:
    engine: str  # one of STEP_ENGINES
    reason: str
    # resolved overlap mode (one of OVERLAP_MODES); always "off" for
    # non-split engines — there is no collective to overlap
    overlap: str = "off"

    @property
    def fused(self) -> bool:
        """True for any fused variant (single-kernel or split, plastic or
        not)."""
        return self.engine != "unfused"

    @property
    def split(self) -> bool:
        return self.engine in (
            "fused_split", "fused_split_plastic", "fused_split_event",
        )

    @property
    def plastic(self) -> bool:
        """True for the variants that fold the STDP pass into the fused
        step."""
        return self.engine in ("fused_plastic", "fused_split_plastic")

    @property
    def event(self) -> bool:
        """True for the event-driven gather variants (panel traversal
        restricted to row blocks with active presynaptic spikes)."""
        return self.engine in ("fused_event", "fused_split_event")


# the fused kernel keeps six full-length f32 state vectors (v/refrac/i_tot
# in, v/refrac/spike out) VMEM-resident alongside the streamed panels;
# partitions whose vectors outgrow this budget fall back to the unfused
# engine, which tiles state into (rows, 128) panels
_FUSED_VECTOR_VMEM_BUDGET = 6 * 1024 * 1024
FUSED_MAX_N_P = _FUSED_VECTOR_VMEM_BUDGET // (6 * 4)
# the plastic single-kernel variant additionally keeps the two e-trace
# vectors resident, in and out (ten vectors total), so its n_p cap is
# proportionally tighter
FUSED_PLASTIC_MAX_N_P = _FUSED_VECTOR_VMEM_BUDGET // (10 * 4)
# the split post-exchange kernel pins the *global* activity vector
# (n_global f32) whole in VMEM, like spike_gather; larger nets fall back
FUSED_SPLIT_MAX_N_GLOBAL = _FUSED_VECTOR_VMEM_BUDGET // 4
# the plastic split variant pins the exchanged pre-trace vector alongside
# the activity vector (two n_global f32 panels), halving the budget
FUSED_SPLIT_PLASTIC_MAX_N_GLOBAL = _FUSED_VECTOR_VMEM_BUDGET // (2 * 4)
# the overlapped plastic remote pass pins THREE global vectors whole in
# VMEM (remote-masked activity + full activity + pre-trace) — plastic
# panels are never split, so both overlap passes traverse the full panels
FUSED_SPLIT_OVERLAP_PLASTIC_MAX_N_GLOBAL = (
    _FUSED_VECTOR_VMEM_BUDGET // (3 * 4)
)

# -- event-driven gather (fused_event / fused_split_event) ----------------
# the per-step compressed spike-id buffer (``event_select``) rides the
# pallas_call as a scalar-prefetch input; cap its int32 footprint so the
# schedule never crowds the panel/state budget above
EVENT_IDS_VMEM_BUDGET = 1 * 1024 * 1024
EVENT_MAX_IDS = EVENT_IDS_VMEM_BUDGET // 4
# Session's activity-adaptive dispatcher (SimConfig(gather="auto")) swaps
# to the event engine below this running mean spike rate and back to the
# dense sweep above it.  Calibrated from the committed benchmark activity
# sweep (benchmarks/spike_throughput.py --mode event, numbers in
# benchmarks/baseline.json): on the interpret-mode CPU proxy the event
# path wins ~2x at 0.035% activity and loses ~0.75x by 0.5%, so the
# crossover sits between those points.  On TPU the skipped HBM panel
# fetches (not just skipped arithmetic) move the real crossover higher;
# this constant is the conservative CPU-proxy value.
EVENT_ACTIVITY_THRESHOLD = 0.002


# -- per-engine contracts (machine-checked by repro.analysis.contracts) ---
#
# Each engine declares the properties the analyzer verifies against the
# *lowered program* (jaxpr + post-SPMD HLO) for every eligible selector
# configuration: the exact number of parts-axis collectives one step may
# issue (keyed by exchange flavour), the collective kinds allowed inside
# the scan body, and how many full-length f32 vectors the engine keeps
# VMEM-resident per step — the same counts the budget constants above
# divide by, so the selector's eligibility promises are checked against
# what XLA actually built.  Declaring a new engine without a contract is
# itself a checker failure (see docs/ANALYSIS.md).


@dataclasses.dataclass(frozen=True)
class EngineContract:
    """The machine-checked promises of one step engine.

    ``collectives_per_step`` maps an exchange key — ``identity`` /
    ``dense`` / ``index``, with ``+plastic`` appended when the exchange
    also carries the pre-trace vector — to the EXACT number of
    parts-axis collectives a single scan step issues.  A key absent from
    the map means that exchange flavour is not a valid configuration of
    the engine, and the checker fails if the selector ever produces it.

    ``resident_np_vectors`` / ``resident_nglobal_vectors`` count the
    full-length f32 vectors ((n_p,) state and (n_global,) exchanged
    panels) the engine pins in VMEM per step — multiplied by the actual
    widths of the lowered program and checked against
    ``_FUSED_VECTOR_VMEM_BUDGET``, exactly the arithmetic behind
    ``FUSED_MAX_N_P`` / ``FUSED_PLASTIC_MAX_N_P`` /
    ``FUSED_SPLIT_*_MAX_N_GLOBAL``.  ``overlap_nglobal_vectors``
    replaces the n_global count when an overlap mode is active (the
    plastic remote pass pins three global vectors).

    ``id_buffer_budget`` bounds the int32 compressed spike-id buffer of
    the event engines (``EVENT_IDS_VMEM_BUDGET``)."""

    engine: str
    collectives_per_step: Dict[str, int]
    allowed_collectives: Tuple[str, ...] = ("all_gather",)
    resident_np_vectors: int = 0
    resident_nglobal_vectors: int = 0
    overlap_nglobal_vectors: Optional[int] = None
    id_buffer_budget: Optional[int] = None


ENGINE_CONTRACTS: Dict[str, EngineContract] = {
    c.engine: c
    for c in (
        EngineContract(
            "fused",
            {"identity": 0},
            resident_np_vectors=6,
        ),
        EngineContract(
            "fused_plastic",
            {"identity+plastic": 0},
            resident_np_vectors=10,
        ),
        EngineContract(
            "fused_event",
            {"identity": 0},
            resident_np_vectors=6,
            id_buffer_budget=EVENT_IDS_VMEM_BUDGET,
        ),
        EngineContract(
            "fused_split",
            {"dense": 1, "index": 1},
            resident_np_vectors=6,
            resident_nglobal_vectors=1,
        ),
        EngineContract(
            "fused_split_plastic",
            # dense rides spikes+traces on ONE stacked all_gather; the
            # index exchange needs a second collective for the dense
            # real-valued pre-trace vector
            {"dense+plastic": 1, "index+plastic": 2},
            resident_np_vectors=10,
            resident_nglobal_vectors=2,
            overlap_nglobal_vectors=3,
        ),
        EngineContract(
            "fused_split_event",
            {"dense": 1, "index": 1},
            resident_np_vectors=6,
            resident_nglobal_vectors=1,
            id_buffer_budget=EVENT_IDS_VMEM_BUDGET,
        ),
        EngineContract(
            # the unfused fallback tiles state into panels — no
            # VMEM-residency promise — but its exchange discipline is
            # identical to the split engines'
            "unfused",
            {
                "identity": 0, "identity+plastic": 0,
                "dense": 1, "index": 1,
                "dense+plastic": 1, "index+plastic": 2,
            },
        ),
    )
}
assert set(ENGINE_CONTRACTS) == set(STEP_ENGINES), (
    "every step engine must declare an EngineContract "
    "(see docs/ANALYSIS.md)"
)


def event_id_cap(n_global: int, cap_frac: float) -> int:
    """Effective compressed spike-id capacity of the event engines — the
    single source of the formula (SimConfig(event_cap_frac=...) is a
    fraction of the activity-vector width, floored so tiny nets keep a
    usable buffer).  More active ids than this in one step degrade that
    step to the dense sweep (all blocks flagged) — exact, just not fast."""
    return max(int(cap_frac * n_global), 32)


def event_gather_blocker(
    any_plastic: bool, n_global: int, event_cap_frac: float
) -> Optional[str]:
    """Why the event-driven gather cannot serve this partition (None when
    it can).  Separate from ``_fusion_blocker``: an event-ineligible
    partition still takes the *dense* fused engine — these rules only
    gate the gather flavour."""
    if any_plastic:
        return (
            "plastic nets stay dense for now: the STDP pass must visit "
            "every synapse panel every step to apply trace-decay weight "
            "updates, so skipping untouched panels would skip learning"
        )
    cap = event_id_cap(n_global, event_cap_frac)
    if cap > EVENT_MAX_IDS:
        return (
            f"compressed spike-id buffer ({cap} ids = {4 * cap} bytes at "
            f"event_cap_frac={event_cap_frac}) exceeds the event-gather "
            f"VMEM budget ({EVENT_IDS_VMEM_BUDGET} bytes); lower "
            "SimConfig(event_cap_frac=...) or use gather='dense'"
        )
    return None


def _fusion_blocker(
    models_present: Sequence[str],
    any_plastic: bool,
    identity_exchange: bool,
    identity_rows: bool,
    n_delay_buckets: int,
    n_p: int,
    n_global: Optional[int],
) -> Optional[str]:
    if tuple(models_present) != ("lif",):
        return (
            f"heterogeneous vertex models {tuple(models_present)} "
            "(fused step is LIF-only)"
        )
    if not identity_rows:
        return "heavy-row-split ELL needs the segment-sum re-reduction"
    if n_delay_buckets < 1:
        return "no synapses to propagate"
    max_n_p = FUSED_PLASTIC_MAX_N_P if any_plastic else FUSED_MAX_N_P
    if n_p > max_n_p:
        what = "state+trace" if any_plastic else "state"
        return (
            f"partition too large ({n_p} > {max_n_p} neurons) for "
            f"VMEM-resident fused {what} vectors"
        )
    max_n_global = (
        FUSED_SPLIT_PLASTIC_MAX_N_GLOBAL if any_plastic
        else FUSED_SPLIT_MAX_N_GLOBAL
    )
    if (
        not identity_exchange
        and n_global is not None
        and n_global > max_n_global
    ):
        what = (
            "activity + pre-trace vectors" if any_plastic
            else "activity vector"
        )
        return (
            f"network too large ({n_global} > {max_n_global} "
            f"neurons) for the VMEM-resident exchanged {what} of "
            "the split post-exchange kernel"
        )
    return None


def select_step_engine(
    *,
    backend: str,
    models_present: Sequence[str],
    any_plastic: bool,
    identity_exchange: bool,
    identity_rows: bool,
    n_delay_buckets: int,
    n_p: int,
    n_global: Optional[int] = None,
    fused: Optional[bool] = None,
    gather: str = "dense",
    event_cap_frac: float = 0.05,
    overlap: str = "off",
) -> StepEngineChoice:
    """Pick one of ``STEP_ENGINES`` for a partition's step.

    ``identity_exchange`` is a *placement* input, not an eligibility gate:
    identity exchanges (k = 1 dense) take the single-kernel ``fused``
    engine, every other exchange (distributed dense/index collectives, a
    k = 1 capacity-truncating index exchange) takes ``fused_split`` — the
    same fusion split at the exchange so the collective stays in place.
    ``any_plastic`` likewise only selects the ``*_plastic`` variant (which
    folds the STDP pass into the same panel traversal); it is no longer an
    unfused gate — only the tighter trace-vector VMEM budgets can block a
    plastic partition.

    ``fused=None`` (auto) fuses whenever the partition is eligible and the
    backend runs Pallas kernels; ``fused=True`` demands fusion (raises if
    the partition is ineligible); ``fused=False`` disables it.

    ``gather`` picks the panel-traversal flavour of the fused engines:
    ``"dense"`` sweeps every synapse panel every step, ``"event"`` takes
    the event-driven variants (``fused_event`` / ``fused_split_event``)
    that touch only row blocks with active presynaptic spikes.  The
    ``"auto"`` SimConfig value never reaches here — Session resolves it
    per chunk from the running spike rate (EVENT_ACTIVITY_THRESHOLD).
    An event-ineligible partition (``event_gather_blocker``: plastic, or
    a compressed id buffer past its VMEM budget) falls back to the
    *dense* fused variant with the reason attached — unless
    ``fused=True`` demanded the event engine, which raises.

    ``overlap`` sets the exchange/compute overlap mode of the *split*
    engines (``"off"`` | ``"local"`` | ``"double_buffer"`` — SimConfig's
    ``"auto"`` is resolved by the simulators before selection).  Overlap
    needs a collective to hide, so identity exchanges resolve to
    ``"off"``; a plastic partition whose three VMEM-resident global
    vectors exceed ``FUSED_SPLIT_OVERLAP_PLASTIC_MAX_N_GLOBAL`` likewise
    falls back, with the reason attached — unless ``fused=True`` demanded
    overlap, which raises.  The resolved mode is returned as
    ``StepEngineChoice.overlap``.
    """
    if gather not in ("dense", "event"):
        raise ValueError(
            f"select_step_engine(gather={gather!r}): expected 'dense' or "
            "'event' ('auto' is resolved by Session before selection)"
        )
    if overlap not in OVERLAP_MODES:
        raise ValueError(
            f"select_step_engine(overlap={overlap!r}): expected one of "
            f"{OVERLAP_MODES} ('auto' is resolved by the simulators "
            "before selection)"
        )
    if fused is False:
        return StepEngineChoice("unfused", "disabled by config")
    blocker = _fusion_blocker(
        models_present, any_plastic, identity_exchange, identity_rows,
        n_delay_buckets, n_p, n_global,
    )
    if blocker is not None:
        if fused is True:
            raise ValueError(f"fused step engine requested but: {blocker}")
        return StepEngineChoice("unfused", blocker)
    target = "fused" if identity_exchange else "fused_split"
    if any_plastic:
        target += "_plastic"
    placement = (
        "identity exchange" if identity_exchange
        else "split at the exchange collective"
    )
    if any_plastic:
        placement += ", STDP fused into the panel pass"
    if gather == "event":
        eb = event_gather_blocker(
            any_plastic,
            n_global if n_global is not None else n_p,
            event_cap_frac,
        )
        if eb is None:
            target = (
                "fused_event" if identity_exchange else "fused_split_event"
            )
            placement += ", event-driven gather"
        elif fused is True:
            raise ValueError(f"event-driven gather requested but: {eb}")
        else:
            placement += f" (event gather unavailable: {eb})"
    overlap_resolved = "off"
    if overlap != "off":
        ob = None
        if identity_exchange:
            ob = "identity exchange has no collective to overlap"
        elif (
            any_plastic
            and n_global is not None
            and n_global > FUSED_SPLIT_OVERLAP_PLASTIC_MAX_N_GLOBAL
        ):
            ob = (
                f"network too large ({n_global} > "
                f"{FUSED_SPLIT_OVERLAP_PLASTIC_MAX_N_GLOBAL} neurons) for "
                "the three VMEM-resident global vectors of the plastic "
                "remote pass"
            )
        if ob is None:
            overlap_resolved = overlap
            placement += f", {overlap} exchange/compute overlap"
        elif fused is True:
            raise ValueError(f"overlap={overlap!r} requested but: {ob}")
        else:
            placement += f" (overlap unavailable: {ob})"
    if fused is True:
        return StepEngineChoice(
            target, f"forced by config ({placement})", overlap_resolved
        )
    if backend in ("pallas", "pallas_interpret"):
        return StepEngineChoice(
            target, f"auto: {backend} backend ({placement})",
            overlap_resolved,
        )
    return StepEngineChoice(
        "unfused",
        "auto: 'ref' backend composes pure-jnp oracles (XLA-fused)",
    )
