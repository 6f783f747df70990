"""Public jit'd entry points for the Pallas kernels.

Backend dispatch goes through the registry in ``kernels/dispatch.py``: on
TPU the compiled Pallas kernels run natively; elsewhere ``interpret=True``
executes the same kernel bodies for correctness (this container is
CPU-only — TPU is the target, interpret mode the validator).
``backend="ref"`` routes to the pure-jnp oracles (used by the distributed
simulator under shard_map, where XLA fusion of the oracle is already
optimal on CPU, and by A/B correctness tests).  ``REPRO_BACKEND`` in the
environment overrides the platform default.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from . import ref
from .dispatch import lookup, register
from .event_step import event_post_exchange_pallas
from .keystream import keystream_jnp, keystream_pallas
from .fused_step import (
    fused_lif_step_pallas,
    fused_plastic_step_pallas,
    fused_post_exchange_local_pallas,
    fused_post_exchange_pallas,
    fused_post_exchange_plastic_pallas,
    fused_post_exchange_remote_pallas,
    fused_post_exchange_remote_plastic_pallas,
    fused_pre_exchange_pallas,
)
from .lif_step import lif_step_pallas
from .spike_gather import spike_gather_bits_pallas, spike_gather_pallas
from .stdp_update import stdp_update_pallas


def _register_pallas(op: str) -> Callable:
    """Register one Pallas entry point (which takes ``interpret=``) as both
    the compiled and the interpret-mode backend of ``op``."""

    def deco(fn: Callable) -> Callable:
        register(op, "pallas")(fn)
        register(op, "pallas_interpret")(
            functools.partial(fn, interpret=True)
        )
        return fn

    return deco


# -- builder_keystream (procedural construction word matrix) --------------

@register("builder_keystream", "ref")
def _builder_keystream_ref(seed, stream, rows, j0, n_words, **kw):
    import numpy as np

    return keystream_jnp(
        np.uint32(seed), np.uint32(stream), jnp.asarray(rows),
        np.uint32(j0), int(n_words),
    )


_register_pallas("builder_keystream")(keystream_pallas)


def builder_keystream(
    seed, stream, rows, j0, n_words, *, backend: Optional[str] = None, **kw
):
    """Counter-based keystream words for the procedural network builder:
    a ``(len(rows), n_words)`` uint32 matrix, bit-identical across
    backends (see ``repro.builder.crng.word_matrix``)."""
    return lookup("builder_keystream", backend)(
        seed, stream, rows, j0, n_words, **kw
    )


# -- spike_gather ---------------------------------------------------------

@register("spike_gather", "ref")
def _spike_gather_ref(activity, cols, weights, **kw):
    return ref.spike_gather_ref(activity, cols, weights)


_register_pallas("spike_gather")(spike_gather_pallas)


@register("spike_gather_bits", "ref")
def _spike_gather_bits_ref(words, cols, weights, *, fired=False, **kw):
    return ref.spike_gather_bits_ref(words, cols, weights, fired)


_register_pallas("spike_gather_bits")(spike_gather_bits_pallas)


def spike_gather(
    activity, cols, weights, *, backend: Optional[str] = None, **kw
):
    """Spike delivery over one ELL panel: ``currents[r] = sum_k
    weights[r,k] * activity[cols[r,k]]`` for 0/1 ``activity``, in f32."""
    return lookup("spike_gather", backend)(activity, cols, weights, **kw)


def spike_gather_bits(
    words, cols, weights, *, backend: Optional[str] = None, **kw
):
    """``spike_gather`` from activity packed once by
    ``kernels.spike_gather.pack_spikes``, so a step packs its spikes once
    for every panel.  ``fired=True`` also returns each slot's presynaptic
    spike, (R, K) f32 0/1, for ``stdp_update(..., pre_fired=...)``."""
    return lookup("spike_gather_bits", backend)(words, cols, weights, **kw)


# -- lif_step -------------------------------------------------------------

@register("lif_step", "ref")
def _lif_step_ref(v, refrac, i_syn, *, params, **kw):
    return ref.lif_step_ref(v, refrac, i_syn, **params)


_register_pallas("lif_step")(lif_step_pallas)


def lif_step(v, refrac, i_syn, *, params, backend: Optional[str] = None,
             **kw):
    return lookup("lif_step", backend)(v, refrac, i_syn, params=params, **kw)


# -- stdp_update ----------------------------------------------------------

def _stdp_args(params):
    return dict(
        a_plus=params["a_plus"], a_minus=params["a_minus"],
        w_min=params["w_min"], w_max=params["w_max"],
    )


@register("stdp_update", "ref")
def _stdp_update_ref(
    weights, valid, cols, pre_trace, pre_spike, post_trace, post_spike,
    *, params, pre_fired=None, **kw
):
    return ref.stdp_update_ref(
        weights, valid, cols, pre_trace, pre_spike, post_trace, post_spike,
        **_stdp_args(params), pre_fired=pre_fired,
    )


@_register_pallas("stdp_update")
def _stdp_update_pallas(
    weights, valid, cols, pre_trace, pre_spike, post_trace, post_spike,
    *, params, **kw
):
    return stdp_update_pallas(
        weights, valid, cols, pre_trace, pre_spike, post_trace, post_spike,
        **_stdp_args(params), **kw,
    )


def stdp_update(
    weights, valid, cols, pre_trace, pre_spike, post_trace, post_spike,
    *, params, backend: Optional[str] = None, **kw
):
    return lookup("stdp_update", backend)(
        weights, valid, cols, pre_trace, pre_spike, post_trace, post_spike,
        params=params, **kw,
    )


# -- fused_step (LIF advance + spike emission + gather, one launch) -------

@register("fused_step", "ref")
def _fused_step_ref(v, refrac, i_tot, cols, weights, *, params, **kw):
    return ref.fused_step_ref(v, refrac, i_tot, cols, weights, params=params)


_register_pallas("fused_step")(fused_lif_step_pallas)


def fused_step(
    v, refrac, i_tot, cols, weights, *, params,
    backend: Optional[str] = None, **kw
):
    """Fused LIF step: (v', refrac', spikes, per-bucket currents).

    ``cols``/``weights`` are tuples of per-delay-bucket (R, K_d) panels
    with common R; eligibility rules live in ``dispatch.select_step_engine``.
    """
    return lookup("fused_step", backend)(
        v, refrac, i_tot, tuple(cols), tuple(weights), params=params, **kw
    )


# -- fused_step_plastic (the same, + trace decay + STDP write-back) -------

@register("fused_step_plastic", "ref")
def _fused_step_plastic_ref(
    v, refrac, i_tot, tr_plus, tr_minus, cols, weights, plastic,
    *, params, taus, stdp, **kw
):
    return ref.fused_step_plastic_ref(
        v, refrac, i_tot, tr_plus, tr_minus, cols, weights, plastic,
        params=params, taus=taus, stdp=_stdp_args(stdp),
    )


_register_pallas("fused_step_plastic")(fused_plastic_step_pallas)


def fused_step_plastic(
    v, refrac, i_tot, tr_plus, tr_minus, cols, weights, plastic, *,
    params, taus, stdp, backend: Optional[str] = None, **kw
):
    """Plastic fused LIF step (identity exchange): LIF advance + spike
    emission + trace decay + per-bucket gather + STDP weight update in one
    launch.  Returns ``(v', refrac', spikes, tr_plus', tr_minus',
    currents, new_weights)``.  ``stdp`` carries a_plus/a_minus/w_min/w_max
    (extra keys like the taus are ignored)."""
    return lookup("fused_step_plastic", backend)(
        v, refrac, i_tot, tr_plus, tr_minus,
        tuple(cols), tuple(weights), tuple(plastic),
        params=params, taus=tuple(taus), stdp=stdp, **kw
    )


# -- split engine halves (fused step for non-identity exchanges) ----------

@register("fused_pre_exchange", "ref")
def _fused_pre_exchange_ref(
    v, refrac, i_tot, tr_plus=None, tr_minus=None, *, params, taus=None,
    **kw
):
    return ref.fused_pre_exchange_ref(
        v, refrac, i_tot, tr_plus, tr_minus, params=params, taus=taus
    )


_register_pallas("fused_pre_exchange")(fused_pre_exchange_pallas)


def fused_pre_exchange(
    v, refrac, i_tot, tr_plus=None, tr_minus=None, *, params, taus=None,
    backend: Optional[str] = None, **kw
):
    """Pre-exchange half of the split step: LIF advance + spike emission
    (+ trace decay when traces are passed).  Returns
    ``(v', refrac', spikes[, tr_plus', tr_minus'])``."""
    return lookup("fused_pre_exchange", backend)(
        v, refrac, i_tot, tr_plus, tr_minus, params=params, taus=taus, **kw
    )


@register("fused_post_exchange", "ref")
def _fused_post_exchange_ref(
    act, ring, clear_mask, write_onehot, cols, weights, **kw
):
    return ref.fused_post_exchange_ref(
        act, ring, clear_mask, write_onehot, cols, weights
    )


_register_pallas("fused_post_exchange")(fused_post_exchange_pallas)


def fused_post_exchange(
    act, ring, clear_mask, write_onehot, cols, weights, *,
    backend: Optional[str] = None, **kw
):
    """Post-exchange half of the split step: ring-buffer rotate + every
    delay bucket's ELL gather-accumulate in one pass.  Returns the new
    ``(D, n_p)`` ring."""
    return lookup("fused_post_exchange", backend)(
        act, ring, clear_mask, write_onehot, tuple(cols), tuple(weights),
        **kw
    )


# -- overlapped split engine: local / remote gather passes ----------------

@register("fused_post_exchange_local", "ref")
def _fused_post_exchange_local_ref(
    act_local, ring, clear_mask, write_onehot, cols, weights, **kw
):
    return ref.fused_post_exchange_local_ref(
        act_local, ring, clear_mask, write_onehot, cols, weights
    )


_register_pallas("fused_post_exchange_local")(fused_post_exchange_local_pallas)


def fused_post_exchange_local(
    act_local, ring, clear_mask, write_onehot, cols, weights, *,
    backend: Optional[str] = None, **kw
):
    """Local pass of the overlapped split step: ring rotate + the gathers
    over the build-time *local* sub-panels, fed by the partition's own
    ``(n_p,)`` activity — no collective input, so the caller issues the
    exchange first and this pass runs concurrently with it.  Returns the
    partially updated ``(D, n_p)`` ring (complete it with
    ``fused_post_exchange_remote``)."""
    return lookup("fused_post_exchange_local", backend)(
        act_local, ring, clear_mask, write_onehot, tuple(cols),
        tuple(weights), **kw
    )


@register("fused_post_exchange_remote", "ref")
def _fused_post_exchange_remote_ref(
    act, ring, write_onehot, cols, weights, **kw
):
    return ref.fused_post_exchange_remote_ref(
        act, ring, write_onehot, cols, weights
    )


_register_pallas("fused_post_exchange_remote")(
    fused_post_exchange_remote_pallas
)


def fused_post_exchange_remote(
    act, ring, write_onehot, cols, weights, *,
    backend: Optional[str] = None, **kw
):
    """Remote pass of the overlapped split step: accumulate the gathered
    remote contributions (the *remote* sub-panels reference only
    off-partition presynaptic ids) onto the local pass's already-rotated
    ring.  Returns the completed ``(D, n_p)`` ring."""
    return lookup("fused_post_exchange_remote", backend)(
        act, ring, write_onehot, tuple(cols), tuple(weights), **kw
    )


@register("fused_post_exchange_remote_plastic", "ref")
def _fused_post_exchange_remote_plastic_ref(
    act_remote, act, pre_trace, ring, write_onehot, post_trace,
    post_spike, cols, weights, plastic, *, stdp, **kw
):
    return ref.fused_post_exchange_remote_plastic_ref(
        act_remote, act, pre_trace, ring, write_onehot, post_trace,
        post_spike, cols, weights, plastic, stdp=_stdp_args(stdp),
    )


_register_pallas("fused_post_exchange_remote_plastic")(
    fused_post_exchange_remote_plastic_pallas
)


def fused_post_exchange_remote_plastic(
    act_remote, act, pre_trace, ring, write_onehot, post_trace,
    post_spike, cols, weights, plastic, *, stdp,
    backend: Optional[str] = None, **kw
):
    """Plastic remote pass of the overlapped split step: remote-only ring
    accumulate (``act_remote`` is the exchanged activity with the own
    slice zeroed — plastic panels are never split, their weights are
    state) + the full STDP weight update from the *full* activity and
    pre-trace vectors, one pass over the panels.  Returns
    ``(new_ring, new_weights)``."""
    return lookup("fused_post_exchange_remote_plastic", backend)(
        act_remote, act, pre_trace, ring, write_onehot, post_trace,
        post_spike, tuple(cols), tuple(weights), tuple(plastic),
        stdp=stdp, **kw
    )


@register("event_post_exchange", "ref")
def _event_post_exchange_ref(
    act, ring, clear_mask, write_onehot, sel, flags, cols, weights, **kw
):
    return ref.event_post_exchange_ref(
        act, ring, clear_mask, write_onehot, sel, flags, cols, weights
    )


_register_pallas("event_post_exchange")(event_post_exchange_pallas)


def event_post_exchange(
    act, ring, clear_mask, write_onehot, sel, flags, cols, weights, *,
    backend: Optional[str] = None, **kw
):
    """Event-driven post-exchange half of the split step: ring-buffer
    rotate + the delay-bucket gathers restricted to row blocks flagged by
    ``sel``/``flags`` (from ``kernels.event_step.event_select``).  Returns
    the new ``(D, n_p)`` ring; bit-equal to ``fused_post_exchange`` when
    the flags are conservative (the contract ``event_select`` provides).

    Two skip levels, both exact: with NO block flagged anywhere (a fully
    silent step — the common case at biological activity) the gather
    launch is skipped outright via ``lax.cond`` and the ring only rotates
    (every bucket's contribution is provably zero); otherwise the kernel
    runs and skips *per block* (scalar-prefetch aliasing + ``pl.when``).
    The step-level skip is backend-generic — it is also what the CPU
    interpret proxy actually measures, since interpret mode pays the full
    per-grid-step harness cost regardless of ``pl.when``."""
    fn = lookup("event_post_exchange", backend)
    cols = tuple(cols)
    weights = tuple(weights)

    def _gather(_):
        return fn(
            act, ring, clear_mask, write_onehot, sel, flags, cols,
            weights, **kw
        )

    def _rotate(_):
        return ring * clear_mask.astype(ring.dtype)[:, None]

    return jax.lax.cond(jnp.any(flags > 0), _gather, _rotate, None)


@register("fused_post_exchange_plastic", "ref")
def _fused_post_exchange_plastic_ref(
    act, pre_trace, ring, clear_mask, write_onehot, post_trace,
    post_spike, cols, weights, plastic, *, stdp, **kw
):
    return ref.fused_post_exchange_plastic_ref(
        act, pre_trace, ring, clear_mask, write_onehot, post_trace,
        post_spike, cols, weights, plastic, stdp=_stdp_args(stdp),
    )


_register_pallas("fused_post_exchange_plastic")(
    fused_post_exchange_plastic_pallas
)


def fused_post_exchange_plastic(
    act, pre_trace, ring, clear_mask, write_onehot, post_trace,
    post_spike, cols, weights, plastic, *, stdp,
    backend: Optional[str] = None, **kw
):
    """Plastic post-exchange half of the split step: ring-buffer rotate +
    every delay bucket's gather-accumulate (pre-update weights) + the STDP
    weight update, one pass over the synapse panels.  Returns
    ``(new_ring, new_weights)``.  ``stdp`` carries
    a_plus/a_minus/w_min/w_max (extra keys like the taus are ignored)."""
    return lookup("fused_post_exchange_plastic", backend)(
        act, pre_trace, ring, clear_mask, write_onehot, post_trace,
        post_spike, tuple(cols), tuple(weights), tuple(plastic),
        stdp=stdp, **kw
    )
