"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematical definition the corresponding kernel must
match (tests sweep shapes/dtypes and assert_allclose against these).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp


def spike_gather_ref(
    activity: jnp.ndarray,  # (n,) global activity (spikes as 0/1 floats)
    cols: jnp.ndarray,  # (R, K) int32 global source ids (0 on padding)
    weights: jnp.ndarray,  # (R, K) weights (0 on padding)
) -> jnp.ndarray:  # (R,)
    """currents[r] = sum_k weights[r,k] * activity[cols[r,k]].

    Padding slots carry weight 0, so no mask is needed for the forward
    accumulation (a deliberate layout invariant of repro.core.ell).
    Accumulation is in f32 regardless of weight dtype — the contract the
    Pallas kernels implement (low-precision partial sums lose ~1% at
    realistic in-degrees); the result stays f32 for the ring buffers.
    """
    vals = jnp.take(activity, cols, axis=0).astype(jnp.float32)
    return jnp.sum(weights.astype(jnp.float32) * vals, axis=-1)


def spike_gather_bits_ref(
    words: jnp.ndarray,  # (S, 128) int32: the activity, from pack_spikes
    cols: jnp.ndarray,  # (R, K) int32 global source ids (0 on padding)
    weights: jnp.ndarray,  # (R, K) weights (0 on padding)
    fired: bool = False,
):
    """``spike_gather_ref`` with the activity packed into bits: neuron i
    fired iff bit ``i & 31`` of word ``i >> 5`` is set.  With ``fired``
    also returns each slot's presynaptic spike, (R, K) f32 0/1."""
    word = jnp.take(words.reshape(-1), cols >> 5, axis=0)
    hit = ((word >> (cols & 31)) & 1) != 0
    cur = jnp.sum(jnp.where(hit, weights.astype(jnp.float32), 0.0), axis=-1)
    return (cur, hit.astype(jnp.float32)) if fired else cur


def lif_step_ref(
    v: jnp.ndarray,  # (R,) membrane potential
    refrac: jnp.ndarray,  # (R,) remaining refractory steps (float, >= 0)
    i_syn: jnp.ndarray,  # (R,) synaptic current this step
    *,
    dt: float,
    tau_m: float,
    v_rest: float,
    v_reset: float,
    v_thresh: float,
    t_ref: float,
    r_m: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Leaky integrate-and-fire, exact exponential-Euler update.

    During refractoriness the membrane is clamped to v_reset and input is
    discarded; the counter then decrements.  Returns (v', refrac', spike).
    """
    decay = jnp.exp(-dt / tau_m).astype(v.dtype)
    active = refrac <= 0
    v_int = v_rest + (v - v_rest) * decay + r_m * i_syn * (1 - decay)
    v_new = jnp.where(active, v_int, v_reset)
    spike = (v_new >= v_thresh) & active
    ref_steps = jnp.asarray(round(t_ref / dt), dtype=refrac.dtype)
    refrac_new = jnp.where(spike, ref_steps, jnp.maximum(refrac - 1, 0))
    v_out = jnp.where(spike, v_reset, v_new)
    return v_out, refrac_new, spike.astype(v.dtype)


def alif_step_ref(
    v, refrac, adapt, i_syn, *, dt, tau_m, v_rest, v_reset, v_thresh,
    t_ref, r_m, tau_adapt, beta,
):
    """Adaptive LIF: threshold rises by beta per spike, decays with
    tau_adapt.  Returns (v', refrac', adapt', spike)."""
    decay = jnp.exp(-dt / tau_m).astype(v.dtype)
    a_decay = jnp.exp(-dt / tau_adapt).astype(v.dtype)
    active = refrac <= 0
    v_int = v_rest + (v - v_rest) * decay + r_m * i_syn * (1 - decay)
    v_new = jnp.where(active, v_int, v_reset)
    thresh = v_thresh + adapt
    spike = (v_new >= thresh) & active
    ref_steps = jnp.asarray(round(t_ref / dt), dtype=refrac.dtype)
    refrac_new = jnp.where(spike, ref_steps, jnp.maximum(refrac - 1, 0))
    adapt_new = adapt * a_decay + beta * spike.astype(v.dtype)
    v_out = jnp.where(spike, v_reset, v_new)
    return v_out, refrac_new, adapt_new, spike.astype(v.dtype)


def izhikevich_step_ref(v, u, i_syn, *, dt, a, b, c, d):
    """Izhikevich (2003) two-variable model, forward Euler.
    Returns (v', u', spike)."""
    spike = v >= 30.0
    v0 = jnp.where(spike, c, v)
    u0 = jnp.where(spike, u + d, u)
    dv = 0.04 * v0 * v0 + 5.0 * v0 + 140.0 - u0 + i_syn
    du = a * (b * v0 - u0)
    return v0 + dt * dv, u0 + dt * du, spike.astype(v.dtype)


def stdp_update_ref(
    weights: jnp.ndarray,  # (R, K)
    valid: jnp.ndarray,  # (R, K) 0/1 float mask
    cols: jnp.ndarray,  # (R, K) int32 global pre ids
    pre_trace: jnp.ndarray,  # (n,) global presynaptic traces
    pre_spike: jnp.ndarray,  # (n,) global spike vector this step
    post_trace: jnp.ndarray,  # (R,) local postsynaptic traces
    post_spike: jnp.ndarray,  # (R,) local spikes this step
    *,
    a_plus: float,
    a_minus: float,
    w_min: float,
    w_max: float,
    pre_fired: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Trace-based pair STDP (all-to-all interaction):

      on post spike: w += a_plus  * pre_trace[col]   (potentiation)
      on pre  spike: w -= a_minus * post_trace[row]  (depression)

    applied simultaneously per step; weights clipped to [w_min, w_max].
    Slots with ``valid == 0`` (padding *or* non-plastic synapses) keep their
    original weight unchanged.  ``pre_fired``, the (R, K) presynaptic spike
    of each slot as the delivery kernel read it, replaces the gather of
    ``pre_spike``.
    """
    pre_t = jnp.take(pre_trace, cols, axis=0)
    pre_s = (
        jnp.take(pre_spike, cols, axis=0) if pre_fired is None else pre_fired
    )
    dw = (
        a_plus * pre_t * post_spike[:, None]
        - a_minus * post_trace[:, None] * pre_s
    )
    w = jnp.clip(weights + dw, w_min, w_max)
    return jnp.where(valid > 0, w, weights)


def trace_decay_ref(trace, spike, *, dt, tau):
    """x' = x * exp(-dt/tau) + spike   (per-neuron e-trace)."""
    return trace * jnp.exp(-dt / tau).astype(trace.dtype) + spike


def fused_pre_exchange_ref(
    v: jnp.ndarray,  # (n_p,)
    refrac: jnp.ndarray,  # (n_p,)
    i_tot: jnp.ndarray,  # (n_p,) total input current (syn + bias + noise)
    tr_plus: jnp.ndarray = None,  # (n_p,) pre-synaptic e-trace (optional)
    tr_minus: jnp.ndarray = None,  # (n_p,) post-synaptic e-trace (optional)
    *,
    params: Dict[str, float],
    taus: Tuple[float, float] = None,  # (tau_plus, tau_minus) with traces
):
    """Oracle for the fused pre-exchange kernel: everything that happens
    *before* the spike exchange — LIF state advance + spike emission, plus
    the trace decay+bump when traces are passed (the hook for fusing the
    STDP pass later).  Returns ``(v', refrac', spikes)`` or
    ``(v', refrac', spikes, tr_plus', tr_minus')``.
    """
    v2, r2, s = lif_step_ref(v, refrac, i_tot, **params)
    if tr_plus is None:
        return v2, r2, s
    dt = params["dt"]
    return (
        v2, r2, s,
        trace_decay_ref(tr_plus, s, dt=dt, tau=taus[0]),
        trace_decay_ref(tr_minus, s, dt=dt, tau=taus[1]),
    )


def fused_post_exchange_ref(
    act: jnp.ndarray,  # (n,) exchanged global activity
    ring: jnp.ndarray,  # (D, n_p) future-current ring buffer (uncleared)
    clear_mask: jnp.ndarray,  # (D,) 0 at the just-delivered slot, 1 else
    write_onehot: jnp.ndarray,  # (nd, D) one-hot of (t + d) % D per bucket
    cols,  # per delay bucket (R, K_d) int32, global ids
    weights,  # per delay bucket (R, K_d)
) -> jnp.ndarray:
    """Oracle for the fused post-exchange kernel: everything *after* the
    spike exchange — ring-buffer rotate (clear the delivered slot) + every
    delay bucket's ELL gather-accumulate in one pass over the activity
    vector.  Slot arithmetic is precomputed by the caller into masks so the
    kernel stays free of dynamic indexing.  Returns the new ring.
    """
    n_p = ring.shape[1]
    new_ring = ring * clear_mask[:, None]
    for i, (c, w) in enumerate(zip(cols, weights)):
        cur = spike_gather_ref(act, c, w)[:n_p]
        new_ring = new_ring + write_onehot[i][:, None] * cur[None, :]
    return new_ring


def event_post_exchange_ref(
    act: jnp.ndarray,  # (n,) exchanged global activity
    ring: jnp.ndarray,  # (D, n_p) future-current ring buffer (uncleared)
    clear_mask: jnp.ndarray,  # (D,) 0 at the just-delivered slot, 1 else
    write_onehot: jnp.ndarray,  # (nd, D) one-hot of (t + d) % D per bucket
    sel: jnp.ndarray,  # (nd, num_blocks) int32 block selectors (unused)
    flags: jnp.ndarray,  # (nd, num_blocks) int32 0/1 block activity
    cols,  # per delay bucket (R, K_d) int32, global ids
    weights,  # per delay bucket (R, K_d)
) -> jnp.ndarray:
    """Oracle for the event-driven post-exchange kernel: the dense
    post-exchange gather with each bucket's row blocks *masked by its
    flags* — the defined semantics of the kernel's block skipping.  With
    conservative flags (``event_select``: every block holding a valid
    active synapse is flagged) the mask is a mathematical no-op and the
    result equals ``fused_post_exchange_ref``; a flag-computation bug
    surfaces as a mismatch against the dense oracle.  ``sel`` is a fetch
    schedule (which HBM block each grid step reads), not semantics — the
    oracle ignores it.
    """
    del sel
    n_p = ring.shape[1]
    new_ring = ring * clear_mask[:, None]
    for i, (c, w) in enumerate(zip(cols, weights)):
        nb = flags.shape[1]
        block_r = c.shape[0] // nb
        row_mask = jnp.repeat(
            flags[i].astype(jnp.float32), block_r, total_repeat_length=c.shape[0]
        )
        cur = (spike_gather_ref(act, c, w) * row_mask)[:n_p]
        new_ring = new_ring + write_onehot[i][:, None] * cur[None, :]
    return new_ring


def fused_post_exchange_local_ref(
    act_local: jnp.ndarray,  # (n_p,) own-partition activity (pre-collective)
    ring: jnp.ndarray,  # (D, n_p) future-current ring buffer (uncleared)
    clear_mask: jnp.ndarray,  # (D,) 0 at the just-delivered slot, 1 else
    write_onehot: jnp.ndarray,  # (nd, D) one-hot of (t + d) % D per bucket
    cols,  # per delay bucket (R, K_l) int32, LOCAL ids (< n_p)
    weights,  # per delay bucket (R, K_l)
) -> jnp.ndarray:
    """Oracle for the *local pass* of the overlapped split step: the ring
    rotate plus the gather restricted to the build-time local sub-panels
    (synapses whose presynaptic neuron lives on this partition).  The
    activity is the partition's own spike vector — available before any
    collective, so this pass runs concurrently with the spike exchange.
    Arithmetic is the plain post-exchange gather over the sub-panels.
    """
    return fused_post_exchange_ref(
        act_local, ring, clear_mask, write_onehot, cols, weights
    )


def fused_post_exchange_remote_ref(
    act: jnp.ndarray,  # (n,) exchanged global activity
    ring: jnp.ndarray,  # (D, n_p) ring ALREADY rotated by the local pass
    write_onehot: jnp.ndarray,  # (nd, D) one-hot of (t + d) % D per bucket
    cols,  # per delay bucket (R, K_r) int32, global ids (remote only)
    weights,  # per delay bucket (R, K_r)
) -> jnp.ndarray:
    """Oracle for the *remote pass* of the overlapped split step: add the
    gathered remote contributions on top of the local pass's ring.  No
    clear — the local pass already rotated the delivered slot; the remote
    sub-panels reference only off-partition presynaptic ids, so the full
    exchanged vector can be gathered directly.
    """
    ones = jnp.ones((ring.shape[0],), jnp.float32)
    return fused_post_exchange_ref(
        act, ring, ones, write_onehot, cols, weights
    )


def fused_post_exchange_remote_plastic_ref(
    act_remote: jnp.ndarray,  # (n,) exchanged activity, own slice zeroed
    act: jnp.ndarray,  # (n,) full exchanged activity (for STDP)
    pre_trace: jnp.ndarray,  # (n,) exchanged global pre-synaptic traces
    ring: jnp.ndarray,  # (D, n_p) ring ALREADY rotated by the local pass
    write_onehot: jnp.ndarray,  # (nd, D) one-hot of (t + d) % D per bucket
    post_trace: jnp.ndarray,  # (n_p,) local post-synaptic traces (updated)
    post_spike: jnp.ndarray,  # (n_p,) local spikes this step
    cols,  # per delay bucket (R, K_d) int32, global ids (FULL panels)
    weights,  # per delay bucket (R, K_d)
    plastic,  # per delay bucket (R, K_d) 0/1 mask of STDP slots
    *,
    stdp: Dict[str, float],  # a_plus / a_minus / w_min / w_max
):
    """Oracle for the plastic *remote pass* of the overlapped split step.

    Plastic panels are never split (the weights inside them are mutable
    state), so both passes traverse the full panels: the local pass
    gathers an (n,)-embedded copy of the partition's own activity, and
    this remote pass gathers ``act_remote`` (the exchanged vector with the
    own-partition slice zeroed) for the ring update while the STDP weight
    update — elementwise per synapse slot, hence not decomposable across
    passes — applies here once from the *full* activity and pre-trace
    vectors, exactly as in ``fused_post_exchange_plastic_ref``.  Returns
    ``(new_ring, new_weights)``.
    """
    n_p = ring.shape[1]
    new_ring = ring
    new_weights = []
    for i, (c, w, pm) in enumerate(zip(cols, weights, plastic)):
        cur = spike_gather_ref(act_remote, c, w)[:n_p]
        new_ring = new_ring + write_onehot[i][:, None] * cur[None, :]
        pad_r = c.shape[0] - n_p
        post_t = jnp.pad(post_trace, (0, pad_r)) if pad_r else post_trace
        post_s = jnp.pad(post_spike, (0, pad_r)) if pad_r else post_spike
        new_weights.append(
            stdp_update_ref(w, pm, c, pre_trace, act, post_t, post_s, **stdp)
        )
    return new_ring, new_weights


def fused_step_ref(
    v: jnp.ndarray,  # (n_p,)
    refrac: jnp.ndarray,  # (n_p,)
    i_tot: jnp.ndarray,  # (n_p,) total input current
    cols,  # per delay bucket (R, K_d) int32, local ids
    weights,  # per delay bucket (R, K_d)
    *,
    params: Dict[str, float],
):
    """Oracle for the fused per-partition step (kernels/fused_step.py):
    LIF advance + spike emission + per-bucket gather-accumulate, composed
    from the individual oracles.  Returns (v', refrac', spikes, currents).
    """
    v2, r2, s = lif_step_ref(v, refrac, i_tot, **params)
    currents = [spike_gather_ref(s, c, w) for c, w in zip(cols, weights)]
    return v2, r2, s, currents


def fused_step_plastic_ref(
    v: jnp.ndarray,  # (n_p,)
    refrac: jnp.ndarray,  # (n_p,)
    i_tot: jnp.ndarray,  # (n_p,) total input current
    tr_plus: jnp.ndarray,  # (n_p,) pre-synaptic e-trace
    tr_minus: jnp.ndarray,  # (n_p,) post-synaptic e-trace
    cols,  # per delay bucket (R, K_d) int32, local ids
    weights,  # per delay bucket (R, K_d)
    plastic,  # per delay bucket (R, K_d) 0/1 mask of STDP slots
    *,
    params: Dict[str, float],
    taus: Tuple[float, float],  # (tau_plus, tau_minus)
    stdp: Dict[str, float],  # a_plus / a_minus / w_min / w_max
):
    """Oracle for the plastic fused per-partition step: LIF advance + spike
    emission + trace decay + per-bucket gather-accumulate + STDP weight
    update, composed from the individual oracles in the documented step
    order (gather uses *pre-update* weights; the identity exchange means
    ``act == spikes`` and ``pre_trace == tr_plus'``).  Returns
    ``(v', refrac', spikes, tr_plus', tr_minus', currents, new_weights)``.
    """
    v2, r2, s = lif_step_ref(v, refrac, i_tot, **params)
    dt = params["dt"]
    tp = trace_decay_ref(tr_plus, s, dt=dt, tau=taus[0])
    tm = trace_decay_ref(tr_minus, s, dt=dt, tau=taus[1])
    n_p = v.shape[0]
    currents, new_weights = [], []
    for c, w, pm in zip(cols, weights, plastic):
        currents.append(spike_gather_ref(s, c, w))
        pad_r = c.shape[0] - n_p
        post_t = jnp.pad(tm, (0, pad_r)) if pad_r else tm
        post_s = jnp.pad(s, (0, pad_r)) if pad_r else s
        new_weights.append(
            stdp_update_ref(w, pm, c, tp, s, post_t, post_s, **stdp)
        )
    return v2, r2, s, tp, tm, currents, new_weights


def fused_post_exchange_plastic_ref(
    act: jnp.ndarray,  # (n,) exchanged global activity
    pre_trace: jnp.ndarray,  # (n,) exchanged global pre-synaptic traces
    ring: jnp.ndarray,  # (D, n_p) future-current ring buffer (uncleared)
    clear_mask: jnp.ndarray,  # (D,) 0 at the just-delivered slot, 1 else
    write_onehot: jnp.ndarray,  # (nd, D) one-hot of (t + d) % D per bucket
    post_trace: jnp.ndarray,  # (n_p,) local post-synaptic traces (updated)
    post_spike: jnp.ndarray,  # (n_p,) local spikes this step
    cols,  # per delay bucket (R, K_d) int32, global ids
    weights,  # per delay bucket (R, K_d)
    plastic,  # per delay bucket (R, K_d) 0/1 mask of STDP slots
    *,
    stdp: Dict[str, float],  # a_plus / a_minus / w_min / w_max
):
    """Oracle for the plastic fused post-exchange kernel: everything after
    the spike exchange — ring rotate + every delay bucket's ELL
    gather-accumulate (pre-update weights) + the STDP weight update on the
    plastic-masked slots, in one pass over the panels.  Returns
    ``(new_ring, new_weights)``.
    """
    n_p = ring.shape[1]
    new_ring = ring * clear_mask[:, None]
    new_weights = []
    for i, (c, w, pm) in enumerate(zip(cols, weights, plastic)):
        cur = spike_gather_ref(act, c, w)[:n_p]
        new_ring = new_ring + write_onehot[i][:, None] * cur[None, :]
        pad_r = c.shape[0] - n_p
        post_t = jnp.pad(post_trace, (0, pad_r)) if pad_r else post_trace
        post_s = jnp.pad(post_spike, (0, pad_r)) if pad_r else post_spike
        new_weights.append(
            stdp_update_ref(w, pm, c, pre_trace, act, post_t, post_s, **stdp)
        )
    return new_ring, new_weights
