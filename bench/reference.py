"""Plain reference of a clock-driven LIF network with delta synapses,
per-synapse delays, Gaussian step noise and pair STDP, read from the dCSR
arrays of the network (the format's rows, columns and edge tuples).

It imports nothing of the program under test.  It follows the documented
step order: deliver the ring slot ``t % D``; integrate ``i_syn + noise +
bias`` by the exact exponential-Euler LIF update; spike at threshold
(refractory neurons neither integrate nor spike); bump the e-traces;
propagate each spiking source's synapses into ``ring[(t + d) % D]`` with
the weights before this step's update; apply STDP to plastic synapses;
record the spikes in ``hist[t % D]``.

The replay is teacher-forced: each step takes its spikes from the raster
the program produced, so the trajectory is the program's own, and every
number the program reports (membrane, refractory counters, ring, history,
traces, weights) can be recomputed and compared after any number of steps.
Where the program's spike disagrees with the reference's threshold test,
the margin by which it does is the threshold gap.

A step visits only the out-edges of the sources that spike in it, through
an index of out-edges by source built once per replay, so a replay costs
in proportion to its synaptic events and its steps times the neurons, not
to the edges times the steps.

The reference computes in float64.  The lower-precision control that has
to fail the comparison is the same replay with every result rounded to
bfloat16, the precision below the configuration's float32.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import numpy as np

# a program spike where the reference holds the neuron refractory breaks
# the model outright; it reads as this gap (mV), far above any limit
REFRACTORY_SPIKE_GAP = 1.0e3


def _exact(x):
    return x


def _bf16(x):
    import ml_dtypes

    return (
        np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)
    )



@dataclasses.dataclass
class Network:
    """The reference's view of a one-partition dCSR network (host arrays).

    Rows are targets; ``col`` holds each in-edge's global source id."""

    row_ptr: np.ndarray  # (n + 1,) int64
    col: np.ndarray  # (m,) source ids
    weight: np.ndarray  # (m,) initial weights
    delay: np.ndarray  # (m,) integer steps
    plastic: np.ndarray  # (m,) bool: an STDP synapse
    v0: np.ndarray  # (n,) initial membrane potential
    refrac0: np.ndarray  # (n,) initial refractory steps
    bias: np.ndarray  # (n,) constant input current
    noise_ids: np.ndarray  # (n,) permanent neuron ids keying the noise

    @property
    def n(self) -> int:
        return len(self.row_ptr) - 1

    @property
    def ring_len(self) -> int:
        return max(int(self.delay.max()) if len(self.delay) else 1, 1)


@dataclasses.dataclass
class Params:
    dt: float  # ms
    tau_m: float
    v_rest: float
    v_reset: float
    v_thresh: float
    t_ref: float
    r_m: float
    noise_sigma: float
    stdp: Optional[Dict[str, float]] = None  # a_plus a_minus tau_plus tau_minus w_min w_max

    @classmethod
    def from_config(cls, cfg: dict) -> "Params":
        nrn = cfg["neuron"]
        return cls(
            dt=float(cfg["dt_ms"]),
            tau_m=float(nrn["tau_m"]), v_rest=float(nrn["v_rest"]),
            v_reset=float(nrn["v_reset"]), v_thresh=float(nrn["v_thresh"]),
            t_ref=float(nrn["t_ref"]), r_m=float(nrn["r_m"]),
            noise_sigma=float(cfg["noise_sigma"]),
            stdp=dict(cfg["stdp"]) if cfg.get("stdp") else None,
        )


def _ranges(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """The integers of every range ``[lo[i], hi[i])``, concatenated."""
    cnt = hi - lo
    return np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(cnt.sum())


class _OutEdges:
    """Out-edges by source of every source that spikes somewhere in the
    raster (the CSR is by target): their edge ids grouped by source, those
    of source ``s`` at ``edge[ptr[s]:ptr[s + 1]]`` in CSR order, with each
    one's target row and delay beside it.  Built once, by one sort over
    the edges of those sources; a step then visits exactly the out-edges of
    the sources that spike in it, so a replay costs its synaptic events,
    not edges times steps."""

    def __init__(self, net: Network, raster: np.ndarray):
        if len(net.col) >= 2**31:
            raise ValueError("the index holds edge ids as int32")
        fired = raster.any(axis=0)
        kept = np.flatnonzero(fired[net.col])  # by target, in CSR order
        row = np.repeat(np.arange(net.n, dtype=np.int32),
                        np.diff(np.searchsorted(kept, net.row_ptr)))
        # (source, position in kept) pairs: sorted, they group the edges by
        # source and keep the CSR order within a source
        key = net.col[kept].astype(np.int64)
        key <<= 32
        key |= np.arange(len(kept))
        key.sort()
        self.ptr = np.searchsorted(key, np.arange(net.n + 1, dtype=np.int64) << 32)
        key &= 0xFFFFFFFF
        self.row = row[key]
        self.edge = kept[key].astype(np.int32)
        del key, kept, row
        self.delay = net.delay[self.edge].astype(np.int32)

    def of(self, spikes: np.ndarray) -> np.ndarray:
        """Positions (into this index) of the out-edges of the sources
        that spike."""
        src = np.flatnonzero(spikes)
        return _ranges(self.ptr[src], self.ptr[src + 1])


class _Replayer:
    def __init__(self, net: Network, p: Params, q: Callable):
        self.net, self.p, self.q = net, p, q
        n, D = net.n, net.ring_len
        self.D = D
        self.v = q(net.v0.astype(np.float64))
        self.refrac = net.refrac0.astype(np.float64)
        self.bias = q(net.bias.astype(np.float64))
        self.ring = np.zeros((D, n), np.float64)
        self.hist = np.zeros((D, n), np.uint8)
        self.decay = q(np.exp(-p.dt / p.tau_m))
        self.ref_steps = float(round(p.t_ref / p.dt))
        self.plastic = p.stdp is not None and bool(net.plastic.any())
        self.events = 0  # synaptic events visited: out-edges of spiking sources
        if self.plastic:
            s = p.stdp
            self.w = q(net.weight.astype(np.float64))
            self.tr_plus = np.zeros(n, np.float64)
            self.tr_minus = np.zeros(n, np.float64)
            self.dec_plus = q(np.exp(-p.dt / s["tau_plus"]))
            self.dec_minus = q(np.exp(-p.dt / s["tau_minus"]))
        else:
            self.w = net.weight

    def membrane(self, t: int, noise: np.ndarray):
        """(v after integration, before threshold and reset; active)."""
        q, p = self.q, self.p
        i_syn = self.ring[t % self.D]
        i_tot = q(q(i_syn + q(noise)) + self.bias)
        v_int = q(
            q(p.v_rest + q(q(self.v - p.v_rest) * self.decay))
            + q(q(p.r_m * i_tot) * q(1.0 - self.decay))
        )
        active = self.refrac <= 0
        return np.where(active, v_int, p.v_reset), active

    def advance(self, t: int, v_new: np.ndarray, spikes: np.ndarray,
                src: _OutEdges) -> None:
        """Finish step ``t`` with the given (forced) spikes."""
        q, p = self.q, self.p
        slot = t % self.D
        self.ring[slot] = 0.0
        self.refrac = np.where(
            spikes, self.ref_steps, np.maximum(self.refrac - 1.0, 0.0)
        )
        self.v = np.where(spikes, p.v_reset, v_new)
        sf = spikes.astype(np.float64)
        if self.plastic:
            self.tr_plus = q(q(self.tr_plus * self.dec_plus) + sf)
            self.tr_minus = q(q(self.tr_minus * self.dec_minus) + sf)
        # propagate with the weights from before this step's update
        at = src.of(spikes)
        e = src.edge[at]
        self.events += len(e)
        if len(e):
            slots = (t + src.delay[at].astype(np.int64)) % self.D
            # the ring is C-contiguous, so its flat view adds in place
            np.add.at(self.ring.reshape(-1), slots * self.net.n + src.row[at],
                      np.asarray(q(self.w[e]), np.float64))
            if self.q is not _exact:
                self.ring = q(self.ring)
        if self.plastic:
            self._stdp(spikes, e)
        self.hist[slot] = spikes.astype(np.uint8)

    def _stdp(self, spikes: np.ndarray, pre_edges: np.ndarray) -> None:
        q, s, net = self.q, self.p.stdp, self.net
        post_rows = np.flatnonzero(spikes)
        post_edges = _ranges(net.row_ptr[post_rows], net.row_ptr[post_rows + 1])
        e = np.union1d(pre_edges, post_edges)
        e = e[net.plastic[e]]
        if not len(e):
            return
        rows = np.searchsorted(net.row_ptr, e, side="right") - 1
        cols = net.col[e]
        sf = spikes.astype(np.float64)
        dw = q(
            q(q(s["a_plus"] * self.tr_plus[cols]) * sf[rows])
            - q(q(s["a_minus"] * self.tr_minus[rows]) * sf[cols])
        )
        self.w[e] = np.clip(q(self.w[e] + dw), s["w_min"], s["w_max"])

    def state(self) -> Dict[str, np.ndarray]:
        out = dict(v=self.v, refrac=self.refrac, ring=self.ring, hist=self.hist)
        if self.plastic:
            out.update(tr_plus=self.tr_plus, tr_minus=self.tr_minus, w=self.w)
        return out


def _threshold_gap(spikes, v_new, active, v_thresh) -> float:
    """Widest margin by which ``spikes`` sit on the wrong side of the
    threshold of the reference's membrane ``v_new``."""
    if np.any(spikes & ~active):
        return REFRACTORY_SPIKE_GAP
    over = spikes & (v_new < v_thresh)
    under = ~spikes & active & (v_new >= v_thresh)
    gap = 0.0
    if over.any():
        gap = max(gap, float(np.max(v_thresh - v_new[over])))
    if under.any():
        gap = max(gap, float(np.max(v_new[under] - v_thresh)))
    return gap


@dataclasses.dataclass
class Replayed:
    """What a replay ends with: the state, the threshold gap, and the
    synaptic events it visited (the out-edges of each step's spiking
    sources, summed over the steps); with the control, the same of the
    bfloat16 replay."""

    state: Dict[str, np.ndarray]
    thr_gap: float
    events: int
    control: Optional["Replayed"] = None


def replay(net: Network, p: Params, raster: np.ndarray,
           noise: Callable[[int], np.ndarray],
           control: bool = False) -> Replayed:
    """Replay steps ``0 .. len(raster) - 1`` forced by ``raster`` (uint8,
    ``(T, n)``).  ``noise(t)`` is the step noise ``(n,)`` in current units.

    Returns the float64 reference's :class:`Replayed`, and with
    ``control`` also that of the same replay in bfloat16, whose spike
    decisions are its own."""
    src = _OutEdges(net, raster)
    ref = _Replayer(net, p, _exact)
    ctl = _Replayer(net, p, _bf16) if control else None
    gap = ctl_gap = 0.0
    for t in range(raster.shape[0]):
        spikes = raster[t].astype(bool)
        nz = noise(t)
        v_new, active = ref.membrane(t, nz)
        gap = max(gap, _threshold_gap(spikes, v_new, active, p.v_thresh))
        if ctl is not None:
            cv, cact = ctl.membrane(t, nz)
            own = (cv >= p.v_thresh) & cact
            ctl_gap = max(
                ctl_gap, _threshold_gap(own, v_new, active, p.v_thresh)
            )
            ctl.advance(t, cv, spikes, src)
        ref.advance(t, v_new, spikes, src)
    out = Replayed(ref.state(), gap, ref.events)
    if ctl is not None:
        out.control = Replayed(ctl.state(), ctl_gap, ctl.events)
    return out


def gaps(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
         thr_gap: float, net: Network, rows: np.ndarray) -> Dict[str, float]:
    """The numbers compared: widest gaps of the continuous state, and
    exact counts of the discrete state, over the neurons ``rows`` (the
    padding rows of a partitioned network are none of the model's)."""
    got = {k: v if k == "w" else v[..., rows] for k, v in got.items()}
    ref = {k: v if k == "w" else v[..., rows] for k, v in ref.items()}
    out = dict(
        thr_gap_mv=float(thr_gap),
        v_gap_mv=float(np.max(np.abs(got["v"] - ref["v"]))),
        ring_gap=float(np.max(np.abs(got["ring"] - ref["ring"]))),
        refrac_diff=float(np.count_nonzero(got["refrac"] != ref["refrac"])),
        hist_diff=float(np.count_nonzero(got["hist"] != ref["hist"])),
    )
    if "w" in ref:
        pl = net.plastic
        out["w_gap"] = float(np.max(np.abs(got["w"][pl] - ref["w"][pl])))
        out["static_w_diff"] = float(
            np.count_nonzero(got["w"][~pl] != net.weight[~pl])
        )
        out["trace_gap"] = float(max(
            np.max(np.abs(got["tr_plus"] - ref["tr_plus"])),
            np.max(np.abs(got["tr_minus"] - ref["tr_minus"])),
        ))
    return out
