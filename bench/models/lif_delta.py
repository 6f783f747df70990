"""The repository's LIF neuron with delta synapses, per-synapse delays,
Gaussian step noise and pair STDP, as the configurations that name
``"reference": "lif_delta"`` state it.

It follows the documented step order: deliver the ring slot ``t % D``;
integrate ``i_syn + noise + bias`` by the exact exponential-Euler LIF
update; spike at threshold (refractory neurons neither integrate nor
spike); bump the e-traces; propagate each spiking source's synapses into
``ring[(t + d) % D]`` with the weights before this step's update; apply
STDP to plastic synapses; record the spikes in ``hist[t % D]``.

Configuration keys read here, and nowhere else in the benchmark:
``neuron`` (``tau_m v_rest v_reset v_thresh t_ref r_m``), ``noise_sigma``,
``bias`` (``mu``, ``sigma``), ``v_init_uniform`` and ``stdp`` (``a_plus
a_minus tau_plus tau_minus w_min w_max``, or null).  The program's
per-neuron state is ``(v, refrac, bias)`` in its ``vtx_state`` columns.

The names the harness calls are listed in ``bench/models/README.md``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import reference, structure
from bench.reference import _OutEdges, _exact, _ranges

# the program's edge models whose weights this model's rule changes
PLASTIC_EDGES = ("syn_stdp",)

# a program spike where the reference holds the neuron refractory breaks
# the model outright; it reads as this gap (mV), far above any limit
REFRACTORY_SPIKE_GAP = 1.0e3


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


def neurons(vtx: np.ndarray) -> Dict[str, np.ndarray]:
    """The per-neuron part of the reference's view, from the program's
    built ``vtx_state`` (rows of the whole network)."""
    return dict(v0=np.array(vtx[:, 0]), refrac0=np.array(vtx[:, 1]),
                bias=np.array(vtx[:, 2]))


def departures(net, cfg: dict) -> List[str]:
    """Where the built network departs from the parameters the
    configuration states (neuron and synapse models, dt, noise); its sizes
    and connectivity are ``structure``'s to check."""
    out = []
    lif = net.registry.spec("lif").params
    for k, v in cfg["neuron"].items():
        if k != "model" and float(lif[k]) != float(v):
            out.append(f"lif {k}={lif[k]}, configuration states {v}")
    if cfg.get("stdp"):
        st = net.registry.spec("syn_stdp").params
        for k, v in cfg["stdp"].items():
            if float(st[k]) != float(v):
                out.append(f"stdp {k}={st[k]}, configuration states {v}")
    for key, meta in (("dt_ms", "dt"), ("noise_sigma", "noise_sigma")):
        if float(net.meta[meta]) != float(cfg[key]):
            out.append(f"{meta}={net.meta[meta]}, configuration states {cfg[key]}")
    return out


def program_state(state: dict) -> Dict[str, np.ndarray]:
    """The carry by row of the whole network.  A k-chip carry has a
    leading partition axis: ``(k, n_p, ...)`` and rings ``(k, D, n_p)``."""
    vtx = np.asarray(state["vtx_state"])
    ring, hist = np.asarray(state["ring"], np.float64), np.asarray(state["hist"])
    tr_plus = np.asarray(state["tr_plus"], np.float64)
    tr_minus = np.asarray(state["tr_minus"], np.float64)
    if vtx.ndim == 3:
        vtx = vtx.reshape(-1, vtx.shape[-1])
        ring, hist = (np.concatenate(list(x), axis=-1) for x in (ring, hist))
        tr_plus, tr_minus = tr_plus.reshape(-1), tr_minus.reshape(-1)
    return dict(
        v=vtx[:, 0].astype(np.float64), refrac=vtx[:, 1].astype(np.float64),
        ring=ring, hist=hist, tr_plus=tr_plus, tr_minus=tr_minus,
    )


def step_input(seed: int, net: reference.Network, cfg: dict):
    """Step noise of the model: N(0, sigma^2) drawn by JAX's threefry from
    the key ``fold_in(PRNGKey(seed), t)`` over the permanent neuron ids."""
    import jax
    import jax.numpy as jnp

    n, ids, sigma = net.n, net.noise_ids, float(cfg["noise_sigma"])
    key = jax.random.PRNGKey(seed)
    draw = jax.jit(
        lambda t: jax.random.normal(jax.random.fold_in(key, t), (n,), jnp.float32)
    )
    return lambda t: sigma * np.asarray(draw(t), np.float64)[ids]


class _Replayer:
    def __init__(self, net: reference.Network, p: Params, q: Callable):
        self.net, self.p, self.q = net, p, q
        n, D = net.n, net.ring_len
        self.D = D
        self.v = q(net.neurons["v0"].astype(np.float64))
        self.refrac = net.neurons["refrac0"].astype(np.float64)
        self.bias = q(net.neurons["bias"].astype(np.float64))
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

    def integrate(self, t: int, noise: np.ndarray):
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

    def fires(self, pre) -> np.ndarray:
        v_new, active = pre
        return (v_new >= self.p.v_thresh) & active

    def gap(self, spikes: np.ndarray, pre) -> float:
        return _threshold_gap(spikes, *pre, self.p.v_thresh)

    def advance(self, t: int, pre, spikes: np.ndarray, src: _OutEdges) -> None:
        """Finish step ``t`` with the given (forced) spikes."""
        q, p = self.q, self.p
        v_new = pre[0]
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


def replay(net: reference.Network, cfg: dict, raster: np.ndarray,
           step_input: Callable[[int], np.ndarray],
           control: bool = False) -> reference.Replayed:
    """``reference.replay`` of this model under ``cfg``'s parameters."""
    p = Params.from_config(cfg)
    return reference.replay(net, lambda q: _Replayer(net, p, q), raster,
                            step_input, control)


def gaps(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
         thr_gap: float, net: reference.Network,
         rows: np.ndarray) -> Dict[str, float]:
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


def neuron_structure(net: reference.Network, cfg: dict, row_pop: np.ndarray,
                     n_pops: int) -> Tuple[Dict[str, int], Dict[str, float]]:
    """The neuron part of the structure check: the initial membrane
    potential uniform over ``v_init_uniform`` and no neuron refractory at
    the start (exact counts), and the bias mean and spread per population
    against ``bias`` and the mean initial potential (z values).
    ``row_pop`` is each row's population index, ``n_pops`` for padding."""
    z = structure.z_score
    real_rows = row_pop < n_pops
    exact: Dict[str, int] = {}
    bias_mu, bias_sd = float(cfg["bias"]["mu"]), float(cfg["bias"]["sigma"])
    v_lo, v_hi = map(float, cfg["v_init_uniform"])
    zb = zv = 0.0
    v0 = np.asarray(net.neurons["v0"], np.float64)
    exact["v_init"] = int(np.count_nonzero(real_rows & ((v0 < v_lo) | (v0 >= v_hi))))
    exact["refractory_init"] = int(np.count_nonzero(
        real_rows & (np.asarray(net.neurons["refrac0"]) != 0)))
    for i in range(n_pops):
        rows = row_pop == i
        c = int(np.count_nonzero(rows))
        if not c:
            continue
        b = np.asarray(net.neurons["bias"], np.float64)[rows]
        zb = max(zb, z(b.mean(), bias_mu, bias_sd / np.sqrt(c)),
                 z(b.std(), bias_sd, bias_sd / np.sqrt(2 * c)))
        zv = max(zv, z(v0[rows].mean(), (v_lo + v_hi) / 2,
                       (v_hi - v_lo) / np.sqrt(12 * c)))
    return exact, dict(bias=zb, v_init=zv)
