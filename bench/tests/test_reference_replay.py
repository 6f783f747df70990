"""The reference's replay of the configurations' model (``bench/models/
lif_delta.py`` through the shared loop of ``bench/reference.py``) against a
plain one written here, which loops over every edge of every spiking
source, for both configurations' parameters (STDP in the Brunel one) and
for the bfloat16 control; and the count of synaptic events the replay
visits."""
import json
import os

import numpy as np
import pytest

import bench_helpers  # noqa: F401  (puts the repository on the path)
from bench import reference, spec

CONFIGS = ("pd14_microcircuit", "brunel2000_stdp")


def _config(name: str) -> dict:
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


lif_delta = spec.load_model(spec.BENCH_DIR, _config(CONFIGS[0]))


def _params(name: str):
    return lif_delta.Params.from_config(_config(name))


def _network(p, n=240, mean_in=30, d_max=15, seed=0):
    """A random network with the configuration's parameters: every row's
    in-degree, sources and delays drawn, some neurons with no in-edge,
    weights of both signs, and with STDP half the synapses plastic."""
    rng = np.random.default_rng(seed)
    deg = rng.poisson(mean_in, n)
    deg[rng.choice(n, 5, replace=False)] = 0
    m = int(deg.sum())
    plastic = (rng.random(m) < 0.5) if p.stdp else np.zeros(m, bool)
    return reference.Network(
        row_ptr=np.concatenate([[0], np.cumsum(deg)]).astype(np.int64),
        col=rng.integers(0, n, m).astype(np.int32),
        weight=(rng.normal(0.2, 0.4, m) * (p.v_thresh - p.v_rest) / 10).astype(np.float32),
        delay=rng.integers(1, d_max + 1, m).astype(np.float32),
        plastic=plastic,
        noise_ids=np.arange(n),
        neurons=dict(
            v0=rng.uniform(p.v_reset, p.v_thresh, n),
            refrac0=np.zeros(n),
            bias=np.full(n, 0.3 * (p.v_thresh - p.v_rest) / p.r_m),
        ),
    )


def _raster(n, steps=60, rate=0.04, seed=1):
    """Forced spikes; a tenth of the neurons never fire."""
    rng = np.random.default_rng(seed)
    r = (rng.random((steps, n)) < rate).astype(np.uint8)
    r[:, rng.choice(n, n // 10, replace=False)] = 0
    return r


def _noise(p, n):
    return lambda t: np.random.default_rng(1000 + t).normal(0.0, p.noise_sigma, n)


class _PlainReplayer(lif_delta._Replayer):
    """Propagation and STDP by a loop over every edge of every spiking
    source (and over every plastic edge), one edge at a time."""

    def __init__(self, net, p, q):
        super().__init__(net, p, q)
        self.target = np.repeat(np.arange(net.n), np.diff(net.row_ptr))
        self.out = [[] for _ in range(net.n)]
        for e, s in enumerate(net.col):
            self.out[s].append(e)

    def advance(self, t, pre, spikes, src=None):
        q, p, net = self.q, self.p, self.net
        v_new = pre[0]
        slot = t % self.D
        self.ring[slot] = 0.0
        self.refrac = np.where(spikes, self.ref_steps,
                               np.maximum(self.refrac - 1.0, 0.0))
        self.v = np.where(spikes, p.v_reset, v_new)
        sf = spikes.astype(np.float64)
        if self.plastic:
            self.tr_plus = q(q(self.tr_plus * self.dec_plus) + sf)
            self.tr_minus = q(q(self.tr_minus * self.dec_minus) + sf)
        for s in np.flatnonzero(spikes):
            for e in self.out[s]:
                d = int(net.delay[e])
                self.ring[(t + d) % self.D, self.target[e]] += q(self.w[e])
                self.events += 1
        if self.q is not reference._exact:
            self.ring = q(self.ring)
        if self.plastic:
            st = p.stdp
            for e in np.flatnonzero(net.plastic):
                r, c = self.target[e], net.col[e]
                if not (spikes[r] or spikes[c]):
                    continue
                dw = q(q(q(st["a_plus"] * self.tr_plus[c]) * sf[r])
                       - q(q(st["a_minus"] * self.tr_minus[r]) * sf[c]))
                self.w[e] = np.clip(q(self.w[e] + dw), st["w_min"], st["w_max"])
        self.hist[slot] = spikes.astype(np.uint8)


def _plain_replay(net, p, raster, noise, q):
    """The replay loop of ``reference.replay`` over the plain replayer, in
    float64 or, with ``q`` the bfloat16 rounding, as the control: each
    step on its own membrane, with the raster's spikes."""
    rep = _PlainReplayer(net, p, q)
    for t in range(raster.shape[0]):
        rep.advance(t, rep.integrate(t, noise(t)), raster[t].astype(bool))
    return rep


def _assert_states_equal(got, want):
    assert set(got) == set(want)
    for key in ("hist", "refrac"):
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    for key in set(got) - {"hist", "refrac"}:
        np.testing.assert_allclose(got[key], want[key], rtol=0, atol=1e-12,
                                   err_msg=key)


@pytest.mark.parametrize("control", [False, True], ids=["float64", "bfloat16"])
@pytest.mark.parametrize("config", CONFIGS)
def test_replay_equals_plain_per_edge_replay(config, control):
    p = _params(config)
    net = _network(p)
    raster = _raster(net.n)
    noise = _noise(p, net.n)
    got = lif_delta.replay(net, _config(config), raster, noise, control=control)
    plain = _plain_replay(net, p, raster, noise,
                          reference._bf16 if control else reference._exact)
    ran = got.control if control else got
    assert ran.events == plain.events > 0
    _assert_states_equal(ran.state, plain.state())
    if p.stdp:
        assert np.any(ran.state["w"] != net.weight), "no weight moved"
    assert np.any(ran.state["ring"] != 0)


@pytest.mark.parametrize("config", CONFIGS)
def test_replay_visits_exactly_the_raster_events(config):
    """Each step visits the out-edges of its spiking sources, no more:
    the replay's count is the raster's synaptic events, for the float64
    replay and for the control alike."""
    p, cfg = _params(config), _config(config)
    net = _network(p, seed=3)
    raster = _raster(net.n, steps=40, seed=4)
    out_degree = np.bincount(net.col, minlength=net.n)
    events = int(raster.sum(axis=0, dtype=np.int64) @ out_degree)
    got = lif_delta.replay(net, cfg, raster, _noise(p, net.n), control=True)
    assert got.events == got.control.events == events
    # a raster in which nobody fires visits nothing
    quiet = lif_delta.replay(net, cfg, np.zeros_like(raster[:5]), _noise(p, net.n))
    assert quiet.events == 0
