"""The built network against what its configuration states.

The reference replays the network the program built, so a fault of the
builder (a rule left out, synapses dropped, an inhibitory weight with the
wrong sign, a wrong delay) would replay without a gap.  This check holds
the built arrays to the configuration's own numbers instead:

* connectivity: per (target, source) population pair, the synapse count
  against ``p * N_pre * N_post`` within the binomial spread
  (``connection_probabilities``), or every row's in-degree per source
  population exactly (``in_degree``); no synapse where ``p`` is 0, and
  none from a neuron to itself where ``autapses`` is false;
* weights (``synapses``, by source population): the sign of every
  weight, and the mean and spread against ``weight_mean`` and
  ``weight_sd`` (every weight exactly where the spread is 0);
* delays: every delay within the group's ``delay_steps`` ``[lo, hi]``,
  and uniform over it where ``lo < hi``;
* plasticity: a synapse is plastic exactly on the ``plastic_pairs``
  ``[source, target]``;
* neurons: one row for each of the ``n`` permanent ids; what a neuron
  holds at the start is the model's to check (``neuron_structure`` of the
  configuration's model file), and its counts and z values join these.

Two numbers come out: ``structure_diff``, the count of synapses and
neurons that break an exact rule, and ``structure_z``, the widest
deviation of a count or a statistic in standard errors of what the
configuration states.  Rows are read by permanent neuron id, so a network
of any number of partitions (padding rows included) is read the same.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# edges read per pass, to keep the temporaries of a 0.3e9-edge network small
EDGES_PER_PASS = 1 << 23


class _Plan:
    """Per source population: group statistics the configuration states."""

    def __init__(self, cfg: dict):
        self.names = list(cfg["populations"])
        self.sizes = np.array(list(cfg["populations"].values()), np.int64)
        self.P = P = len(self.names)
        self.bounds = np.cumsum(self.sizes)  # exclusive ends, by permanent id
        self.n = int(cfg["n"])
        at = {name: i for i, name in enumerate(self.names)}
        self.w_mean = np.full(P, np.nan)
        self.w_sd = np.full(P, np.nan)
        self.d_lo = np.zeros(P, np.int64)
        self.d_hi = np.zeros(P, np.int64)
        for grp in cfg["synapses"]:
            for s in grp["sources"]:
                i = at[s]
                self.w_mean[i] = float(grp["weight_mean"])
                self.w_sd[i] = float(grp["weight_sd"])
                self.d_lo[i], self.d_hi[i] = map(int, grp["delay_steps"])
        if np.isnan(self.w_mean).any():
            missing = [self.names[i] for i in np.flatnonzero(np.isnan(self.w_mean))]
            raise ValueError(f"configuration: no synapse group for sources {missing}")
        self.plastic = np.zeros((P, P), bool)  # [target, source]
        for s, t in cfg.get("plastic_pairs", ()):
            self.plastic[at[t], at[s]] = True
        self.probs = (np.asarray(cfg["connection_probabilities"], np.float64)
                      if "connection_probabilities" in cfg else None)
        self.in_degree = None
        if "in_degree" in cfg:
            self.in_degree = np.array(
                [int(cfg["in_degree"][name]) for name in self.names], np.int64)
        self.autapses = bool(cfg.get("autapses", True))
        self.d_max = int(self.d_hi.max())

    def pop_of(self, ids: np.ndarray) -> np.ndarray:
        """Population index of each permanent id; ``P`` for padding."""
        return np.searchsorted(self.bounds, ids, side="right")


def z_score(x, expected, se) -> float:
    return float(abs(x - expected) / se) if se > 0 else (0.0 if x == expected else np.inf)


def check(net, cfg: dict, model) -> Tuple[Dict[str, float], List[str]]:
    """``({"structure_diff": .., "structure_z": ..}, lines)`` for the
    reference's view of the built network (``reference.Network``, its
    weights as built) under configuration ``cfg`` and its ``model`` (the
    module of its model file); ``lines`` name each part of both
    numbers."""
    pl = _Plan(cfg)
    P, Q = pl.P, pl.P + 1  # populations, and padding
    ids = np.asarray(net.noise_ids, np.int64)
    row_pop = pl.pop_of(ids)
    # per (target, source) population pair, in one pass over the edges
    pair_sums = {k: np.zeros(Q * Q) for k in ("wsum", "w2sum")}
    pair_counts = {k: np.zeros(Q * Q, np.int64)
                   for k in ("all", "neg", "pos", "plastic")}
    dhist = np.zeros(Q * (pl.d_max + 2), np.int64)
    fixed_w = np.append(np.where(pl.w_sd == 0, pl.w_mean, np.nan), np.nan)
    exact = dict(weight=0, delay_steps=0, autapse=0, in_degree_rows=0)
    n_rows = len(net.row_ptr) - 1
    r0 = 0
    while r0 < n_rows:
        r1 = int(np.searchsorted(net.row_ptr, net.row_ptr[r0] + EDGES_PER_PASS,
                                 side="right")) - 1
        r1 = min(max(r1, r0 + 1), n_rows)
        e0, e1 = int(net.row_ptr[r0]), int(net.row_ptr[r1])
        deg = np.diff(net.row_ptr[r0:r1 + 1])
        col = np.asarray(net.col[e0:e1])
        spop = row_pop[col]
        pair = np.repeat(row_pop[r0:r1] * Q, deg) + spop
        w = np.asarray(net.weight[e0:e1], np.float64)
        for key, sel in (("all", slice(None)), ("neg", w < 0), ("pos", w > 0),
                         ("plastic", np.asarray(net.plastic[e0:e1]))):
            pair_counts[key] += np.bincount(pair[sel], minlength=Q * Q)
        pair_sums["wsum"] += np.bincount(pair, weights=w, minlength=Q * Q)
        pair_sums["w2sum"] += np.bincount(pair, weights=w * w, minlength=Q * Q)
        if not np.isnan(fixed_w).all():
            stated = fixed_w[spop]
            exact["weight"] += int(np.count_nonzero(~np.isnan(stated) & (w != stated)))
        d = np.asarray(net.delay[e0:e1])
        di = np.rint(d).astype(np.int64)
        exact["delay_steps"] += int(np.count_nonzero(d != di))
        dhist += np.bincount(spop * (pl.d_max + 2) + np.clip(di, 0, pl.d_max + 1),
                             minlength=dhist.size)
        if not pl.autapses:
            exact["autapse"] += int(np.count_nonzero(
                np.repeat(np.arange(r0, r1), deg) == col))
        if pl.in_degree is not None:
            local = np.repeat(np.arange(r1 - r0), deg)
            per_row = np.bincount(local * Q + spop,
                                  minlength=(r1 - r0) * Q).reshape(-1, Q)
            rows_real = row_pop[r0:r1] < P
            off = (per_row[rows_real, :P] != pl.in_degree).any(axis=1)
            exact["in_degree_rows"] += int(np.count_nonzero(off))
        r0 = r1

    counts, neg, pos, plastic = (pair_counts[k].reshape(Q, Q)
                                 for k in ("all", "neg", "pos", "plastic"))
    exact["padding_edges"] = int(counts[P].sum() + counts[:P, P].sum())
    neg_src = pl.w_mean < 0
    exact["sign"] = int(pos[:P, :P][:, neg_src].sum() + neg[:P, :P][:, ~neg_src].sum())
    exact["plastic"] = int(np.where(pl.plastic, counts[:P, :P] - plastic[:P, :P],
                                    plastic[:P, :P]).sum())
    dhist = dhist.reshape(Q, pl.d_max + 2)
    exact["delay_range"] = int(sum(dhist[s, :pl.d_lo[s]].sum() + dhist[s, pl.d_hi[s] + 1:].sum()
                                   for s in range(P)))
    zs: Dict[str, float] = {}
    if pl.probs is not None:
        z = 0.0
        zero_p = 0
        for t in range(P):
            for s in range(P):
                p = pl.probs[t, s]
                if p <= 0.0:
                    zero_p += int(counts[t, s])
                    continue
                pairs = float(pl.sizes[t] * pl.sizes[s])
                z = max(z, z_score(counts[t, s], p * pairs, np.sqrt(pairs * p * (1 - p))))
        exact["zero_p_edges"] = zero_p
        zs["pair_count"] = z
    per_src = counts[:P, :P].sum(axis=0)
    wsum, w2sum = (pair_sums[k].reshape(Q, Q)[:P, :P].sum(axis=0)
                   for k in ("wsum", "w2sum"))
    zw = 0.0
    for s in range(P):
        c, sd = per_src[s], pl.w_sd[s]
        if c == 0 or sd == 0:
            continue
        mean = wsum[s] / c
        spread = np.sqrt(max(w2sum[s] / c - mean * mean, 0.0))
        zw = max(zw, z_score(mean, pl.w_mean[s], sd / np.sqrt(c)),
                 z_score(spread, sd, sd / np.sqrt(2 * c)))
    zs["weight"] = zw
    zd = 0.0
    for s in range(P):
        lo, hi, c = pl.d_lo[s], pl.d_hi[s], per_src[s]
        if lo < hi and c:
            q = 1.0 / (hi - lo + 1)
            for d in range(lo, hi + 1):
                zd = max(zd, z_score(dhist[s, d], c * q, np.sqrt(c * q * (1 - q))))
    zs["delay_uniform"] = zd

    real_rows = row_pop < P
    exact["rows"] = abs(int(np.count_nonzero(real_rows)) - pl.n) + (
        0 if np.array_equal(np.sort(ids[real_rows]), np.arange(pl.n)) else 1)
    neuron_exact, neuron_zs = model.neuron_structure(net, cfg, row_pop, P)
    exact.update(neuron_exact)
    zs.update(neuron_zs)

    numbers = dict(structure_diff=float(sum(exact.values())),
                   structure_z=float(max(zs.values())))
    lines = [
        "exact rules broken: " + ", ".join(f"{k} {v}" for k, v in exact.items()),
        "widest z: " + ", ".join(f"{k} {v:.4f}" for k, v in zs.items()),
        "synapses per (target, source) population: " + "; ".join(
            f"{pl.names[t]}<-{pl.names[s]} {counts[t, s]}"
            for t in range(P) for s in range(P) if counts[t, s]),
    ]
    return numbers, lines
