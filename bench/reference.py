"""Plain reference library of a clock-driven network with per-synapse
delays, read from the dCSR arrays of the network (the format's rows,
columns and edge tuples).  What a neuron and a synapse do is a model's:
each configuration names its model file, ``bench/models/<name>.py``
(``"reference"``), which replays its step through this library.

It imports nothing of the program under test.  The replay is
teacher-forced: each step takes its spikes from the raster the program
produced, so the trajectory is the program's own, and every number the
program reports can be recomputed and compared after any number of steps.
Where the program's spike disagrees with the model's own spike test, the
margin by which it does is the model's threshold gap.

A step visits only the out-edges of the sources that spike in it, through
an index of out-edges by source built once per replay (``_OutEdges``), so
a replay costs in proportion to its synaptic events and its steps times
the neurons, not to the edges times the steps.

The reference computes in float64.  The lower-precision control that has
to fail the comparison is the same replay with every result rounded to
bfloat16 (``_bf16``), the precision below the configuration's float32.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import numpy as np


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

    Rows are targets; ``col`` holds each in-edge's global source id.
    ``neurons`` holds the per-neuron part, as the configuration's model
    reads it out of the program's built state (``neurons(vtx_state)`` of
    the model file)."""

    row_ptr: np.ndarray  # (n + 1,) int64
    col: np.ndarray  # (m,) source ids
    weight: np.ndarray  # (m,) initial weights
    delay: np.ndarray  # (m,) integer steps
    plastic: np.ndarray  # (m,) bool: a synapse of a plastic edge model
    noise_ids: np.ndarray  # (n,) permanent neuron ids keying the step input
    neurons: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)

    @property
    def n(self) -> int:
        return len(self.row_ptr) - 1

    @property
    def ring_len(self) -> int:
        return max(int(self.delay.max()) if len(self.delay) else 1, 1)


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


def replay(net: Network, start: Callable, raster: np.ndarray,
           step_input: Callable[[int], np.ndarray],
           control: bool = False) -> Replayed:
    """Replay steps ``0 .. len(raster) - 1`` forced by ``raster`` (uint8,
    ``(T, n)``).  ``step_input(t)`` is the step's input ``(n,)``.

    ``start(q)`` makes a model's replayer that rounds every result by
    ``q`` (``_exact`` or ``_bf16``).  A replayer has ``integrate(t, x)``
    (the step up to its spike test, returning what the rest of the step
    reads), ``fires(pre)`` (its own spikes), ``gap(spikes, pre)`` (the
    widest margin by which ``spikes`` lie on the wrong side of its spike
    test), ``advance(t, pre, spikes, src)`` (the rest of the step with
    the given, forced, spikes; ``src`` is the ``_OutEdges`` index),
    ``state()`` and ``events``.

    Returns the float64 reference's :class:`Replayed`, and with
    ``control`` also that of the same replay in bfloat16, whose spike
    decisions are its own."""
    src = _OutEdges(net, raster)
    ref = start(_exact)
    ctl = start(_bf16) if control else None
    gap = ctl_gap = 0.0
    for t in range(raster.shape[0]):
        spikes = raster[t].astype(bool)
        x = step_input(t)
        pre = ref.integrate(t, x)
        gap = max(gap, ref.gap(spikes, pre))
        if ctl is not None:
            cpre = ctl.integrate(t, x)
            ctl_gap = max(ctl_gap, ref.gap(ctl.fires(cpre), pre))
            ctl.advance(t, cpre, spikes, src)
        ref.advance(t, pre, spikes, src)
    out = Replayed(ref.state(), gap, ref.events)
    if ctl is not None:
        out.control = Replayed(ctl.state(), ctl_gap, ctl.events)
    return out
