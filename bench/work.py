"""Work counts: the bytes and operations a step needs, from shapes and
from the run's own spikes.  Kept with the benchmark so that every PR
computes them the same way.

Two counts, for two metrics:

* ``gather_traffic`` — what the delivery gathers over the ELL panels
  move, for their share of HBM bandwidth, read from each op's own HLO
  signature in the trace (its operands and its result).  Two kinds of op
  count: XLA's gather, a ``kCustom`` fusion with a panel-sized result that
  reads the panel's int32 ids (its operands are the ids and the vector
  gathered from; the weights are read by a separate multiply-reduce op);
  and a delivery kernel of the program's own, a ``custom-call`` that
  reads an int32 operand of a panel's size (the ids; its other operands,
  such as the weights, and its result count too).

* ``least_step_work`` — what any implementation of one step has to move
  and compute, whatever layout it uses:

    - each synaptic event (a spiking source times one of its out-edges)
      reads its weight and its target id: 8 bytes, 1 add;
    - each neuron reads its state (v, refrac, bias) and its delay-ring
      slot, and writes v, refrac and the cleared slot: 28 bytes,
      ``LIF_OPS`` operations;
    - with STDP, each neuron reads and writes its two traces (16 bytes,
      4 operations), and each plastic synapse of a spiking target or
      from a spiking source reads its weight and source id and writes
      its weight (12 bytes, ``STDP_OPS`` operations).

  No implementation can do less, so a share of the peak built on it
  cannot pass 100%.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

EVENT_BYTES = 4 + 4  # weight, target id
EVENT_OPS = 1
NEURON_BYTES = 3 * 4 + 4 + 2 * 4 + 4  # read v/refrac/bias + slot, write v/refrac + slot
LIF_OPS = 10  # decay, input scale, adds, threshold compare, reset selects
TRACE_BYTES = 2 * (4 + 4)
TRACE_OPS = 4
STDP_SYNAPSE_BYTES = 4 + 4 + 4
STDP_OPS = 6

_DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "u8": 1,
                "s8": 1, "pred": 1, "f64": 8, "s64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")


def _signature(text: str):
    """(dtype, elements) of each array shape in a piece of HLO text, in
    order: an op's result comes before its operands."""
    return [(t, int(np.prod([int(x) for x in dims.split(",") if x])))
            for t, dims in _SHAPE.findall(text)]


def gather_traffic(op_seconds: Dict[str, float], op_counts: Dict[str, int],
                   panel_sizes: Iterable[int]) -> Tuple[float, float]:
    """(device seconds, bytes moved) of the delivery gathers over the ELL
    panels, from a trace's ops (keyed by their HLO text).  A gather is
    either a ``kCustom`` fusion whose result has as many elements as one
    of the panels and which reads an ``s32`` index operand of that size,
    or a ``custom-call`` that reads an ``s32`` operand of a panel's size.
    Its bytes are those of its result and operands."""
    sizes = set(panel_sizes)
    secs = moved = 0.0
    for name, s in op_seconds.items():
        if "kind=kCustom" in name and " fusion(" in name:
            elems = _signature(name.split(", kind=")[0])
            _, out_n = elems[0]
            if out_n not in sizes or ("s32", out_n) not in elems[1:]:
                continue
        elif " custom-call(" in name:
            result, operands = name.split(" custom-call(", 1)
            # the attributes after the operands restate their shapes
            operands = _signature(operands.split(", custom_call_target=")[0])
            if not any(("s32", n) in operands for n in sizes):
                continue
            elems = _signature(result) + operands
        else:
            continue
        secs += s
        moved += op_counts[name] * sum(_DTYPE_BYTES[t] * n for t, n in elems)
    return secs, moved


def least_step_work(
    raster: np.ndarray,
    out_degree: np.ndarray,
    plastic_out: Optional[np.ndarray] = None,
    plastic_in: Optional[np.ndarray] = None,
) -> Tuple[float, float]:
    """(bytes, operations) the steps of ``raster`` (``(T, n)`` 0/1) need
    in all.  ``out_degree[i]`` counts neuron i's out-edges; with STDP,
    ``plastic_out``/``plastic_in`` count its plastic out- and in-edges."""
    raster = np.asarray(raster)
    steps, n = raster.shape
    per_neuron = raster.sum(axis=0, dtype=np.int64)  # spikes per neuron
    events = float(per_neuron @ out_degree.astype(np.float64))
    nbytes = EVENT_BYTES * events + NEURON_BYTES * n * steps
    ops = EVENT_OPS * events + LIF_OPS * n * steps
    if plastic_out is not None:
        touched = float(
            per_neuron @ (plastic_out + plastic_in).astype(np.float64)
        )
        nbytes += TRACE_BYTES * n * steps + STDP_SYNAPSE_BYTES * touched
        ops += TRACE_OPS * n * steps + STDP_OPS * touched
    return nbytes, ops


def least_time(nbytes: float, ops: float, peak: dict) -> float:
    """Seconds the chip needs at least: the larger of the bandwidth and
    the compute bound."""
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["flops_per_s"])


def degrees(row_ptr: np.ndarray, col: np.ndarray, n: int,
            plastic: Optional[np.ndarray] = None):
    """Out-degree of each neuron, and with a plastic mask also its plastic
    out- and in-degree, from the CSR arrays (rows are targets)."""
    out = np.bincount(col, minlength=n)
    if plastic is None or not plastic.any():
        return out, None, None
    p_out = np.bincount(col[plastic], minlength=n)
    p_in = np.add.reduceat(
        np.concatenate([plastic, [False]]).astype(np.int64), row_ptr[:-1]
    ) * (np.diff(row_ptr) > 0)
    return out, p_out, p_in
