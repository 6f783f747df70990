"""Pallas TPU kernel: ELL spike delivery over packed spike bits.

The hot loop of clock-driven SNN simulation: for every target row, sum the
weights of the synapses whose presynaptic neuron fired
(``currents[r] = sum_k w[r,k] * bit(cols[r,k])``).

TPU mapping (HBM -> VMEM -> VREG):
  * the step's 0/1 activity is packed once into ``ceil(n/32)`` 32-bit
    words, laid out ``(S, 128)`` (``pack_spikes``; 2,412 words, S = 19, for
    the 77,169-neuron microcircuit) and pinned whole in VMEM, revisited by
    every grid step;
  * the (R, K) column-id and weight panels are tiled through VMEM in
    blocks of whole rows where they fit (``_blocks``), and each (8, 128)
    vreg of column ids is split in-register into word (``col >> 5``) and bit
    (``col & 31``); the word's lane is fetched with a lane gather of the
    words row, once per row of the layout, keeping the row the word lives
    in (``word >> 7``): Mosaic lowers only a 2-D gather whose indices have
    the source's shape, so each words row is broadcast to one vreg and
    gathered with ``take_along_axis`` along the lanes;
  * accumulation is in f32 whatever the weight dtype; the output block
    (block_r, 1) is revisited across the K grid dimension (innermost);
  * for a plastic panel the kernel also writes each slot's presynaptic
    spike, which the STDP pass reads in place of a second gather.

The activity must be 0/1 (spikes): any nonzero entry counts as a spike.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .blocks import pick_block

LANES = 128
WORD_BITS = 32


def packed_rows(n: int) -> int:
    """Rows S of the ``(S, 128)`` packed-bit layout of ``n`` neurons."""
    return -(-n // (WORD_BITS * LANES))


def pack_spikes(activity: jnp.ndarray) -> jnp.ndarray:
    """Pack a 0/1 activity vector ``(n,)`` into ``(S, 128)`` int32 words:
    neuron ``i`` is bit ``i & 31`` of word ``i >> 5``, and word ``j`` sits
    at row ``j >> 7``, lane ``j & 127``."""
    n = activity.shape[0]
    s = packed_rows(n)
    bits = (activity != 0).astype(jnp.uint32)
    bits = jnp.pad(bits, (0, s * LANES * WORD_BITS - n))
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    # distinct powers of two: the sum is the bitwise or, exactly
    words = jnp.sum(bits.reshape(-1, WORD_BITS) << shifts, axis=1,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(s, LANES)


def _kernel(words_ref, cols_ref, w_ref, out_ref, *fired_ref, group):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    n_rows = words_ref.shape[0]
    block_r, block_k = cols_ref.shape

    def rows(g, carry):
        r0 = pl.multiple_of(g * group, group)
        acc = jnp.zeros((group, LANES), jnp.float32)
        for c in range(block_k // LANES):
            lanes = pl.ds(c * LANES, LANES)
            cols = cols_ref[pl.ds(r0, group), lanes]
            w = w_ref[pl.ds(r0, group), lanes].astype(jnp.float32)
            word = cols >> 5
            lane = word & (LANES - 1)
            row = word >> 7
            val = None
            for s in range(n_rows):
                src = jnp.broadcast_to(
                    words_ref[pl.ds(s, 1), :], (group, LANES)
                )
                got = jnp.take_along_axis(
                    src, lane, axis=1, mode="promise_in_bounds"
                )
                val = got if val is None else jnp.where(row == s, got, val)
            fired = ((val >> (cols & (WORD_BITS - 1))) & 1) != 0
            acc = acc + jnp.where(fired, w, 0.0)
            if fired_ref:
                fired_ref[0][pl.ds(r0, group), lanes] = fired.astype(
                    jnp.float32
                )
        out_ref[pl.ds(r0, group), :] += jnp.sum(acc, axis=1, keepdims=True)
        return carry

    jax.lax.fori_loop(0, block_r // group, rows, 0)


# VMEM bytes of one (block_r, block_k) panel block; the ids and the
# weights, each double-buffered, hold four such blocks.  Slot vregs
# (8 x 128) per iteration of the row loop: the independent work between
# the loop's carried dependences.  On a TPU v5e a whole-row 48 x 4,736
# block delivers at 178 GB/s where 128-lane blocks of the same panel reach
# 40 GB/s; for 128-wide panels 32-row groups run 1.7x faster than 8-row
# ones, and more rows per group or per block gain nothing.
BLOCK_BYTES = 1024 * 1024
GROUP_VREGS = 4


def _blocks(R: int, K: int, block_r, block_k, interpret: bool):
    """(block_r, block_k, group): whole rows per block where they fit
    ``BLOCK_BYTES`` (the unrolled chunks of a row group are the kernel's
    independent work), else 128-lane multiples dividing K; ``group`` rows
    per loop iteration, a multiple of 8 dividing block_r."""
    if block_k is None:
        block_k = K if 8 * K * 4 <= BLOCK_BYTES else BLOCK_BYTES // (8 * 4)
    block_k = pick_block(K, max(block_k, LANES), interpret=interpret,
                         what="spike delivery cols", align=LANES)
    chunks = block_k // LANES
    group = 8 * max(1, min(GROUP_VREGS // chunks, -(-R // 8)))
    if block_r is None:
        block_r = BLOCK_BYTES // (block_k * 4)
    # rows need not divide R: the last block's rows past R are never written
    block_r = min(block_r, -(-R // group) * group)
    block_r = max(group, block_r // group * group)
    return block_r, block_k, group


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_k", "interpret", "fired")
)
def spike_gather_bits_pallas(
    words: jnp.ndarray,  # (S, 128) int32, from pack_spikes
    cols: jnp.ndarray,  # (R, K) int32
    weights: jnp.ndarray,  # (R, K)
    *,
    block_r: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    fired: bool = False,
):
    """(R,) f32 currents; with ``fired`` also the (R, K) f32 0/1 spike of
    each slot's presynaptic neuron, which the STDP pass reads in place of
    a gather of its own."""
    R, K0 = cols.shape
    K = K0
    if K % LANES:
        # a panel off the 128-lane tile (SimConfig(align_k=...) below 128):
        # pad with the layout's own padding slots, id 0 and weight 0
        pad = ((0, 0), (0, LANES - K % LANES))
        cols, weights = jnp.pad(cols, pad), jnp.pad(weights, pad)
        K = cols.shape[1]
    block_r, block_k, group = _blocks(R, K, block_r, block_k, interpret)
    panel = pl.BlockSpec((block_r, block_k), lambda r, k: (r, k))
    out_specs = [pl.BlockSpec((block_r, 1), lambda r, k: (r, 0))]
    out_shape = [jax.ShapeDtypeStruct((R, 1), jnp.float32)]
    if fired:
        out_specs.append(panel)
        out_shape.append(jax.ShapeDtypeStruct((R, K), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel, group=group),
        grid=(pl.cdiv(R, block_r), K // block_k),
        in_specs=[
            pl.BlockSpec(words.shape, lambda r, k: (0, 0)),  # resident
            panel,
            panel,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="snn_deliver_bits",
    )(words, cols, weights)
    if fired:
        return out[0][:, 0], out[1][:, :K0]
    return out[0][:, 0]


def spike_gather_pallas(
    activity: jnp.ndarray,  # (n,) 0/1
    cols: jnp.ndarray,  # (R, K) int32
    weights: jnp.ndarray,  # (R, K)
    **kw,
) -> jnp.ndarray:  # (R,) f32
    """``spike_gather`` through the packed-bit kernel: pack, then deliver.
    A step delivering several panels packs once and calls
    ``spike_gather_bits_pallas`` per panel."""
    return spike_gather_bits_pallas(pack_spikes(activity), cols, weights, **kw)
