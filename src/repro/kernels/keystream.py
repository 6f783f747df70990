"""Pallas kernel for the builder's counter-based keystream.

Computes the same Threefry-2x32-20 word matrix as
``repro.builder.crng.word_matrix`` — in fact it calls the same code with
``xp=jax.numpy`` inside the kernel body, so the device fast path is
bit-identical to the NumPy oracle by construction (pure uint32
arithmetic; no floats anywhere near the kernel).

Layout: output word ``(r, j)`` is word ``j0 + j`` of stream
``(seed, stream)`` at counter ``rows[r]``.  Each output element computes
the full cipher at counter ``(row, (j0+j)//2)`` and selects the parity
half — redundant by 2x versus interleaving pairs, but keeps the kernel a
pure elementwise map (no lane shuffles), which is what the VPU wants.

Scalars (seed, stream, j0) ride scalar-prefetch SMEM so chunked builds
with varying streams/offsets reuse one compiled kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..builder import crng
from .blocks import pick_block


def _keystream_kernel(params_ref, rows_ref, out_ref):
    u32 = jnp.uint32
    seed = jax.lax.bitcast_convert_type(params_ref[0], u32)
    stream = jax.lax.bitcast_convert_type(params_ref[1], u32)
    j0 = jax.lax.bitcast_convert_type(params_ref[2], u32)
    rows = jax.lax.bitcast_convert_type(rows_ref[...], u32)  # (block_r,)
    block_r = out_ref.shape[0]
    w = out_ref.shape[1]
    j = j0 + jax.lax.broadcasted_iota(jnp.int32, (block_r, w), 1).astype(u32)
    pair = j >> u32(1)
    parity = j & u32(1)
    c0 = jax.lax.broadcast_in_dim(rows, (block_r, w), (0,))
    x0, x1 = crng.threefry2x32(seed, stream, c0, pair, xp=jnp)
    out_ref[...] = jnp.where(parity == 0, x0, x1)


@functools.partial(jax.jit, static_argnames=("n_words", "block_r", "interpret"))
def _keystream_call(params, rows, *, n_words, block_r, interpret):
    r_pad = rows.shape[0]
    grid = (r_pad // block_r,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec((block_r,), lambda r, params: (r,))],
        out_specs=pl.BlockSpec((block_r, n_words), lambda r, params: (r, 0)),
    )
    return pl.pallas_call(
        _keystream_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r_pad, n_words), jnp.uint32),
        interpret=interpret,
    )(params, rows)


def keystream_pallas(
    seed, stream, rows, j0, n_words, *, interpret: bool = False,
    block_rows: int = 256, **_,
):
    """(len(rows), n_words) uint32 keystream words (Pallas path)."""
    rows = np.asarray(rows, np.int32)
    n = len(rows)
    # rows block: sublane-align; words: lane-align on the compiled path
    r_pad = max(8, -(-n // 8) * 8)
    w_pad = n_words if interpret else max(128, -(-n_words // 128) * 128)
    if r_pad != n:
        rows = np.concatenate([rows, np.zeros(r_pad - n, np.int32)])
    block_r = pick_block(r_pad, block_rows, interpret=interpret,
                         what="builder_keystream")
    params = np.array([seed, stream, j0], np.uint32).view(np.int32)
    out = _keystream_call(
        params, jnp.asarray(rows), n_words=int(w_pad),
        block_r=block_r, interpret=interpret,
    )
    return out[:n, :n_words]


@functools.partial(jax.jit, static_argnames=("j0", "n_words"))
def keystream_jnp(seed, stream, rows, j0, n_words):
    """jnp oracle: the shared word_matrix evaluated under XLA."""
    return crng.word_matrix(seed, stream, rows, j0, n_words, xp=jnp)
