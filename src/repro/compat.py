"""Thin helpers over the jax API surface this repo uses (jax 0.9).

Everything in-repo imports ``shard_map`` from here, so a future API move
has one place to land.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import AbstractMesh

__all__ = ["shard_map", "abstract_mesh", "cost_analysis", "pmean"]

shard_map = jax.shard_map


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def pmean(x, axis_name):
    """``jax.lax.pmean`` with an explicit VJP (pmean is its own transpose).

    Transposing a pmean inside ``shard_map`` can receive a symbolic
    ``Zero`` cotangent (unused aux outputs of a differentiated shard_map
    produce exactly that); ``custom_vjp`` materializes cotangents before
    ``bwd`` runs while keeping the exact gradient.
    """
    return jax.lax.pmean(x, axis_name)


def _pmean_fwd(x, axis_name):
    return jax.lax.pmean(x, axis_name), None


def _pmean_bwd(axis_name, _res, ct):
    return (jax.lax.pmean(ct, axis_name),)


pmean.defvjp(_pmean_fwd, _pmean_bwd)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()``, empty dict when XLA reports none."""
    return compiled.cost_analysis() or {}


def abstract_mesh(axis_sizes, axis_names) -> AbstractMesh:
    """A device-free ``AbstractMesh`` of the given axis sizes and names."""
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))
