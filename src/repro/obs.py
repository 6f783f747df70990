"""The program's profiler spans and scopes, by name.

One mechanism, on the profiler's clock: host spans are
``jax.profiler.TraceAnnotation`` events, device scopes are
``jax.named_scope`` components of each compiled op's name stack (XLA's
``op_name`` metadata, the ``tf_op`` stat of a device op in the trace).
Both land in the ``.xplane.pb`` of any ``jax.profiler`` trace, beside the
device ops.  Nothing is recorded while no trace runs: an idle span costs
about a microsecond, a scope costs nothing on the device.

Every name starts with ``snn.``; a count given to a span (``bytes``) is a
stat of its trace event.  docs/ARCHITECTURE.md ("Observability") says what
each one covers.
"""
from __future__ import annotations

import functools

import jax

# -- host spans: the run loop ------------------------------------------------
CHUNK = "snn.chunk"  # Session.run: one engine.run_chunk
DISPATCH = "snn.dispatch"  # enqueue of the chunk program (compile on a miss)
FETCH = "snn.fetch"  # wait for the device, then the outputs to the host
READOUT = "snn.readout"  # the monitors' on_chunk
# -- host spans: checkpoint, write, restore ----------------------------------
CKPT = "snn.ckpt"  # one checkpoint boundary of Session.run
CKPT_SYNC = "snn.ckpt.sync"  # device -> host, weights back into the dCSR
CKPT_CAPTURE = "snn.ckpt.capture"  # runtime fetch + snapshot copy
CKPT_ENQUEUE = "snn.ckpt.enqueue"  # writer submits (block on a full queue)
WRITE = "snn.write"  # one snapshot written (the writer's thread)
WRITE_PART = "snn.write.part"  # one partition shard of it
RESTORE_READ = "snn.restore.read"  # snapshot read, CRC walk included
RESTORE_RESHARD = "snn.restore.reshard"  # repartition onto another k
# -- host spans: build -------------------------------------------------------
BUILD_RULES = "snn.build.rules"  # procedural build of the dCSR
BUILD_ELL = "snn.build.ell"  # one partition's delay-bucketed ELL repack
BUILD_PLACE = "snn.build.place"  # device data of the partitions, placed
# -- device scopes of the step -----------------------------------------------
NOISE = "snn.noise"  # step noise: fold_in, normal, take by id
NEURON = "snn.neuron"  # slot read/clear, neuron update, traces, history
EXCHANGE = "snn.exchange"  # the spike exchange (identity at k = 1)
DELIVER = "snn.deliver"  # per bucket: gather, row reduce, ring add
STDP = "snn.stdp"  # per bucket: the weight update and its gathers

SPANS = (CHUNK, DISPATCH, FETCH, READOUT, CKPT, CKPT_SYNC, CKPT_CAPTURE,
         CKPT_ENQUEUE, WRITE, WRITE_PART, RESTORE_READ, RESTORE_RESHARD,
         BUILD_RULES, BUILD_ELL, BUILD_PLACE)
SCOPES = (NOISE, NEURON, EXCHANGE, DELIVER, STDP)


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """Host span ``name`` with integer ``counts`` as stats of its event;
    ``set_metadata(**counts)`` on the entered span adds counts known only
    at its end."""
    return jax.profiler.TraceAnnotation(name, **counts)


def spanned(name: str):
    """Decorator: each call of the function is one span ``name``."""
    return functools.partial(jax.profiler.annotate_function, name=name)


def scope(name: str):
    """Device scope ``name`` for the ops traced inside it."""
    return jax.named_scope(name)


def delay_scope(delay: int):
    """The per-bucket scope nested inside ``DELIVER`` and ``STDP``."""
    return jax.named_scope(f"d{int(delay)}")


def nbytes(*trees) -> int:
    """Bytes of every array leaf of ``trees`` (host or device arrays)."""
    return sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(trees))
