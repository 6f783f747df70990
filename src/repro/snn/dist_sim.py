"""Distributed SNN simulation: one dCSR partition per device via shard_map.

The paper's partition-based distribution mapped to SPMD: every device owns
partition p's rows (vertex state, incoming edges, ring buffer, history), the
per-step spike exchange is a single ``all_gather`` over the ``parts`` mesh
axis (dense activity vector — paper-faithful bulk-synchronous), or the
beyond-paper **compressed index exchange** (fixed-capacity spike-id lists,
~8-30x fewer collective bytes at biological activity levels; spikes dropped
past the capacity are counted per step in ``outs['overflow']`` and surfaced
through ``Session.run`` — never silent).  ``SimConfig(exchange='auto')``
resolves to the index exchange for non-plastic nets (collective bytes stay
at spike-count scale — the fused-split default) and dense otherwise.

Eligible partitions (homogeneous LIF, identity ELL rows) run the **fused
split** step engine: a fused pre-exchange kernel (LIF advance + spike
emission, one HBM read/write per state array), the collective, then a
fused post-exchange kernel (ring-buffer rotate + every delay bucket's ELL
gather-accumulate in one pass over the exchanged activity vector).
Plastic partitions take the ``fused_split_plastic`` variant: the
pre-exchange kernel also decays+bumps the e-traces, the dense exchange
carries the global pre-trace vector, and the post-exchange kernel folds
the STDP weight update into the same pass over the synapse panels (each
ELL panel crosses VMEM once per step, not twice).  Others fall back to
the unfused three-kernel sequence.

On top of the split engines, ``SimConfig(overlap=...)`` decouples the
gather from the collective: the post-exchange pass splits into a **local
pass** over the own-partition columns (data-independent of the
collective, so it runs concurrently with the all-gather — the collective
is issued first in program order and XLA's latency hiding does the rest)
and a **remote pass** over the gathered remote spikes.
``overlap='double_buffer'`` additionally defers step t's remote pass to
the top of step t+1, pipelining the collective against a full step of
compute; the per-slot add sequence is unchanged, so ``double_buffer`` is
bit-exact against ``overlap='local'`` by construction.

Requires uniform partitions (``to_dcsr(..., uniform=True)``): SPMD needs
equal shard shapes, so deficient partitions are padded with inert dummy
neurons at build time.  With uniform blocks, partition-contiguous global ids
satisfy ``global_id = p * n_p + local_id`` and the all-gathered activity
vector is *exactly* the single-device oracle's labelling — equivalence is
asserted bit-for-bit in tests.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..compat import shard_map
from ..launch.mesh import make_snn_mesh

from ..core.dcsr import DCSRNetwork
from ..core.ell import build_delay_ell
from ..kernels.dispatch import (
    event_id_cap, require_compilable, resolve_delivery_backend,
    resolve_sim_backend, select_step_engine,
)
from ..kernels.event_step import (
    EventPlan, build_touch_masks, event_block_geometry,
)
from .simulator import (
    SimConfig,
    delivery_path,
    make_core_step,
    partition_device_data,
    _models_present,
    _probe_event_capable,
)


@dataclasses.dataclass
class StackedNet:
    """Per-delay stacked device arrays: leading axis = partition."""

    n_p: int
    k: int
    delays: Tuple[int, ...]
    cols: List[np.ndarray]  # per delay (k, R, K) int32
    weights: List[np.ndarray]
    plastic: List[np.ndarray]  # empty when no partition has STDP synapses
    valid: List[np.ndarray]
    vtx_model: np.ndarray  # (k, n_p)
    vtx_state0: np.ndarray  # (k, n_p, S)
    any_plastic: bool
    d_ring: int
    identity_rows: bool  # all buckets row-identity (max_k=None => True)


def stack_partitions(net: DCSRNetwork, cfg: SimConfig) -> StackedNet:
    n_ps = {p.n for p in net.parts}
    assert len(n_ps) == 1, (
        "distributed sim needs uniform partitions; build with "
        "to_dcsr(..., uniform=True)"
    )
    n_p = n_ps.pop()
    ells = [
        build_delay_ell(p, net.n, align_k=cfg.align_k,
                        align_rows=cfg.align_rows, max_k=None)
        for p in net.parts
    ]
    devs = [
        partition_device_data(p, net, e) for p, e in zip(net.parts, ells)
    ]
    delays = sorted({d for e in ells for d in (b.delay for b in e.buckets)})
    R = max(
        [c.shape[0] for dv in devs for c in dv.cols]
        + [((n_p + cfg.align_rows - 1) // cfg.align_rows) * cfg.align_rows]
    )
    any_plastic = any(d.any_plastic for d in devs)
    cols, weights, plastic, valid = [], [], [], []
    for d in delays:
        K = max(
            (dv.cols[dv.delays.index(d)].shape[1]
             for dv in devs if d in dv.delays),
            default=cfg.align_k,
        )
        c_stack, w_stack, p_stack, v_stack = [], [], [], []
        for dv in devs:
            if d in dv.delays:
                i = dv.delays.index(d)
                c, w, v = dv.cols[i], dv.weights0[i], dv.valid[i]
                pl_ = (dv.plastic[i] if dv.plastic
                       else np.zeros(c.shape, np.float32))
                pr, pk = R - c.shape[0], K - c.shape[1]
                pad = lambda a, pr=pr, pk=pk: np.pad(  # noqa: E731
                    a, ((0, pr), (0, pk))
                )
                c, w, v = pad(c), pad(w), pad(v)
                pl_ = pad(pl_) if any_plastic else None
            else:
                c = np.zeros((R, K), np.int32)
                w = np.zeros((R, K), np.float32)
                pl_ = np.zeros((R, K), np.float32)
                v = np.zeros((R, K), np.float32)
            c_stack.append(c)
            w_stack.append(w)
            p_stack.append(pl_)
            v_stack.append(v)
        cols.append(np.stack(c_stack))
        weights.append(np.stack(w_stack))
        if any_plastic:
            plastic.append(np.stack(p_stack))
        valid.append(np.stack(v_stack))
    return StackedNet(
        n_p=n_p, k=net.k, delays=tuple(delays),
        cols=cols, weights=weights,
        plastic=plastic, valid=valid,
        vtx_model=np.stack([d.vtx_model for d in devs]),
        vtx_state0=np.stack([d.vtx_state0 for d in devs]),
        any_plastic=any_plastic,
        d_ring=max(max(delays, default=1), 1),
        identity_rows=all(
            b.identity_rows for e in ells for b in e.buckets
        ),
    )


def split_overlap_panels(
    s: StackedNet, align_k: int
) -> Tuple[List[np.ndarray], List[np.ndarray],
           List[np.ndarray], List[np.ndarray]]:
    """Split each stacked synapse panel by column ownership for the
    overlap engines (non-plastic only — plastic weights are state and the
    panels stay whole).

    Local panels hold LOCAL column ids (``global - p*n_p``) so the local
    gather reads the own ``(n_p,)`` spike vector before any collective;
    remote panels keep global ids and reference only remote partitions,
    so the full exchanged vector can be gathered directly (padding slots
    point at col 0 with weight 0).  Packing is a stable argsort — the
    surviving entries keep their original panel order — with K padded to
    the max per-row count across rows and partitions, aligned up to
    ``align_k`` (uniform shapes: SPMD shards must match).

    Returns ``(cols_local, weights_local, cols_remote, weights_remote)``,
    each a per-delay list of ``(k, R, K_out)`` arrays.
    """
    align = lambda x: max(((x + align_k - 1) // align_k) * align_k, align_k)
    k, n_p = s.k, s.n_p
    own_lo = (np.arange(k) * n_p)[:, None, None]
    cols_l, w_l, cols_r, w_r = [], [], [], []
    for di in range(len(s.delays)):
        c = np.asarray(s.cols[di])
        w = np.asarray(s.weights[di])
        v = np.asarray(s.valid[di]) > 0
        is_local = v & (c >= own_lo) & (c < own_lo + n_p)
        for mask, out_c, out_w, localize in (
            (is_local, cols_l, w_l, True),
            (v & ~is_local, cols_r, w_r, False),
        ):
            order = np.argsort(~mask, axis=2, kind="stable")
            cs = np.take_along_axis(c, order, axis=2)
            ws = np.take_along_axis(w, order, axis=2)
            ms = np.take_along_axis(mask, order, axis=2)
            cnt = mask.sum(axis=2)  # (k, R)
            k_out = align(int(cnt.max()) if cnt.size else 0)
            if k_out > cs.shape[2]:
                pad = ((0, 0), (0, 0), (0, k_out - cs.shape[2]))
                cs, ws, ms = (np.pad(a, pad) for a in (cs, ws, ms))
            cs, ws, ms = cs[:, :, :k_out], ws[:, :, :k_out], ms[:, :, :k_out]
            if localize:
                cs = cs - own_lo
            out_c.append(np.where(ms, cs, 0).astype(np.int32))
            out_w.append(np.where(ms, ws, 0.0).astype(np.float32))
    return cols_l, w_l, cols_r, w_r


class DistSimulator:
    """k partitions over k devices (mesh axis 'parts').

    .. deprecated::
        ``DistSimulator`` is an internal engine behind
        :class:`repro.snn.Session` (the single supported entry point);
        importing it from ``repro.snn`` emits a ``DeprecationWarning``.
    """

    def __init__(self, net: DCSRNetwork,
                 cfg: Optional[SimConfig] = None,
                 mesh: Optional[Mesh] = None):
        cfg = SimConfig() if cfg is None else cfg
        self._compiled: Dict[int, object] = {}  # steps -> jitted fn
        self._device_args: Optional[Tuple] = None  # constants on the mesh
        self._sync_ells: Optional[List] = None  # per-part ELLs for sync
        self.net = net
        self.cfg = cfg
        self.dt = float(net.meta.get("dt", 0.1))
        self.noise_sigma = float(net.meta.get("noise_sigma", 0.0))
        self.stacked = stack_partitions(net, cfg)
        s = self.stacked
        k = s.k
        if mesh is None:
            assert len(jax.devices()) >= k, (
                f"need >= {k} devices for {k} partitions"
            )
            mesh = make_snn_mesh(k)
        self.mesh = mesh
        self.backend = resolve_sim_backend(cfg.backend)
        self.deliver_backend = resolve_delivery_backend(cfg.backend)
        self.stdp_params = (
            dict(net.registry.spec("syn_stdp").params)
            if s.any_plastic else None
        )
        # 'auto' resolves here: compressed index lists for non-plastic
        # k > 1 (collective bytes scale with spike counts, not partition
        # width), dense otherwise — plastic nets gather the real-valued
        # pre-trace vector densely anyway, so compressing only the spike
        # ids buys little (exchange='index' remains a supported override)
        self.exchange = cfg.exchange
        if self.exchange == "auto":
            self.exchange = (
                "index" if (k > 1 and not s.any_plastic) else "dense"
            )
        # effective per-partition id capacity of the index exchange (the
        # single source of the formula; Session's overflow warning reads
        # it back rather than re-deriving it)
        self.index_cap = (
            max(int(cfg.index_cap_frac * s.n_p), 8)
            if self.exchange == "index" else 0
        )
        # overlap 'auto' resolves to the concurrent local/remote gather
        # split only where it can pay off: the compiled pallas backend
        # (interpreted backends execute serially regardless, and keeping
        # them on the decomposition-free path preserves this container's
        # bit-exact baselines); explicit modes are honored everywhere —
        # the selector still vets eligibility
        self.overlap = cfg.overlap
        if self.overlap == "auto":
            self.overlap = "local" if self.backend == "pallas" else "off"
        self.n_global = k * s.n_p
        self.models_present = _models_present(net)
        self._base_key = jax.random.PRNGKey(cfg.seed)
        # engine selection is deterministic from construction-time facts;
        # computing it once here surfaces SimConfig(fused=True) eligibility
        # errors immediately, and _build_step reuses the same choice.
        # identity_exchange is a *placement* input: k == 1 dense is a true
        # identity (single fused kernel); anything else splits the fused
        # step at the collective
        sel_kw = dict(
            backend=self.backend,
            models_present=self.models_present,
            any_plastic=s.any_plastic and self.stdp_params is not None,
            identity_exchange=(k == 1 and self.exchange == "dense"),
            identity_rows=s.identity_rows,
            n_delay_buckets=len(s.delays),
            n_p=s.n_p,
            n_global=k * s.n_p,
            fused=cfg.fused,
            event_cap_frac=cfg.event_cap_frac,
            overlap=self.overlap,
        )
        self.engine_choice = select_step_engine(
            gather="dense" if cfg.gather == "auto" else cfg.gather,
            **sel_kw,
        )
        require_compilable(
            self.backend, self.engine_choice, sel_kw["any_plastic"]
        )
        self.delivery = delivery_path(
            self.engine_choice, self.deliver_backend, self.n_global
        )
        self.event_capable = _probe_event_capable(**sel_kw)
        # the non-plastic overlap engines gather build-time ownership
        # sub-panels; plastic panels stay whole (weights are state)
        self._overlap_panels = None
        if (self.engine_choice.overlap != "off"
                and not self.engine_choice.plastic):
            self._overlap_panels = split_overlap_panels(s, cfg.align_k)
        # static schedule of the event engines: one row-block geometry for
        # the whole stack (uniform partitions share R and the K widths) and
        # per-partition touch bitmaps stacked on the parts axis — the local
        # shard is rebound inside shard_map like the synapse panels
        self.event_cap = event_id_cap(self.n_global, cfg.event_cap_frac)
        self._event_touch: Optional[List[np.ndarray]] = None
        if self.engine_choice.event:
            R = s.cols[0].shape[1]
            k_widths = [c.shape[2] for c in s.cols]
            self._event_block_r, self._event_nb = event_block_geometry(
                R, k_widths, s.d_ring,
                interpret=self.backend != "pallas",
            )
            self._event_touch = [
                np.stack([
                    build_touch_masks(
                        [s.cols[di][p]], [s.valid[di][p]], self.n_global,
                        self._event_nb, self._event_block_r,
                    )[0]
                    for p in range(k)
                ])
                for di in range(len(s.delays))
            ]

    # -- state ------------------------------------------------------------
    def init_state(self, t0: int = 0) -> Dict:
        s = self.stacked
        k, n_p, D = s.k, s.n_p, s.d_ring
        with obs.span(obs.BUILD_PLACE):
            return dict(
                t=jnp.asarray(t0, jnp.int32),
                vtx_state=jnp.asarray(s.vtx_state0),
                ring=jnp.zeros((k, D, n_p), jnp.float32),
                hist=jnp.zeros((k, D, n_p), jnp.uint8),
                weights=tuple(jnp.asarray(w) for w in s.weights),
                tr_plus=jnp.zeros((k, n_p), jnp.float32),
                tr_minus=jnp.zeros((k, n_p), jnp.float32),
            )

    def _specs(self):
        """PartitionSpecs for the carry pytree (leading axis = parts,
        t replicated)."""
        return dict(
            t=P(),
            vtx_state=P("parts"),
            ring=P("parts"),
            hist=P("parts"),
            weights=tuple(P("parts") for _ in self.stacked.delays),
            tr_plus=P("parts"),
            tr_minus=P("parts"),
        )

    def _exchange(self):
        s = self.stacked
        n_p, n = s.n_p, self.n_global
        if self.exchange == "dense":
            def ex(spikes, tr_plus):
                if self.stdp_params is not None:
                    # one collective, not two: spikes and pre-traces ride
                    # the same all_gather as a (2, n_p) stack
                    both = jax.lax.all_gather(
                        jnp.stack([spikes, tr_plus]), "parts",
                        tiled=True, axis=1,
                    )
                    return both[0], both[1], jnp.zeros((), jnp.int32)
                act = jax.lax.all_gather(
                    spikes, "parts", tiled=True
                )
                return act, act, jnp.zeros((), jnp.int32)
            return ex, 0
        cap = self.index_cap

        def ex(spikes, tr_plus):
            idx = jnp.nonzero(spikes, size=cap, fill_value=-1)[0]
            p = jax.lax.axis_index("parts")
            gidx = jnp.where(idx >= 0, idx + p * n_p, n)
            all_idx = jax.lax.all_gather(
                gidx, "parts", tiled=True
            )  # (k*cap,)
            act = jnp.zeros((n,), jnp.float32).at[all_idx].set(
                1.0, mode="drop"
            )
            # local spikes past the capacity never made it into gidx —
            # count them so the lossy exchange is surfaced, not silent
            overflow = (
                jnp.sum(spikes > 0).astype(jnp.int32)
                - jnp.sum(idx >= 0).astype(jnp.int32)
            )
            if self.stdp_params is not None:
                # plastic nets: the pre-trace vector is real-valued and
                # needed densely, so it all-gathers alongside the
                # compressed spike ids (STDP sees the same truncated
                # activity as propagation — fused and unfused agree)
                pre = jax.lax.all_gather(tr_plus, "parts", tiled=True)
            else:
                pre = act
            return act, pre, overflow
        return ex, cap

    def _build_step(self, dev_template, noise_ids, event_plan=None):
        exchange, cap = self._exchange()
        s = self.stacked
        core = make_core_step(
            event_plan=event_plan,
            registry=self.net.registry,
            models_present=self.models_present,
            dt=self.dt,
            noise_sigma=self.noise_sigma,
            base_key=self._base_key,
            d_ring=s.d_ring,
            n_global=self.n_global,
            dev=dev_template,
            backend=self.backend,
            deliver_backend=self.deliver_backend,
            stdp_params=self.stdp_params,
            exchange=exchange,
            noise_ids=noise_ids,
            record_raster=self.cfg.record_raster,
            record_v=self.cfg.record_v,
            engine_choice=self.engine_choice,
            overlap_ctx=(
                self._overlap_ctx()
                if self.engine_choice.overlap != "off" else None
            ),
        )
        return core, cap

    def _overlap_ctx(self):
        """Partition-geometry closures for the overlap engines (run inside
        shard_map, where ``axis_index('parts')`` is live)."""
        s = self.stacked
        n_p, n = s.n_p, self.n_global
        cap = self.index_cap
        if self.exchange == "index":
            def local(spikes):
                # mirror the collective's cap truncation so the local
                # pass delivers exactly the activity the exchange would
                # have scattered for this partition
                idx = jnp.nonzero(spikes, size=cap, fill_value=-1)[0]
                return jnp.zeros((n_p,), jnp.float32).at[
                    jnp.where(idx >= 0, idx, n_p)
                ].set(1.0, mode="drop")
        else:
            def local(spikes):
                return spikes

        def embed(v):
            p = jax.lax.axis_index("parts")
            return jax.lax.dynamic_update_slice(
                jnp.zeros((n,), v.dtype), v, (p * n_p,)
            )

        def mask_remote(act):
            p = jax.lax.axis_index("parts")
            return jax.lax.dynamic_update_slice(
                act, jnp.zeros((n_p,), act.dtype), (p * n_p,)
            )

        return dict(local=local, embed=embed, mask_remote=mask_remote)

    def lower(self, steps: int):
        """Dry-run path: lower+compile the distributed step without
        touching device memory (ShapeDtypeStruct arguments) — the SNN
        analogue of launch/dryrun.py's transformer cells."""
        sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        state_sds = jax.eval_shape(self.init_state)
        fn, args = self._build_run(steps)
        return jax.jit(fn).lower(
            *[jax.tree.map(sds, a) for a in args], state_sds
        )

    def run(self, state: Dict, steps: int):
        """scan(steps) entirely inside shard_map; returns (state, outs) with
        outs['spike_count'] of shape (steps, k).  The jitted program is
        cached per ``steps`` so chunked callers (Session.run) compile each
        chunk length once instead of on every call."""
        if steps not in self._compiled:
            fn, args = self._build_run(steps)
            if self._device_args is None:
                # the constants go to their devices once, partition p to
                # mesh device p, and are reused by every chunk program
                with obs.span(obs.BUILD_PLACE):
                    self._device_args = jax.device_put(
                        args, NamedSharding(self.mesh, P("parts"))
                    )
            self._compiled[steps] = jax.jit(fn)
        return self._compiled[steps](*self._device_args, state)

    def _build_run(self, steps: int):
        s = self.stacked
        specs = self._specs()
        out_carry_specs = specs
        out_specs = dict(
            spike_count=P(None, "parts"), overflow=P(None, "parts")
        )
        if self.cfg.record_raster:
            out_specs["raster"] = P(None, "parts")
        if self.cfg.record_v:
            out_specs["v_mean"] = P(None, "parts")

        from .simulator import PartitionDeviceData

        def local_run(vtx_model, noise_ids, cols, plastic, touch, opan,
                      carry):
            nd = len(s.delays)
            local_carry = dict(
                t=carry["t"],
                vtx_state=carry["vtx_state"][0],
                ring=carry["ring"][0],
                hist=carry["hist"][0],
                weights=tuple(w[0] for w in carry["weights"]),
                tr_plus=carry["tr_plus"][0],
                tr_minus=carry["tr_minus"][0],
            )
            dev = PartitionDeviceData(
                n_p=s.n_p, row_start=0,
                vtx_model=vtx_model[0],
                vtx_state0=carry["vtx_state"][0],
                delays=s.delays,
                cols=[c[0] for c in cols],
                weights0=list(local_carry["weights"]),
                plastic=[p_[0] for p_ in plastic],
                valid=[],
                row_maps=[
                    jnp.arange(c.shape[1], dtype=jnp.int32) for c in cols
                ],
                identity_rows=tuple(True for _ in s.delays),
                any_plastic=s.any_plastic,
                **(
                    dict(
                        cols_local=[a[0] for a in opan[0 * nd:1 * nd]],
                        weights_local=[a[0] for a in opan[1 * nd:2 * nd]],
                        cols_remote=[a[0] for a in opan[2 * nd:3 * nd]],
                        weights_remote=[a[0] for a in opan[3 * nd:4 * nd]],
                    )
                    if opan else {}
                ),
            )
            plan = None
            if self._event_touch is not None:
                plan = EventPlan(
                    self._event_block_r, self._event_nb, self.event_cap,
                    [tc[0] for tc in touch],
                )
            step, _ = self._build_step(dev, noise_ids[0], event_plan=plan)
            if self.engine_choice.overlap == "double_buffer":
                # the deferred remote contribution lives ONLY inside the
                # scan carry: seeded empty here, flushed right after, so
                # the external carry pytree (checkpoints, reshard) never
                # sees it and chunk boundaries lose no spikes
                local_carry["_pending"] = step.pending_init()
            final, outs = jax.lax.scan(step, local_carry, None, length=steps)
            if self.engine_choice.overlap == "double_buffer":
                final = step.pending_flush(final)
            new_carry = dict(
                t=final["t"],
                vtx_state=final["vtx_state"][None],
                ring=final["ring"][None],
                hist=final["hist"][None],
                weights=tuple(w[None] for w in final["weights"]),
                tr_plus=final["tr_plus"][None],
                tr_minus=final["tr_minus"][None],
            )
            new_outs = dict(
                spike_count=outs["spike_count"][:, None],
                overflow=outs["overflow"][:, None],
            )
            if self.cfg.record_raster:
                new_outs["raster"] = outs["raster"][:, None]
            if self.cfg.record_v:
                new_outs["v_mean"] = outs["v_mean"][:, None]
            return new_carry, new_outs

        shmapped = shard_map(
            local_run,
            mesh=self.mesh,
            in_specs=(
                P("parts"),
                P("parts"),
                [P("parts")] * len(s.delays),
                [P("parts")] * len(s.plastic),
                [P("parts")] * (
                    len(self._event_touch)
                    if self._event_touch is not None else 0
                ),
                [P("parts")] * (
                    4 * len(s.delays)
                    if self._overlap_panels is not None else 0
                ),
                specs,
            ),
            out_specs=(out_carry_specs, out_specs),
            check_vma=False,
        )
        # host numpy: run() places them on the mesh once; lower() maps
        # them to ShapeDtypeStructs without any device allocation
        noise_ids = np.stack(
            [p.global_ids.astype(np.int32) for p in self.net.parts]
        )
        opan = (
            [a for group in self._overlap_panels for a in group]
            if self._overlap_panels is not None else []
        )
        args = (s.vtx_model, noise_ids, list(s.cols), list(s.plastic),
                list(self._event_touch)
                if self._event_touch is not None else [],
                opan)
        return shmapped, args

    # -- dCSR sync ---------------------------------------------------------
    def state_to_dcsr(self, state: Dict) -> None:
        """Write distributed state back into the dCSR partitions (host),
        in place — callers that hand the partitions to a background
        writer must snapshot-copy first (``io.dcsr_binary
        .snapshot_network``).  The per-partition ELL index structures are
        built once and cached: they depend only on topology, and
        rebuilding them dominated checkpoint stall on the old
        every-save path."""
        s = self.stacked
        if self._sync_ells is None:
            self._sync_ells = [
                build_delay_ell(
                    part, self.net.n, align_k=self.cfg.align_k,
                    align_rows=self.cfg.align_rows,
                )
                for part in self.net.parts
            ]
        vtx = np.asarray(state["vtx_state"])
        weights = [np.asarray(w) for w in state["weights"]]
        for p_i, (part, ell) in enumerate(
            zip(self.net.parts, self._sync_ells)
        ):
            part.vtx_state = vtx[p_i, : part.n]
            new_w = []
            for b in ell.buckets:
                di = s.delays.index(b.delay)
                R, K = b.weights.shape
                new_w.append(weights[di][p_i, :R, :K])
            ell.update_bucket_weights(new_w)
            ell.scatter_weights_back(part)

    def runtime_state(self, state: Dict) -> Dict[int, Dict[str, np.ndarray]]:
        """In-flight runtime arrays (ring/hist/traces) keyed per partition —
        the serialization side-channel next to the dCSR snapshot.  The
        arrays may be zero-copy views of device buffers; the snapshot
        layer copies them before any background write."""
        from .reshard import stack_runtime

        return stack_runtime(state, self.stacked.k)
