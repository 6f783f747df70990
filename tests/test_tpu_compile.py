"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with jaxlib, so these tests lower and
compile at the real widths of the scale-1.0 microcircuit (n = 77,169)
and catch what interpret mode cannot: a kernel the chip's compiler
refuses, or a step program that does not fit the chip's 16 GB.  Nothing
runs; a compile that passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.builder.rules import microcircuit_rules
from repro.core.state import default_registry
from repro.kernels import ops
from repro.kernels import dispatch
from repro.kernels.dispatch import (
    resolve_delivery_backend, resolve_sim_backend, select_step_engine,
)
from repro.kernels.keystream import _keystream_call
from repro.kernels.spike_gather import pack_spikes
from repro.snn.neurons import LIF_PARAM_KEYS, registry_with_bias
from repro.snn.simulator import PartitionDeviceData, make_core_step

HBM_BYTES = 16 * 10**9  # one v5e chip
N = 77169  # scale-1.0 microcircuit
N_BRUNEL = 12500  # Brunel 2000 at the published size: 128-wide panels
GATHER_REFUSED = "Only 2D gather is supported"
DELIVERY_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 -- any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache, so keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the platform default to a TPU's, so the simulators' default
    step is the one a TPU runs (its delivery kernel compiled, not
    interpreted); the compile itself still targets the described chip."""
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    dispatch._platform_default.cache_clear()
    yield
    dispatch._platform_default.cache_clear()


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _lif_params():
    lif = registry_with_bias(default_registry()).spec("lif").params
    return {"dt": 0.1, **{k: lif[k] for k in LIF_PARAM_KEYS}}


def _scale1_ell():
    """(R, {delay: K}) of the scale-1.0 microcircuit's delay-bucketed ELL:
    a ``p`` rule gives each target row int(p * n_src) or one more
    sources, so each bucket's width is the largest such sum over target
    populations, lane-aligned (an upper bound of the built width by at
    most one 128-lane tile)."""
    spec = microcircuit_rules(scale=1.0)
    offs = spec.offsets()
    fan = {}
    for rule in spec.rules:
        n_src = offs[rule.src][1] - offs[rule.src][0]
        key = (rule.delay, rule.dst)
        fan[key] = fan.get(key, 0) + int(rule.p * n_src) + 1
    widths = {}
    for (delay, _), k in fan.items():
        widths[delay] = max(widths.get(delay, 0), -(-k // 128) * 128)
    return -(-spec.n // 8) * 8, dict(sorted(widths.items()))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _device_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


def test_lif_step_kernel_compiles(shape):
    params = _lif_params()
    c = _compile(
        lambda v, r, i: ops.lif_step(v, r, i, params=params,
                                     backend="pallas"),
        *[shape((N,))] * 3,
    )
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("traces", [False, True])
def test_fused_pre_exchange_kernel_compiles(shape, traces):
    params = _lif_params()
    n_p = 19293  # one partition of four

    def fn(*vecs):
        taus = (20.0, 20.0) if traces else None
        return ops.fused_pre_exchange(
            *vecs, params=params, taus=taus, backend="pallas"
        )

    c = _compile(fn, *[shape((n_p,))] * (5 if traces else 3))
    assert "tpu_custom_call" in c.as_text()


def _gather_cases(shape):
    """Every synapse kernel at scale-1.0 widths (k=1 panels; the split
    kernels at one 128-aligned partition of four)."""
    R, widths = _scale1_ell()
    ks = tuple(widths.values())
    params = _lif_params()
    stdp = dict(registry_with_bias(default_registry()).spec("syn_stdp").params)
    cols = [shape((R, k), jnp.int32) for k in ks]
    ws = [shape((R, k)) for k in ks]
    n_p, r_p, d = 19293, 19328, max(widths)
    pcols = [shape((r_p, k), jnp.int32) for k in ks]
    pws = [shape((r_p, k)) for k in ks]
    ring = [shape((4 * n_p,)), shape((d, n_p)), shape((d,)),
            shape((len(ks), d))]
    return {
        "stdp_update": (
            lambda w, v, c, pt, ps, qt, qs: ops.stdp_update(
                w, v, c, pt, ps, qt, qs, params=stdp, backend="pallas"),
            [ws[-1], ws[-1], cols[-1], shape((N,)), shape((N,)),
             shape((R,)), shape((R,))],
        ),
        "fused_step": (
            lambda v, r, i, *p: ops.fused_step(
                v, r, i, p[:len(ks)], p[len(ks):], params=params,
                backend="pallas"),
            [shape((N,))] * 3 + cols + ws,
        ),
        "fused_post_exchange": (
            lambda a, rg, cm, oh, *p: ops.fused_post_exchange(
                a, rg, cm, oh, p[:len(ks)], p[len(ks):], backend="pallas"),
            ring + pcols + pws,
        ),
    }


@pytest.mark.parametrize(
    "kernel", ["stdp_update", "fused_step", "fused_post_exchange"],
)
def test_synapse_kernels_are_refused(shape, kernel):
    """Pins the reason the TPU step is XLA's but for its delivery: Mosaic
    refuses the in-kernel 1-D gather of every other synapse kernel.  When
    this starts to compile, the Pallas engines can come back to the
    chip."""
    fn, args = _gather_cases(shape)[kernel]
    with pytest.raises(NotImplementedError, match=GATHER_REFUSED):
        _compile(fn, *args)


@pytest.mark.parametrize(
    "width", ["mc_first", "mc_last", "brunel", "brunel_stdp"]
)
def test_delivery_kernel_compiles(shape, width):
    """The spike delivery kernel over packed bits compiles for the v5e at
    the scale-1.0 microcircuit's panel widths and at Brunel's 128 (also
    writing each slot's presynaptic spike for STDP), as one named
    custom-call reading the panel's own int32 ids."""
    R, widths = _scale1_ell()
    n, K = {
        "mc_first": (N, min(widths.values())),
        "mc_last": (N, max(widths.values())),
        "brunel": (N_BRUNEL, 128),
        "brunel_stdp": (N_BRUNEL, 128),
    }[width]
    if n == N_BRUNEL:
        R = -(-n // 8) * 8
    fired = width == "brunel_stdp"

    def deliver(a, i, w):
        words = pack_spikes(a)
        return ops.spike_gather_bits(words, i, w, backend="pallas",
                                     fired=fired)

    c = _compile(deliver, shape((n,)), shape((R, K), jnp.int32),
                 shape((R, K)))
    calls = [line for line in c.as_text().splitlines()
             if DELIVERY_CALL in line]
    assert len(calls) == 1 and "snn_deliver_bits" in calls[0], calls
    assert f"s32[{R},{K}]" in calls[0]
    assert (f"f32[{R},{K}]" in calls[0].split("custom-call(")[0]) == fired


def test_builder_keystream_kernel_is_refused(shape):
    """Why the TPU build path is the host one (resolve_build_path)."""
    with pytest.raises(Exception, match="bitcast"):
        _compile(
            lambda p, r: _keystream_call(
                p, r, n_words=128, block_r=256, interpret=False),
            shape((3,), jnp.int32), shape((8192,), jnp.int32),
        )


def test_default_tpu_step_fits_one_chip(shape, on_tpu):
    """The engine a TPU runs by default (the XLA 'ref' step, its spikes
    delivered by the packed-bit kernel, one custom-call per delay bucket)
    compiles as a scan step over the scale-1.0 microcircuit's panels,
    inside 16 GB."""
    backend = resolve_sim_backend()
    deliver = resolve_delivery_backend()
    R, widths = _scale1_ell()
    delays = tuple(widths)
    d_ring = max(delays)
    reg = registry_with_bias(default_registry())
    choice = select_step_engine(
        backend=backend, models_present=("lif",), any_plastic=False,
        identity_exchange=True, identity_rows=True,
        n_delay_buckets=len(delays), n_p=N, n_global=N,
    )
    assert (backend, deliver, choice.engine) == ("ref", "pallas", "unfused")
    panels_i = [shape((R, k), jnp.int32) for k in widths.values()]
    panels_f = [shape((R, k)) for k in widths.values()]
    dev = PartitionDeviceData(
        n_p=N, row_start=0, vtx_model=shape((N,), jnp.int32),
        vtx_state0=shape((N, reg.max_vertex_state)), delays=delays,
        cols=panels_i, weights0=panels_f, plastic=[], valid=[],
        row_maps=[shape((R,), jnp.int32) for _ in delays],
        identity_rows=(True,) * len(delays), any_plastic=False,
    )
    state = dict(
        t=shape((), jnp.int32),
        vtx_state=shape((N, reg.max_vertex_state)),
        ring=shape((d_ring, N)), hist=shape((d_ring, N), jnp.uint8),
        weights=tuple(panels_f),
        tr_plus=shape((N,)), tr_minus=shape((N,)),
    )

    def run(dev, noise_ids, state):
        step = make_core_step(
            registry=reg, models_present=("lif",), dt=0.1,
            noise_sigma=1.0, base_key=jax.random.PRNGKey(0),
            d_ring=d_ring, n_global=N, dev=dev, backend=backend,
            stdp_params=None,
            exchange=lambda s, tr: (s, tr, jnp.zeros((), jnp.int32)),
            noise_ids=noise_ids, record_raster=True, engine_choice=choice,
            deliver_backend=deliver,
        )
        return jax.lax.scan(step, state, None, length=1)

    c = _compile(run, dev, shape((N,), jnp.int32), state)
    assert c.as_text().count(DELIVERY_CALL) == len(delays)
    assert _device_bytes(c) < HBM_BYTES, c.memory_analysis()


def test_spmd_step_compiles_for_four_chips(topo, on_tpu):
    """The k=4 SPMD engine's chunk program, partitioned over a 2x2 mesh of
    described chips, with its spike exchange as a collective and its
    delivery kernel once per delay bucket."""
    from jax.sharding import AxisType, Mesh

    from repro.builder.procedural import build_network
    from repro.snn.dist_sim import DistSimulator
    from repro.snn.simulator import SimConfig

    mesh = Mesh(np.array(topo.devices), ("parts",),
                axis_types=(AxisType.Auto,))
    net = build_network(microcircuit_rules(scale=0.02), k=4, uniform=True)
    sim = DistSimulator(net, SimConfig(), mesh=mesh)
    assert (sim.backend, sim.engine_choice.engine) == ("ref", "unfused")
    assert sim.delivery["kernel"] == "pallas_bits"
    text = sim.lower(10).compile().as_text()
    assert "all-reduce" in text or "all-gather" in text
    assert text.count(DELIVERY_CALL) == len(sim.stacked.delays)
