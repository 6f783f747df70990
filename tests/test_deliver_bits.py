"""Spike delivery over packed spike bits (``kernels/spike_gather.py``) in
interpret mode against the XLA oracle ``spike_gather_ref``: the kernel
alone, and whole trajectories of the step a TPU runs by default (the XLA
step with this kernel delivering every delay bucket) at k = 1 and k = 2."""
import numpy as np
import jax.numpy as jnp
import pytest

from helpers import run_with_devices
from repro.kernels import dispatch, ops, ref
from repro.kernels.spike_gather import pack_spikes, packed_rows
from repro.snn import Session, SimConfig, balanced_ei, to_dcsr
from repro.snn import simulator
from repro.snn.monitors import RasterMonitor


def _panel(rng, n, R, K, rate=0.2, pad=0.0):
    act = (rng.random(n) < rate).astype(np.float32)
    cols = rng.integers(0, n, (R, K)).astype(np.int32)
    w = rng.normal(size=(R, K)).astype(np.float32)
    if pad:
        # the layout's padding slots: id 0, weight 0
        holes = rng.random((R, K)) < pad
        cols[holes], w[holes] = 0, 0.0
    return act, cols, w


def _deliver(act, cols, w, **kw):
    return np.asarray(ops.spike_gather(
        jnp.asarray(act), jnp.asarray(cols), jnp.asarray(w),
        backend="pallas_interpret", **kw,
    ))


def _oracle(act, cols, w):
    return np.asarray(ref.spike_gather_ref(
        jnp.asarray(act), jnp.asarray(cols), jnp.asarray(w)
    ))


# f32 sums of the same terms in another order
TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,R,K", [
    (77, 16, 128),      # n under one word row, not a multiple of 32
    (1000, 40, 256),    # R not a multiple of the row group
    (4097, 24, 1536),   # two word rows, one neuron into the second
    (5000, 8, 384),     # three lane chunks
    (300, 10, 100),     # K off the 128-lane tile
])
def test_delivery_matches_oracle(n, R, K):
    act, cols, w = _panel(np.random.default_rng(n + K), n, R, K)
    np.testing.assert_allclose(_deliver(act, cols, w), _oracle(act, cols, w),
                               **TOL)


@pytest.mark.parametrize("block_r,block_k", [(8, 128), (16, 256), (None, None)])
def test_delivery_blocks(block_r, block_k):
    """Every block shape gives the same currents: rows and lanes tiled
    through the grid, the K blocks accumulated in the output block."""
    act, cols, w = _panel(np.random.default_rng(3), 9000, 48, 512)
    np.testing.assert_allclose(
        _deliver(act, cols, w, block_r=block_r, block_k=block_k),
        _oracle(act, cols, w), **TOL,
    )


@pytest.mark.parametrize("activity", ["silent", "all", "last"])
def test_delivery_extreme_activity(activity):
    n, R, K = 4500, 16, 256
    act, cols, w = _panel(np.random.default_rng(7), n, R, K, pad=0.3)
    act[:] = 1.0 if activity == "all" else 0.0
    if activity == "last":
        # the last neuron is the top bit of its word: the sign bit
        act[n - 1] = 1.0
        cols[0, 0] = n - 1
        act[31] = 1.0
    got = _deliver(act, cols, w)
    np.testing.assert_allclose(got, _oracle(act, cols, w), **TOL)
    if activity == "silent":
        assert not got.any()
    if activity == "all":
        np.testing.assert_allclose(got, w.sum(axis=1), **TOL)


def test_delivery_padding_slots_add_nothing():
    """Padding slots (id 0, weight 0) add nothing, whether or not neuron 0
    fired."""
    act, cols, w = _panel(np.random.default_rng(11), 700, 24, 256, pad=0.6)
    for fired in (0.0, 1.0):
        act[0] = fired
        np.testing.assert_allclose(_deliver(act, cols, w),
                                   _oracle(act, cols, w), **TOL)


def test_delivery_with_row_maps():
    """Heavy-row split panels: the kernel's per-ELL-row currents reduce
    through ``row_maps`` as the oracle's do."""
    import jax

    act, cols, w = _panel(np.random.default_rng(5), 600, 32, 128)
    row_map = np.repeat(np.arange(16), 2).astype(np.int32)  # 2 ELL rows each
    got = jax.ops.segment_sum(jnp.asarray(_deliver(act, cols, w)), row_map,
                              num_segments=16)
    want = jax.ops.segment_sum(jnp.asarray(_oracle(act, cols, w)), row_map,
                               num_segments=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("n", [1, 31, 32, 4096, 4097, 77169])
def test_pack_spikes_layout(n):
    """Neuron i is bit i & 31 of word i >> 5, at row word >> 7, lane
    word & 127, in (S, 128) int32 words; nothing past n is set."""
    rng = np.random.default_rng(n)
    act = (rng.random(n) < 0.5).astype(np.float32)
    words = np.asarray(pack_spikes(jnp.asarray(act)))
    assert words.shape == (packed_rows(n), 128) and words.dtype == np.int32
    flat = words.reshape(-1).view(np.uint32)
    bits = (flat[:, None] >> np.arange(32, dtype=np.uint32)) & 1
    bits = bits.reshape(-1)
    np.testing.assert_array_equal(bits[:n], act.astype(np.uint32))
    assert not bits[n:].any()


@pytest.mark.parametrize("backend", ["pallas_interpret", "ref"])
@pytest.mark.parametrize("K", [128, 200])
def test_fired_slots_for_stdp(backend, K):
    """With ``fired`` the op also returns each slot's presynaptic spike,
    exactly ``activity[cols]``, in the panel's own shape."""
    act, cols, w = _panel(np.random.default_rng(K), 2000, 24, K, pad=0.2)
    cur, fired = ops.spike_gather_bits(
        pack_spikes(jnp.asarray(act)), jnp.asarray(cols), jnp.asarray(w),
        backend=backend, fired=True,
    )
    np.testing.assert_array_equal(np.asarray(fired), act[cols])
    np.testing.assert_allclose(np.asarray(cur), _oracle(act, cols, w), **TOL)


@pytest.mark.parametrize("backend", ["pallas_interpret", "ref"])
def test_bits_op_reads_packed_words(backend):
    """The op a step calls per panel, on words packed once, against the
    oracle on the unpacked activity."""
    act, cols, w = _panel(np.random.default_rng(2), 3000, 16, 256)
    words = pack_spikes(jnp.asarray(act))
    for c, ww in ((cols, w), (cols[:, ::-1].copy(), w * 2)):
        got = ops.spike_gather_bits(words, jnp.asarray(c), jnp.asarray(ww),
                                    backend=backend)
        np.testing.assert_allclose(np.asarray(got), _oracle(act, c, ww), **TOL)


# -- the step a TPU runs by default, in interpret mode --------------------

STEPS = 50


def _brunel():
    net = balanced_ei(160, stdp=True, seed=7, delay_steps=5)
    net.vtx_state[:, 2] += 6.0  # drive real activity through STDP
    return net


def _tpu_default_delivery(monkeypatch):
    """Steer the simulators' delivery to the kernel (interpret mode) with
    every other op on the XLA 'ref' path: the TPU default's composition."""
    monkeypatch.setattr(simulator, "resolve_delivery_backend",
                        lambda backend=None: "pallas_interpret")


def _run(net, cfg):
    ses = Session(net, cfg)
    ras = RasterMonitor()
    ses.run(STEPS, monitors=[ras], chunk_size=25)
    return ses, ras.raster


def test_default_step_trajectory_k1(monkeypatch):
    """Brunel + STDP at k = 1: the XLA step with the packed-bit delivery
    against the all-XLA oracle step, 50 steps: equal rasters, membranes
    within 1e-4 mV, weights within f32 rounding."""
    ses_o, ras_o = _run(to_dcsr(_brunel(), k=1), SimConfig(backend="ref"))
    _tpu_default_delivery(monkeypatch)
    ses_b, ras_b = _run(to_dcsr(_brunel(), k=1), SimConfig())
    assert ses_o.describe()["delivery"] == {"kernel": "xla_take"}
    assert ses_b.describe()["delivery"] == {"kernel": "pallas_bits",
                                            "words": 5}
    assert ses_b.describe()["backend"] == "ref"
    assert ras_o.sum() > 30, "test net too quiet for a meaningful check"
    np.testing.assert_array_equal(ras_b, ras_o)
    vo = np.asarray(ses_o.state["vtx_state"])[:, 0]
    vb = np.asarray(ses_b.state["vtx_state"])[:, 0]
    assert np.abs(vb - vo).max() <= 1e-4
    for wb, wo in zip(ses_b.state["weights"], ses_o.state["weights"]):
        np.testing.assert_allclose(np.asarray(wb), np.asarray(wo),
                                   rtol=1e-6, atol=1e-6)


DIST = """
import numpy as np
from repro.core import block_partition
from repro.snn import Session, SimConfig, balanced_ei, to_dcsr
from repro.snn import dist_sim
from repro.snn.monitors import RasterMonitor

def run(cfg):
    net = balanced_ei(160, stdp=True, seed=7, delay_steps=5)
    net.vtx_state[:, 2] += 6.0
    ses = Session(to_dcsr(net, assignment=block_partition(160, 2),
                          uniform=True), cfg)
    assert ses.engine_kind == "spmd", ses.describe()
    ras = RasterMonitor()
    ses.run(50, monitors=[ras], chunk_size=25)
    return ses, ras.raster

ses_o, ras_o = run(SimConfig(backend="ref", exchange="{exchange}"))
# the TPU default: every op on XLA but the delivery kernel
dist_sim.resolve_delivery_backend = lambda backend=None: "pallas_interpret"
ses_b, ras_b = run(SimConfig(exchange="{exchange}"))
assert ses_o.describe()["delivery"] == {{"kernel": "xla_take"}}
assert ses_b.describe()["delivery"] == {{"kernel": "pallas_bits", "words": 5}}
assert ses_b.describe()["step_engine"] == "unfused"
assert ras_o.sum() > 30, ras_o.sum()
assert np.array_equal(ras_b, ras_o), "raster diverged"
vo = np.asarray(ses_o.state["vtx_state"])[..., 0]
vb = np.asarray(ses_b.state["vtx_state"])[..., 0]
assert np.abs(vb - vo).max() <= 1e-4, np.abs(vb - vo).max()
print("DIST BITS OK", int(ras_o.sum()))
"""


@pytest.mark.parametrize("exchange", ["dense", "index"])
def test_default_step_trajectory_k2(exchange):
    """The same at k = 2 on two host devices, over the dense and the
    index-decompressed exchange (0/1 activity either way)."""
    out = run_with_devices(DIST.format(exchange=exchange), n_devices=2)
    assert "DIST BITS OK" in out


# -- which path delivers ---------------------------------------------------

def test_describe_names_the_delivery(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    net = to_dcsr(_brunel(), k=1)
    assert Session(net, SimConfig())\
        .describe()["delivery"] == {"kernel": "xla_take"}
    assert Session(net, SimConfig(backend="pallas_interpret", fused=False))\
        .describe()["delivery"] == {"kernel": "pallas_bits", "words": 5}
    fused = Session(net, SimConfig(backend="pallas_interpret"))
    assert fused.describe()["delivery"] == {
        "kernel": fused.describe()["step_engine"]
    }


def test_delivery_backend_follows_the_platform(monkeypatch):
    """On a TPU the default step delivers through the compiled kernel; an
    explicit backend (argument or REPRO_BACKEND) is taken as given, so
    backend='ref' stays the all-XLA oracle step."""
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert dispatch.resolve_delivery_backend() == "ref"  # not a TPU
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    dispatch._platform_default.cache_clear()
    try:
        assert dispatch.resolve_delivery_backend() == "pallas"
        assert dispatch.resolve_sim_backend() == "ref"
        assert dispatch.resolve_delivery_backend("ref") == "ref"
        monkeypatch.setenv("REPRO_BACKEND", "ref")
        assert dispatch.resolve_delivery_backend() == "ref"
    finally:
        dispatch._platform_default.cache_clear()
