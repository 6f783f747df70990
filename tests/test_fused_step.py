"""Fused step engine: kernel-level parity vs the pure-jnp oracle across
dtypes and non-aligned panel shapes, dispatcher backend/engine selection,
and end-to-end fused-vs-reference equivalence on the microcircuit config
(interpret mode — the TPU kernel body on CPU)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import dispatch, ops, ref
from repro.kernels.fused_step import fused_lif_step_pallas

LIF_PARAMS = dict(
    dt=0.1, tau_m=10.0, v_rest=-65.0, v_reset=-65.0, v_thresh=-50.0,
    t_ref=2.0, r_m=1.0,
)


def _random_case(rng, n_p, R, ks, dtype):
    v = (-65.0 + 20.0 * rng.random(n_p)).astype(np.float32)
    refrac = rng.integers(0, 3, n_p).astype(np.float32)
    i_tot = (8.0 * rng.random(n_p)).astype(np.float32)
    cols, weights = [], []
    for K in ks:
        c = rng.integers(0, n_p, (R, K)).astype(np.int32)
        w = rng.normal(size=(R, K)).astype(dtype)
        w[n_p:] = 0  # padded rows carry no synapses
        cols.append(jnp.asarray(c))
        weights.append(jnp.asarray(w))
    return (
        jnp.asarray(v), jnp.asarray(refrac), jnp.asarray(i_tot),
        tuple(cols), tuple(weights),
    )


@pytest.mark.parametrize("n_p,R,ks", [
    (64, 64, (16,)),  # aligned, single bucket
    (100, 104, (8, 24)),  # non-aligned rows, two buckets
    (37, 40, (4, 12, 20)),  # odd sizes, three buckets
])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_fused_kernel_matches_ref(rng, n_p, R, ks, dtype):
    dtype = jnp.bfloat16 if dtype == "bfloat16" else dtype
    v, refrac, i_tot, cols, weights = _random_case(rng, n_p, R, ks, dtype)
    v_r, r_r, s_r, cur_r = ref.fused_step_ref(
        v, refrac, i_tot, cols, weights, params=LIF_PARAMS
    )
    v_f, r_f, s_f, cur_f = fused_lif_step_pallas(
        v, refrac, i_tot, cols, weights, params=LIF_PARAMS, interpret=True
    )
    # f32 accumulation in both engines: bf16 only rounds on output
    tol = 1e-5 if dtype == np.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(v_f), np.asarray(v_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_f), np.asarray(r_r), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s_f), np.asarray(s_r))
    for a, b in zip(cur_f, cur_r):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol,
        )


@pytest.mark.parametrize("block_r", [1, 8, 64, 256])
def test_fused_kernel_block_sweep(rng, block_r):
    v, refrac, i_tot, cols, weights = _random_case(
        rng, 96, 96, (16, 32), np.float32
    )
    v_r, r_r, s_r, cur_r = ref.fused_step_ref(
        v, refrac, i_tot, cols, weights, params=LIF_PARAMS
    )
    v_f, r_f, s_f, cur_f = fused_lif_step_pallas(
        v, refrac, i_tot, cols, weights, params=LIF_PARAMS,
        block_r=block_r, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(v_f), np.asarray(v_r), atol=1e-5)
    for a, b in zip(cur_f, cur_r):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_ops_fused_step_ref_backend_matches_interpret(rng):
    v, refrac, i_tot, cols, weights = _random_case(
        rng, 50, 56, (8,), np.float32
    )
    out_ref = ops.fused_step(
        v, refrac, i_tot, cols, weights, params=LIF_PARAMS, backend="ref"
    )
    out_int = ops.fused_step(
        v, refrac, i_tot, cols, weights, params=LIF_PARAMS,
        backend="pallas_interpret",
    )
    np.testing.assert_array_equal(
        np.asarray(out_ref[2]), np.asarray(out_int[2])
    )


# -- split-engine kernels (pre/post exchange) ------------------------------

@pytest.mark.parametrize("n_p", [64, 100, 37])
@pytest.mark.parametrize("with_traces", [False, True])
def test_fused_pre_exchange_matches_ref(rng, n_p, with_traces):
    v = jnp.asarray((-65.0 + 20.0 * rng.random(n_p)).astype(np.float32))
    refrac = jnp.asarray(rng.integers(0, 3, n_p).astype(np.float32))
    i_tot = jnp.asarray((8.0 * rng.random(n_p)).astype(np.float32))
    args, kw = (v, refrac, i_tot), dict(params=LIF_PARAMS)
    if with_traces:
        args += (
            jnp.asarray(rng.random(n_p).astype(np.float32)),
            jnp.asarray(rng.random(n_p).astype(np.float32)),
        )
        kw["taus"] = (20.0, 15.0)
    out_r = ops.fused_pre_exchange(*args, backend="ref", **kw)
    out_p = ops.fused_pre_exchange(*args, backend="pallas_interpret", **kw)
    assert len(out_r) == len(out_p) == (5 if with_traces else 3)
    for a, b in zip(out_r, out_p):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        )


@pytest.mark.parametrize("slot,delays", [
    (0, (1,)),  # D = 1: clear and re-add the same slot
    (2, (1, 3)),
    (3, (1, 2, 4)),  # d == D wraps onto the cleared slot
])
def test_fused_post_exchange_matches_unfused_composition(rng, slot, delays):
    """ring rotate + all-bucket gathers in one pass == clear slot, then
    spike_gather + ring.at[(slot+d) % D].add per bucket."""
    n_global, n_p, R, K = 240, 60, 64, 16
    D = max(delays)
    slot = slot % D
    act = jnp.asarray((rng.random(n_global) < 0.2).astype(np.float32))
    ring = jnp.asarray(rng.normal(size=(D, n_p)).astype(np.float32))
    clear = (jnp.arange(D) != slot).astype(jnp.float32)
    onehot = (
        jnp.asarray([[(slot + d) % D] for d in delays])
        == jnp.arange(D)[None, :]
    ).astype(jnp.float32)
    cols, weights = [], []
    for _ in delays:
        c = rng.integers(0, n_global, (R, K)).astype(np.int32)
        w = rng.normal(size=(R, K)).astype(np.float32)
        w[n_p:] = 0  # padded rows carry no synapses
        cols.append(jnp.asarray(c))
        weights.append(jnp.asarray(w))

    expect = np.asarray(ring).copy()
    expect[slot] = 0.0
    for c, w, d in zip(cols, weights, delays):
        cur = np.asarray(ref.spike_gather_ref(act, c, w))[:n_p]
        expect[(slot + d) % D] += cur

    for backend in ("ref", "pallas_interpret"):
        got = ops.fused_post_exchange(
            act, ring, clear, onehot, cols, weights, backend=backend
        )
        assert got.shape == (D, n_p)
        np.testing.assert_allclose(
            np.asarray(got), expect, rtol=1e-5, atol=1e-5
        )


# -- plastic fused kernels (STDP folded into the panel pass) ---------------

STDP_PARAMS = dict(
    a_plus=0.01, a_minus=0.012, w_min=-2.0, w_max=2.0,
    tau_plus=20.0, tau_minus=15.0,
)


def _plastic_panels(rng, n_src, n_rows, R, ks):
    cols, weights, plastic = [], [], []
    for K in ks:
        c = rng.integers(0, n_src, (R, K)).astype(np.int32)
        w = rng.normal(size=(R, K)).astype(np.float32)
        w[n_rows:] = 0  # padded rows carry no synapses
        pm = (rng.random((R, K)) < 0.5).astype(np.float32)
        pm[n_rows:] = 0  # ...and no plastic slots
        cols.append(jnp.asarray(c))
        weights.append(jnp.asarray(w))
        plastic.append(jnp.asarray(pm))
    return cols, weights, plastic


@pytest.mark.parametrize("n_p,R,ks", [
    (64, 64, (16,)),  # aligned, single bucket
    (100, 104, (8, 24)),  # non-aligned rows, two buckets
    (37, 40, (4, 12, 20)),  # odd sizes, three buckets
])
def test_fused_plastic_kernel_matches_ref(rng, n_p, R, ks):
    """One launch: LIF + traces + gather + STDP == the composed oracles,
    bit-for-bit on spikes/traces and to f32 tolerance on v/currents."""
    v = jnp.asarray((-65.0 + 20.0 * rng.random(n_p)).astype(np.float32))
    refrac = jnp.asarray(rng.integers(0, 3, n_p).astype(np.float32))
    i_tot = jnp.asarray((18.0 * rng.random(n_p)).astype(np.float32))
    tp = jnp.asarray(rng.random(n_p).astype(np.float32))
    tm = jnp.asarray(rng.random(n_p).astype(np.float32))
    cols, weights, plastic = _plastic_panels(rng, n_p, n_p, R, ks)
    args = (v, refrac, i_tot, tp, tm, cols, weights, plastic)
    kw = dict(params=LIF_PARAMS, taus=(20.0, 15.0), stdp=STDP_PARAMS)
    out_r = ops.fused_step_plastic(*args, backend="ref", **kw)
    out_p = ops.fused_step_plastic(*args, backend="pallas_interpret", **kw)
    assert int(np.asarray(out_r[2]).sum()) > 0, "case emits no spikes"
    np.testing.assert_allclose(
        np.asarray(out_p[0]), np.asarray(out_r[0]), atol=1e-5
    )  # v (FMA-contraction tolerance, as for the non-plastic kernel)
    np.testing.assert_array_equal(
        np.asarray(out_p[2]), np.asarray(out_r[2])
    )  # spikes
    for i in (3, 4):  # traces
        np.testing.assert_allclose(
            np.asarray(out_p[i]), np.asarray(out_r[i]), atol=1e-6
        )
    for a, b in zip(out_p[5], out_r[5]):  # currents
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )
    for a, b, w0, pm in zip(out_p[6], out_r[6], weights, plastic):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )  # new weights
        # non-plastic slots froze exactly, in both engines
        frozen = np.asarray(pm) == 0
        np.testing.assert_array_equal(
            np.asarray(a)[frozen], np.asarray(w0)[frozen]
        )


@pytest.mark.parametrize("slot,delays", [
    (0, (1,)),
    (2, (1, 3)),
    (3, (1, 2, 4)),  # d == D wraps onto the cleared slot
])
def test_fused_post_exchange_plastic_matches_unfused_composition(
    rng, slot, delays
):
    """ring rotate + gathers + STDP in one pass == clear slot, then per
    bucket: spike_gather with PRE-update weights, ring add, stdp_update."""
    n_global, n_p, R, K = 240, 60, 64, 16
    D = max(delays)
    slot = slot % D
    act = jnp.asarray((rng.random(n_global) < 0.2).astype(np.float32))
    pre_trace = jnp.asarray(rng.random(n_global).astype(np.float32))
    ring = jnp.asarray(rng.normal(size=(D, n_p)).astype(np.float32))
    post_t = jnp.asarray(rng.random(n_p).astype(np.float32))
    post_s = jnp.asarray((rng.random(n_p) < 0.3).astype(np.float32))
    clear = (jnp.arange(D) != slot).astype(jnp.float32)
    onehot = (
        jnp.asarray([[(slot + d) % D] for d in delays])
        == jnp.arange(D)[None, :]
    ).astype(jnp.float32)
    cols, weights, plastic = _plastic_panels(
        rng, n_global, n_p, R, (K,) * len(delays)
    )

    expect_ring = np.asarray(ring).copy()
    expect_ring[slot] = 0.0
    expect_w = []
    pad_r = R - n_p
    for c, w, pm, d in zip(cols, weights, plastic, delays):
        cur = np.asarray(ref.spike_gather_ref(act, c, w))[:n_p]
        expect_ring[(slot + d) % D] += cur
        expect_w.append(np.asarray(ref.stdp_update_ref(
            w, pm, c, pre_trace, act,
            jnp.pad(post_t, (0, pad_r)), jnp.pad(post_s, (0, pad_r)),
            a_plus=STDP_PARAMS["a_plus"], a_minus=STDP_PARAMS["a_minus"],
            w_min=STDP_PARAMS["w_min"], w_max=STDP_PARAMS["w_max"],
        )))

    for backend in ("ref", "pallas_interpret"):
        got_ring, got_w = ops.fused_post_exchange_plastic(
            act, pre_trace, ring, clear, onehot, post_t, post_s,
            cols, weights, plastic, stdp=STDP_PARAMS, backend=backend,
        )
        assert got_ring.shape == (D, n_p)
        np.testing.assert_allclose(
            np.asarray(got_ring), expect_ring, rtol=1e-5, atol=1e-5
        )
        assert len(got_w) == len(expect_w)
        for a, b in zip(got_w, expect_w):
            np.testing.assert_allclose(
                np.asarray(a), b, rtol=1e-5, atol=1e-6
            )


# -- dispatcher -----------------------------------------------------------

def test_registry_has_all_backends():
    for op in (
        "spike_gather", "lif_step", "stdp_update", "fused_step",
        "fused_step_plastic", "fused_pre_exchange", "fused_post_exchange",
        "fused_post_exchange_plastic",
    ):
        assert dispatch.backends_for(op) == (
            "pallas", "pallas_interpret", "ref"
        ), op


def test_lookup_unknown_raises():
    with pytest.raises(KeyError, match="no implementation"):
        dispatch.lookup("no_such_op", "ref")
    with pytest.raises(KeyError, match="available"):
        dispatch.lookup("spike_gather", "tpu_v7")


def test_resolve_backend_precedence(monkeypatch):
    assert dispatch.resolve_backend("ref") == "ref"
    monkeypatch.setenv("REPRO_BACKEND", "ref")
    assert dispatch.resolve_backend() == "ref"
    assert dispatch.resolve_backend("pallas") == "pallas"  # flag wins
    monkeypatch.delenv("REPRO_BACKEND")
    assert dispatch.resolve_backend() == dispatch._platform_default()


def test_sim_backend_is_ref_on_a_tpu_platform(monkeypatch):
    """On TPU the simulators take the XLA-compiled 'ref' step; direct
    kernel calls keep the compiled Pallas platform default."""
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    dispatch._platform_default.cache_clear()
    try:
        assert dispatch.resolve_sim_backend() == "ref"
        assert dispatch.resolve_backend() == "pallas"
        assert dispatch.resolve_sim_backend("pallas") == "pallas"
    finally:
        dispatch._platform_default.cache_clear()


@pytest.mark.parametrize("fused", [None, False])
def test_compiled_pallas_backend_raises_at_session(fused):
    """An explicit compiled-Pallas backend is refused at construction,
    naming the compiler's limit, wherever the step holds a kernel the TPU
    compiler refuses: the fused engines, and the unfused engine's
    stdp_update pass.  Without STDP the unfused step compiles (its
    delivery kernel reads packed spike bits) and is built."""
    from repro.builder import balanced_ei_rules, microcircuit_rules
    from repro.snn import Session, SimConfig

    cfg = SimConfig(backend="pallas", fused=fused)
    with pytest.raises(ValueError, match="Only 2D gather is supported"):
        Session(balanced_ei_rules(n=200, stdp=True), cfg)
    if fused is None:
        with pytest.raises(ValueError, match="Only 2D gather is supported"):
            Session(microcircuit_rules(scale=0.01), cfg)
    else:
        ses = Session(microcircuit_rules(scale=0.01), cfg)
        assert ses.describe()["delivery"]["kernel"] == "pallas_bits"


ELIGIBLE = dict(
    backend="pallas", models_present=("lif",), any_plastic=False,
    identity_exchange=True, identity_rows=True, n_delay_buckets=2,
    n_p=1024,
)


def test_select_step_engine_auto():
    assert dispatch.select_step_engine(**ELIGIBLE).engine == "fused"
    # ref backend: XLA fuses the oracles already
    c = dispatch.select_step_engine(**{**ELIGIBLE, "backend": "ref"})
    assert c.engine == "unfused"
    # pallas_interpret validates the fused TPU path on CPU
    c = dispatch.select_step_engine(
        **{**ELIGIBLE, "backend": "pallas_interpret"}
    )
    assert c.engine == "fused"


def test_select_step_engine_exchange_is_placement_not_gate():
    """A non-identity exchange no longer blocks fusion — it selects the
    split engine (pre kernel, collective, post kernel)."""
    c = dispatch.select_step_engine(
        **{**ELIGIBLE, "identity_exchange": False}, n_global=4096
    )
    assert c.engine == "fused_split"
    assert c.fused and c.split
    assert "split at the exchange" in c.reason
    # identity exchange keeps the single-kernel engine
    one = dispatch.select_step_engine(**ELIGIBLE)
    assert one.engine == "fused" and one.fused and not one.split


@pytest.mark.parametrize("override,reason_part", [
    ({"models_present": ("lif", "alif")}, "heterogeneous"),
    ({"identity_rows": False}, "segment-sum"),
    ({"n_delay_buckets": 0}, "no synapses"),
    ({"n_p": dispatch.FUSED_MAX_N_P + 1}, "too large"),
    ({"identity_exchange": False,
      "n_global": dispatch.FUSED_SPLIT_MAX_N_GLOBAL + 1},
     "activity vector"),
    # plastic partitions keep the trace vectors resident too, so their
    # VMEM budgets are tighter — the ONLY way plasticity blocks fusion
    ({"any_plastic": True, "n_p": dispatch.FUSED_PLASTIC_MAX_N_P + 1},
     "state+trace"),
    ({"any_plastic": True, "identity_exchange": False,
      "n_global": dispatch.FUSED_SPLIT_PLASTIC_MAX_N_GLOBAL + 1},
     "pre-trace"),
])
def test_select_step_engine_blockers(override, reason_part):
    c = dispatch.select_step_engine(**{**ELIGIBLE, **override})
    assert c.engine == "unfused"
    assert reason_part in c.reason
    # demanding fusion on an ineligible partition is an error, not silence
    with pytest.raises(ValueError, match="fused step engine requested"):
        dispatch.select_step_engine(**{**ELIGIBLE, **override}, fused=True)


def test_select_step_engine_plastic_selects_variant_not_unfused():
    """any_plastic is a variant selector, not an unfused gate: a plastic
    partition within the (tighter) trace budgets fuses as fused_plastic /
    fused_split_plastic."""
    c = dispatch.select_step_engine(**{**ELIGIBLE, "any_plastic": True})
    assert c.engine == "fused_plastic"
    assert c.fused and c.plastic and not c.split
    c = dispatch.select_step_engine(
        **{**ELIGIBLE, "any_plastic": True, "identity_exchange": False},
        n_global=4096,
    )
    assert c.engine == "fused_split_plastic"
    assert c.fused and c.plastic and c.split
    assert "STDP fused" in c.reason
    # the plastic n_p budget sits between never-fuse and the non-plastic
    # cap: a partition inside the plastic cap fuses, one between the caps
    # falls back with the trace-budget reason, never the old STDP blocker
    mid = dispatch.FUSED_PLASTIC_MAX_N_P
    assert dispatch.select_step_engine(
        **{**ELIGIBLE, "any_plastic": True, "n_p": mid}
    ).engine == "fused_plastic"
    c = dispatch.select_step_engine(
        **{**ELIGIBLE, "any_plastic": True, "n_p": mid + 1}
    )
    assert c.engine == "unfused" and "STDP" not in c.reason


def test_select_step_engine_flags():
    assert dispatch.select_step_engine(
        **ELIGIBLE, fused=False
    ).engine == "unfused"
    assert dispatch.select_step_engine(
        **{**ELIGIBLE, "backend": "ref"}, fused=True
    ).engine == "fused"
    # fused=True on a ref-backend distributed partition forces the split
    assert dispatch.select_step_engine(
        **{**ELIGIBLE, "backend": "ref", "identity_exchange": False},
        fused=True,
    ).engine == "fused_split"


# -- end to end -----------------------------------------------------------

def test_fused_sim_matches_ref_on_microcircuit():
    """Acceptance: fused step == pure-JAX reference to <= 1e-5 on the
    microcircuit config (interpret mode)."""
    from repro.snn import SimConfig, Simulator, microcircuit, to_dcsr

    def build():
        return to_dcsr(microcircuit(scale=0.01, seed=0), k=1)

    sim_r = Simulator(build(), SimConfig(
        align_k=32, backend="ref", record_raster=True
    ))
    sim_f = Simulator(build(), SimConfig(
        align_k=32, backend="pallas_interpret", fused=True,
        record_raster=True,
    ))
    assert sim_r.engine_choice.engine == "unfused"
    assert sim_f.engine_choice.engine == "fused"
    st_r, out_r = sim_r.run(sim_r.init_state(), 50)
    st_f, out_f = sim_f.run(sim_f.init_state(), 50)
    np.testing.assert_array_equal(
        np.asarray(out_r["raster"]), np.asarray(out_f["raster"])
    )
    np.testing.assert_allclose(
        np.asarray(st_r["vtx_state"]), np.asarray(st_f["vtx_state"]),
        rtol=1e-5, atol=1e-5,
    )


def test_fused_plastic_sim_bit_exact_vs_unfused_stdp():
    """Acceptance: SimConfig(fused=True) on a plastic net no longer raises
    — it runs the fused_plastic engine, bit-exact vs the unfused STDP path
    on raster, spike counts, weights AND traces, with real weight
    movement."""
    from repro.snn import SimConfig, Simulator, balanced_ei, to_dcsr

    def build():
        net = balanced_ei(150, stdp=True, seed=5, delay_steps=5)
        net.vtx_state[:, 2] += 6.0  # drive real activity through STDP
        return to_dcsr(net, k=1)

    sim_u = Simulator(build(), SimConfig(
        align_k=8, backend="ref", record_raster=True
    ))
    sim_f = Simulator(build(), SimConfig(
        align_k=8, backend="pallas_interpret", fused=True,
        record_raster=True,
    ))
    assert sim_u.engine_choice.engine == "unfused"
    assert sim_f.engine_choice.engine == "fused_plastic"
    st_u, out_u = sim_u.run(sim_u.init_state(), 80)
    st_f, out_f = sim_f.run(sim_f.init_state(), 80)
    ras_u = np.asarray(out_u["raster"])
    np.testing.assert_array_equal(ras_u, np.asarray(out_f["raster"]))
    np.testing.assert_array_equal(
        np.asarray(out_u["spike_count"]), np.asarray(out_f["spike_count"])
    )
    assert int(ras_u.sum()) > 30, "test net too quiet to exercise STDP"
    for key in ("tr_plus", "tr_minus"):
        np.testing.assert_array_equal(
            np.asarray(st_u[key]), np.asarray(st_f[key])
        )
    moved = 0.0
    for w_u, w_f, w0 in zip(
        st_u["weights"], st_f["weights"], sim_u.dev.weights0
    ):
        np.testing.assert_array_equal(np.asarray(w_u), np.asarray(w_f))
        moved += float(np.abs(np.asarray(w_u) - np.asarray(w0)).max())
    assert moved > 0, "STDP moved no weights — the parity is vacuous"


def test_dist_index_exchange_splits_instead_of_bypassing():
    """k=1 compressed-index exchange truncates at its cap — it is NOT an
    identity exchange, so the single-kernel engine (which bypasses the
    exchange entirely) must not be picked.  It IS eligible for the SPLIT
    engine, where the exchange stays in place between the two kernels —
    and the truncating exchange must still truncate."""
    import numpy as np
    from repro.snn import DistSimulator, SimConfig, spatial_random, to_dcsr
    from repro.core import block_partition

    def build():
        net = spatial_random(64, avg_degree=6, seed=1)
        # drive hard enough that the whole net fires within a couple of
        # steps of each other — the synchronized wave overruns the cap
        net.vtx_state[:, 2] += 500.0
        return to_dcsr(net, assignment=block_partition(64, 1), uniform=True)

    outs_by_engine = {}
    for exchange, want in (("index", "fused_split"), ("dense", "fused")):
        dist = DistSimulator(build(), SimConfig(
            align_k=8, backend="pallas_interpret", exchange=exchange,
            index_cap_frac=0.1,
        ))
        _, outs = dist.run(dist.init_state(), 30)
        assert dist.engine_choice.engine == want, (exchange, want)
        outs_by_engine[exchange] = outs
    # the split engine routed spikes through the lossy exchange: the cap
    # (max(0.1 * 64, 8) = 8 ids/step) dropped spikes, and said so
    assert int(np.asarray(
        outs_by_engine["index"]["overflow"]
    ).sum()) > 0
    # the unfused index run agrees bit-for-bit with the split one
    dist_u = DistSimulator(build(), SimConfig(
        align_k=8, backend="ref", fused=False, exchange="index",
        index_cap_frac=0.1,
    ))
    _, outs_u = dist_u.run(dist_u.init_state(), 30)
    np.testing.assert_array_equal(
        np.asarray(outs_u["spike_count"]),
        np.asarray(outs_by_engine["index"]["spike_count"]),
    )
    np.testing.assert_array_equal(
        np.asarray(outs_u["overflow"]),
        np.asarray(outs_by_engine["index"]["overflow"]),
    )
