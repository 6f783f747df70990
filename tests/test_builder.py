"""Procedural per-partition construction (repro.builder): determinism of
the counter-based sampler across partition count / chunk size / sampling
path, bridge equality with the eager NetworkDef path, and end-to-end
simulation bit-identity for rule-built networks."""
import numpy as np
import pytest

from repro.builder import (
    ConnectRule,
    DistanceKernel,
    Population,
    RuleSpec,
    balanced_ei_rules,
    build_network,
    microcircuit_rules,
    network_def,
    spatial_random_rules,
)
from repro.builder import crng
from repro.core.dcsr import merge_to_single
from repro.snn import Session, SimConfig, to_dcsr
from repro.snn.monitors import RasterMonitor, permanent_order


def _nets_equal(a, b):
    """Bit-exact dCSR equality (no tolerances: determinism contract)."""
    assert a.n == b.n and a.m == b.m and a.k == b.k
    np.testing.assert_array_equal(a.dist, b.dist)
    for pa, pb in zip(a.parts, b.parts):
        for f in ("global_ids", "row_ptr", "col_idx", "vtx_model",
                  "edge_model", "vtx_state", "edge_state", "coords"):
            np.testing.assert_array_equal(
                getattr(pa, f), getattr(pb, f), err_msg=f
            )


def _specs():
    return [
        balanced_ei_rules(n=160, seed=3),
        microcircuit_rules(scale=0.02, seed=5),
        spatial_random_rules(n=150, avg_degree=8, seed=7),
    ]


# -- counter-based determinism ---------------------------------------------

@pytest.mark.parametrize("spec_i", [0, 1, 2])
def test_bit_identical_across_k(spec_i):
    """Same (seed, rules) -> bit-identical network for k in {1, 2, 4}:
    merging the k-way build equals the k=1 build exactly."""
    spec = _specs()[spec_i]
    d1 = build_network(spec, k=1)
    for k in (2, 4):
        dk = build_network(spec, k=k)
        assert dk.k == k
        _nets_equal(merge_to_single(dk), d1)


@pytest.mark.parametrize("chunk_rows", [1, 17, 64, 10_000])
def test_bit_identical_across_chunk_sizes(chunk_rows):
    spec = spatial_random_rules(n=130, avg_degree=7, seed=11)
    ref = build_network(spec, k=2)
    got = build_network(spec, k=2, chunk_rows=chunk_rows)
    _nets_equal(got, ref)


def test_different_seed_differs():
    a = build_network(balanced_ei_rules(n=120, seed=0), k=1)
    b = build_network(balanced_ei_rules(n=120, seed=1), k=1)
    assert not np.array_equal(a.parts[0].col_idx, b.parts[0].col_idx) or \
        not np.array_equal(a.parts[0].edge_state, b.parts[0].edge_state)


def test_uniform_padding_matches_to_dcsr():
    """uniform=True padding (ghost rows, pad ids, dist) matches the eager
    to_dcsr(uniform=True) contract bit-exactly."""
    spec = balanced_ei_rules(n=130, seed=2)
    eager = to_dcsr(network_def(spec), k=4, uniform=True)
    proc = build_network(spec, k=4, uniform=True)
    _nets_equal(proc, eager)


# -- bridge equality: procedural vs eager NetworkDef path ------------------

@pytest.mark.parametrize("spec_i", [0, 1, 2])
def test_bridge_equality_with_network_def(spec_i):
    """to_dcsr(network_def(spec), k) == build_network(spec, k) bit-exactly:
    the chunked emitter and the whole-network edge-list path agree."""
    spec = _specs()[spec_i]
    eager = to_dcsr(network_def(spec), k=4)
    proc = build_network(spec, k=4)
    _nets_equal(proc, eager)


def test_to_dcsr_accepts_rule_spec():
    spec = spatial_random_rules(n=90, avg_degree=6, seed=1)
    _nets_equal(to_dcsr(spec, k=2), build_network(spec, k=2))


# -- ref vs device sampling path -------------------------------------------

def test_keystream_ref_vs_device_words():
    """The uint32 keystream is bit-identical between the NumPy reference
    and the device (jnp / Pallas-interpret) kernels, including large row
    counters and odd word offsets."""
    from repro.kernels import ops

    rows = np.array([0, 1, 5, 2**20, 7], dtype=np.int64)
    ref = crng.word_matrix(123, 17, rows, 2, 9)
    for backend in ("ref", "pallas_interpret"):
        got = np.asarray(
            ops.builder_keystream(123, 17, rows.astype(np.int32), 2, 9,
                                  backend=backend)
        )
        np.testing.assert_array_equal(got, ref, err_msg=backend)


@pytest.mark.parametrize("j0,n_words", [(0, 1), (1, 1), (3, 4), (2, 9)])
def test_word_matrix_is_the_per_word_cipher(j0, n_words):
    """Word ``w`` of a row is half ``w % 2`` of the cipher at counter
    ``(row, w // 2)``, whatever window of words is asked for."""
    rows = np.array([0, 3, 2**20], dtype=np.int64)
    got = crng.word_matrix(7, 99, rows, j0, n_words)
    for i, row in enumerate(rows):
        for j in range(n_words):
            w = j0 + j
            halves = crng.threefry2x32(7, 99, np.uint32(row), np.uint32(w // 2))
            assert got[i, j] == halves[w % 2], (row, w)


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_network_ref_vs_device_path(backend):
    """Float assembly is host-side shared code; the device path only
    produces keystream words -> bit-identical networks."""
    spec = spatial_random_rules(n=110, avg_degree=6, seed=4)
    ref = build_network(spec, k=2, path="ref")
    dev = build_network(spec, k=2, path="device", backend=backend,
                        chunk_rows=33)
    _nets_equal(dev, ref)


# -- end-to-end simulation bit-identity ------------------------------------

def test_session_rule_built_trajectory_bit_identical(tmp_path):
    """Session(spec, k=1) vs Session(spec, k=4) vs chunked build: raster,
    spike_count, and post-run (STDP) weights all bit-identical."""
    # n=150, k=4 -> unequal blocks, so the uniform-slot relabel is live
    spec = balanced_ei_rules(n=150, seed=6)
    cfg = SimConfig(align_k=8)

    from repro.io import load_binary

    runs = {}
    for name, kw in {
        "k1": dict(),
        "k4": dict(k=4),
        "chunked": dict(build_chunk_rows=23),
    }.items():
        ses = Session(spec, cfg, **kw)
        ras = RasterMonitor()
        res = ses.run(60, monitors=[ras], chunk_size=16)
        ses.save(str(tmp_path / name))
        net, _, _ = load_binary(str(tmp_path / name))
        # permanent-id space: uniform k=4 carries isolated pad neurons
        # (ids >= spec.n) which never spike — slice them off
        perm = permanent_order(ras.raster, ses.permanent_ids)[:, :spec.n]
        runs[name] = (
            perm, res.spike_count,
            np.concatenate([p.edge_state[:, 0] for p in net.parts]),
        )

    ref = runs["k1"]
    for name in ("k4", "chunked"):
        for a, b in zip(runs[name], ref):
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_session_rejects_k_for_non_rule_input():
    net = to_dcsr(spatial_random_rules(n=60, avg_degree=5, seed=0), k=1)
    with pytest.raises(ValueError, match="RuleSpec"):
        Session(net, SimConfig(align_k=8), k=2)


# -- rule-spec validation ---------------------------------------------------

def test_rule_spec_validation():
    pops = (Population("a", 10), Population("b", 10))
    with pytest.raises(ValueError):  # no connectivity family
        RuleSpec(pops, (ConnectRule("a", "b"),))
    with pytest.raises(ValueError):  # two families at once
        RuleSpec(pops, (ConnectRule("a", "b", fan_in=3, p=0.5),))
    with pytest.raises(ValueError):  # unknown population
        RuleSpec(pops, (ConnectRule("a", "zzz", fan_in=2),))
    with pytest.raises(ValueError):  # kernel rule needs candidates
        RuleSpec(pops, (ConnectRule(
            "a", "b", kernel=DistanceKernel(0.5, 1.0)),))
    spec = RuleSpec(pops, (ConnectRule("a", "b", fan_in=2),), seed=9)
    assert spec.n == 20 and spec.offsets()["b"] == (10, 20)


def test_build_path_choice_is_explicit(monkeypatch):
    """'auto' is the host path on every platform, whatever the simulator
    backend; a failing device keystream propagates instead of being
    swapped for the host path."""
    from repro.builder import procedural
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    assert procedural.resolve_build_path("auto") == "ref"
    with pytest.raises(ValueError, match="unknown build path"):
        procedural.resolve_build_path("gpu")

    def broken(*a, **k):
        raise RuntimeError("keystream refused")

    monkeypatch.setattr(ops, "builder_keystream", broken)
    with pytest.raises(RuntimeError, match="keystream refused"):
        build_network(balanced_ei_rules(n=40, seed=1), path="device")


def test_microcircuit_rules_inhibitory_populations():
    """Synapses from the four inhibitory populations (``L*I``) are
    negative with the 0.8 ms delay; excitatory ones positive with 1.5 ms,
    as in the eager ``microcircuit()``."""
    spec = microcircuit_rules(scale=0.02, seed=1)
    net = build_network(spec)
    part = net.parts[0]
    offs = spec.offsets()
    inh = np.zeros(spec.n, bool)
    for name, (a, b) in offs.items():
        inh[a:b] = name.endswith("I")
    src_inh = inh[part.col_idx]
    w, d = part.edge_state[:, 0], part.edge_state[:, 1]
    assert src_inh.any() and (~src_inh).any()
    assert (w[src_inh] < 0).all() and (d[src_inh] == 8).all()
    assert (w[~src_inh] > 0).all() and (d[~src_inh] == 15).all()
