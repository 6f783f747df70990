"""Whole runs of tiny cells on the CPU, with the harness's look for a chip
skipped: the reference agrees with ``Session.run`` for both
configurations and for save -> restore -> continue; the bfloat16 control
and each planted fault of the step or of the network builder come out as
not correct; a new configuration, traffic mix, metric and model file are
picked up from their files."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_helpers
from bench import harness

SEED = 2**31 + 12345  # more than 32 signed bits hold


@pytest.mark.parametrize("workload", [w for w, *_ in bench_helpers.TINY])
def test_reference_agrees(tiny, workload, capsys):
    rc, res = bench_helpers.run_cell(tiny, workload, seed=SEED, capsys=capsys)
    assert rc == 0
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == len(res["checks"])
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"] and "rtf" in res["metrics"]
    if workload == "tiny_ckpt":
        assert {"ckpt_stall_s", "restore_s"} <= set(res["metrics"])
        assert res["checks"]["restore_diff"]["value"] == 0


@pytest.mark.parametrize("workload", ["tiny_bal", "tiny_mc"])
def test_bfloat16_control_fails(tiny, workload, capsys):
    rc = harness.run(
        ["--workload", workload, "--seed", "11", "--seconds", "0.5"],
        require_tpu=False, root=tiny, peaks=bench_helpers.CPU_PEAK,
        control=True,
    )
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    ctl = json.loads(lines[-2])
    assert ctl["control_failed"], ctl
    assert json.loads(lines[-1])["correct"]


def _patch_chunk(monkeypatch, fault):
    """Break the timed path underneath the harness: wrap the engine's
    chunk so that its state or its outputs are wrong."""
    from repro.snn import session

    real = session._SingleEngine.run_chunk

    def broken(self, state, steps):
        new, outs = real(self, state, steps)
        if fault == "state_unchanged":
            return state, outs
        if fault == "half_left_out":
            half = new["vtx_state"].shape[0] // 2
            new = dict(new, vtx_state=new["vtx_state"].at[half:].set(
                state["vtx_state"][half:]))
            return new, outs
        if fault == "spike_altered":
            r = np.array(outs["raster"])
            r[-1, r.shape[1] // 3] ^= 1
            return new, dict(outs, raster=r)
        raise ValueError(fault)

    monkeypatch.setattr(session._SingleEngine, "run_chunk", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out", "spike_altered"])
@pytest.mark.parametrize("workload", ["tiny_bal", "tiny_mc"])
def test_planted_faults_are_not_correct(tiny, workload, fault, monkeypatch, capsys):
    _patch_chunk(monkeypatch, fault)
    rc, res = bench_helpers.run_cell(tiny, workload, seed=SEED, seconds=0.3,
                                     capsys=capsys)
    assert rc == 0
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1


@pytest.mark.parametrize("fault", bench_helpers.BUILDER_FAULTS)
@pytest.mark.parametrize("workload", ["tiny_bal", "tiny_mc"])
def test_builder_faults_are_not_correct(tiny, workload, fault, monkeypatch, capsys):
    """A network built wrong replays without a gap; the check of the
    built network against the configuration's own numbers fails it."""
    bench_helpers.plant_builder_fault(monkeypatch, fault)
    rc, res = bench_helpers.run_cell(tiny, workload, seed=SEED, seconds=0.3,
                                     capsys=capsys)
    assert rc == 0
    assert not res["correct"], res["checks"]
    failed = {k for k, c in res["checks"].items() if not c["value"] <= c["limit"]}
    assert failed <= {"structure_diff", "structure_z"}, failed


def test_trace_run_on_cpu_reports_no_device_metric(tiny, capsys):
    """The profiler records no TPU plane on a CPU: a traced run fails
    loudly rather than reading device metrics from the CPU."""
    with pytest.raises(ValueError, match="no TPU device plane"):
        bench_helpers.run_cell(tiny, "tiny_bal", seconds=0.3, trace=1)


def test_new_files_are_picked_up(tiny, tmp_path, capsys):
    """A configuration, a traffic mix and a metric added as files, with
    their BENCHMARK.json entries, run without an edit to any file that
    was there."""
    root = bench_helpers.tiny_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "tiny_brunel.json")) as f:
        cfg = json.load(f)
    cfg["builder_args"].update(n=250, epsilon=0.2)
    cfg["n"], cfg["populations"] = 250, {"E": 200, "I": 50}
    cfg["in_degree"] = {"E": 40, "I": 10}
    with open(os.path.join(bench, "configs", "tiny_brunel_dense.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "steady_rates_c7.json"), "w") as f:
        json.dump({"chunk_steps": 7, "monitors": ["rate"]}, f)
    with open(os.path.join(bench, "limits", "tiny_new.json"), "w") as f:
        with open(os.path.join(bench, "limits", "bal_stdp_k1.json")) as g:
            f.write(g.read())
    with open(os.path.join(bench, "metrics", "steps_per_chunk.py"), "w") as f:
        f.write("def read(run):\n    return 7.0\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append(dict(name="tiny_brunel_dense", source="test",
                              file="bench/configs/tiny_brunel_dense.json",
                              reduced=[], why="test"))
    bm["workloads"].append(dict(name="tiny_new", config="tiny_brunel_dense",
                                traffic="steady_rates_c7", chips=1, why="test"))
    bm["end_to_end"].append(dict(name="steps_per_chunk", unit="steps",
                                 better="higher", bound=0.01, source="host_clock",
                                 workloads=["tiny_new"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    rc, res = bench_helpers.run_cell(root, "tiny_new", seconds=0.3, capsys=capsys)
    assert rc == 0 and res["correct"], res["checks"]
    assert res["metrics"]["steps_per_chunk"] == {"value": 7.0, "unit": "steps"}


# a model file of a test's own: the configurations' LIF, its parameters
# read from the configuration's own key "lif_model" (no top-level neuron,
# noise, bias, initial-state or STDP key); with NO_BIAS its replay leaves
# the bias current out
_OWN_MODEL = """
import dataclasses
import os

import numpy as np

from bench import spec

base = spec.load_model(os.path.dirname(os.path.dirname(__file__)),
                       {{"reference": "lif_delta"}})
NO_BIAS = {no_bias}

PLASTIC_EDGES = base.PLASTIC_EDGES
neurons, program_state, gaps = base.neurons, base.program_state, base.gaps


def _own(cfg):
    return dict(cfg, **cfg["lif_model"])


def departures(net, cfg):
    return base.departures(net, _own(cfg))


def step_input(seed, net, cfg):
    return base.step_input(seed, net, _own(cfg))


def replay(net, cfg, raster, step_input, control=False):
    if NO_BIAS:
        net = dataclasses.replace(net, neurons=dict(
            net.neurons, bias=np.zeros_like(net.neurons["bias"])))
    return base.replay(net, _own(cfg), raster, step_input, control)


def neuron_structure(net, cfg, row_pop, n_pops):
    return base.neuron_structure(net, _own(cfg), row_pop, n_pops)
"""

_MODEL_KEYS = ("neuron", "noise_sigma", "bias", "v_init_uniform", "stdp")


@pytest.mark.parametrize("no_bias", [False, True], ids=["sound", "replay_without_bias"])
def test_a_model_file_is_picked_up(tmp_path, no_bias, capsys):
    """A configuration that names a model file of its own, whose
    parameters sit under its own key, runs without an edit to any file
    that was there; a model file that replays the network without its
    bias makes the same run not correct, so the harness replays the file
    the configuration names."""
    root = bench_helpers.tiny_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "models", "own_lif.py"), "w") as f:
        f.write(_OWN_MODEL.format(no_bias=no_bias))
    with open(os.path.join(bench, "configs", "tiny_brunel.json")) as f:
        cfg = json.load(f)
    cfg["lif_model"] = {k: cfg.pop(k) for k in _MODEL_KEYS}
    cfg["reference"] = "own_lif"
    with open(os.path.join(bench, "configs", "tiny_brunel_own.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "limits", "tiny_own.json"), "w") as f:
        with open(os.path.join(bench, "limits", "bal_stdp_k1.json")) as g:
            f.write(g.read())
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append(dict(name="tiny_brunel_own", source="test",
                              file="bench/configs/tiny_brunel_own.json",
                              reduced=[], why="test"))
    bm["workloads"].append(dict(name="tiny_own", config="tiny_brunel_own",
                                traffic="steady_rates_c20", chips=1, why="test"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    rc, res = bench_helpers.run_cell(root, "tiny_own", seconds=0.3, capsys=capsys)
    assert rc == 0
    failed = {k for k, c in res["checks"].items() if not c["value"] <= c["limit"]}
    if not no_bias:
        assert res["correct"], res["checks"]
        assert "w_gap" in res["checks"], "the plastic weights were not compared"
    else:
        assert not res["correct"]
        assert "v_gap_mv" in failed
        assert failed <= {"v_gap_mv", "thr_gap_mv", "refrac_diff", "hist_diff",
                          "ring_gap", "w_gap", "trace_gap"}, failed


def test_configuration_without_reference_fails_at_load(tmp_path):
    """A configuration must name its model file: without the key the
    cell does not load, and the message names the key."""
    from bench import spec

    root = bench_helpers.tiny_root(str(tmp_path))
    path = os.path.join(root, "bench", "configs", "tiny_brunel.json")
    with open(path) as f:
        cfg = json.load(f)
    del cfg["reference"]
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(KeyError, match='"reference"'):
        spec.load_cell("tiny_bal", root)


_SPLIT_CELLS = """
import json, os, sys
import bench_helpers
from bench import harness
root = sys.argv[1]
bench = os.path.join(root, "bench")
def load(*p):
    with open(os.path.join(bench, *p)) as f:
        return json.load(f)
def dump(obj, *p):
    with open(os.path.join(bench, *p), "w") as f:
        json.dump(obj, f)
bm = json.load(open(os.path.join(root, "BENCHMARK.json")))
for cfg, k in (("tiny_brunel", 2), ("tiny_microcircuit", 3)):
    c = load("configs", cfg + ".json")
    c["k"] = k
    dump(c, "configs", f"{cfg}_k{k}.json")
    bm["configs"].append(dict(name=f"{cfg}_k{k}", source="test",
                              file=f"bench/configs/{cfg}_k{k}.json", reduced=[], why="test"))
t = load("traffic", "ckpt_every20_restore.json")
t["restore_k"] = 2
dump(t, "traffic", "ckpt_every20_restore_k2.json")
cells = (("split_bal", "tiny_brunel_k2", "steady_rates_c20", "tiny_bal", 2),
         ("split_mc", "tiny_microcircuit_k3", "steady_raster_c5", "tiny_mc", 3),
         ("resume_k2", "tiny_brunel", "ckpt_every20_restore_k2", "tiny_ckpt", 1))
for name, cfg, traffic, like, chips in cells:
    bm["workloads"].append(dict(name=name, config=cfg, traffic=traffic, chips=chips, why="test"))
    dump(load("limits", like + ".json"), "limits", name + ".json")
json.dump(bm, open(os.path.join(root, "BENCHMARK.json"), "w"))
for name, *_ in cells:
    rc = harness.run(["--workload", name, "--seed", "2147483700", "--seconds", "0.3"],
                     require_tpu=False, root=root, peaks=bench_helpers.CPU_PEAK)
    assert rc == 0
"""


def test_cells_over_several_chips_are_files_only(tmp_path):
    """Configurations at k=2 and k=3 (the SPMD engine over fake CPU
    devices; k=3 pads its partitions) and a traffic mix that restores a
    k=1 snapshot at k=2 run correct, added as files only."""
    root = bench_helpers.tiny_root(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=3",
               PYTHONPATH=os.pathsep.join([os.path.dirname(__file__),
                                           bench_helpers.REPO]))
    p = subprocess.run([sys.executable, "-c", _SPLIT_CELLS, root], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    results = [json.loads(line) for line in p.stdout.splitlines()
               if line.startswith("{")]
    assert len(results) == 3
    for res in results:
        assert res["correct"], res["checks"]
    assert results[2]["checks"]["restore_diff"]["value"] == 0
    assert "[restore] restored at k=2" in p.stderr


def _run_script(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bal_stdp_k1",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_no_tpu_no_result():
    """Without a TPU the command exits non-zero and prints nothing on
    standard output."""
    p = _run_script(bench_helpers.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_does_not_run(tmp_path):
    """In a directory that holds only BENCHMARK.json and bench/, there is
    no program to run: non-zero exit, no result."""
    bench_helpers.tiny_root(str(tmp_path))
    p = _run_script(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_departure_from_configuration_is_not_correct(tmp_path, capsys):
    """A configuration that states other neuron parameters than the
    program runs is counted as a departure and fails the run."""
    root = bench_helpers.tiny_root(str(tmp_path))
    path = os.path.join(root, "bench", "configs", "tiny_brunel.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["neuron"]["tau_m"] = 20.0
    with open(path, "w") as f:
        json.dump(cfg, f)
    rc, res = bench_helpers.run_cell(root, "tiny_bal", seconds=0.3, capsys=capsys)
    assert rc == 0 and not res["correct"]
    assert res["checks"]["config_departures"]["value"] == 1
