"""Mutation tests for the static-analysis subsystem (repro.analysis):
each deliberately broken fixture must FAIL its pass with a message
naming the violating op/file, and the clean codebase must pass both
passes."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_with_devices
from repro.analysis import repolint
from repro.analysis.contracts import (
    CaseSpec, check_hlo_text, check_jaxpr_facts, contract_matrix,
    exchange_key, jaxpr_facts, run_case,
)
from repro.kernels.dispatch import (
    ENGINE_CONTRACTS, EngineContract, STEP_ENGINES,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# engine-contract checker: broken toy engines must fail
# ---------------------------------------------------------------------------


def _toy_scan(n_collectives: int):
    """A toy 'engine': a scan whose body issues that many all_gathers
    over a 1-device parts mesh (the primitive is recorded in the jaxpr
    regardless of mesh size)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:1]), ("parts",))

    def body(c, _):
        acc = c
        for _i in range(n_collectives):
            acc = acc + jax.lax.all_gather(c, "parts").sum(0)
        return acc, acc.sum()

    def fn(x):
        return jax.lax.scan(body, x, None, length=3)

    return shard_map(
        fn, mesh=mesh, in_specs=P("parts"), out_specs=(P("parts"), P()),
        check_vma=False,
    )


def test_extra_collective_fails_contract():
    contract = EngineContract("toy", {"dense": 1})
    fn = _toy_scan(2)
    facts = jaxpr_facts(fn, jnp.zeros(8, jnp.float32))
    assert facts.scan_collectives.get("all_gather") == 2
    problems = check_jaxpr_facts(
        facts, contract, "dense", n_p=8, n_global=8
    )
    assert any("2 collective(s)" in p and "'toy'" in p
               for p in problems), problems
    # the conforming toy engine passes the same contract
    ok = check_jaxpr_facts(
        jaxpr_facts(_toy_scan(1), jnp.zeros(8, jnp.float32)),
        contract, "dense", n_p=8, n_global=8,
    )
    assert ok == [], ok


def test_undeclared_exchange_key_fails():
    contract = EngineContract("toy", {"dense": 1})
    facts = jaxpr_facts(_toy_scan(1), jnp.zeros(8, jnp.float32))
    problems = check_jaxpr_facts(
        facts, contract, exchange_key("index", True), n_p=8, n_global=8
    )
    assert any("index+plastic" in p and "not a declared" in p
               for p in problems), problems


def test_disallowed_collective_kind_fails():
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:1]), ("parts",))

    def body(c, _):
        return jax.lax.psum(c, "parts"), c.sum()

    fn = shard_map(
        lambda x: jax.lax.scan(body, x, None, length=2),
        mesh=mesh, in_specs=P("parts"), out_specs=(P("parts"), P()),
        check_vma=False,
    )
    contract = EngineContract("toy", {"dense": 1})  # allows all_gather
    problems = check_jaxpr_facts(
        jaxpr_facts(fn, jnp.zeros(8, jnp.float32)), contract, "dense",
        n_p=8, n_global=8,
    )
    assert any("psum" in p and "not in the contract" in p
               for p in problems), problems


def test_float64_leak_fails_contract():
    contract = EngineContract("toy", {"identity": 0})

    def body(c, _):
        wide = c.astype(jnp.float64) + 1.0  # the leak
        return wide.astype(jnp.float32), None

    with jax.enable_x64(True):
        facts = jaxpr_facts(
            lambda x: jax.lax.scan(body, x, None, length=2),
            jax.ShapeDtypeStruct((4,), jnp.float32),
        )
    assert facts.wide_values, "expected a float64 value in the trace"
    problems = check_jaxpr_facts(
        facts, contract, "identity", n_p=4, n_global=4
    )
    assert any("float64" in p and "promotion" in p
               for p in problems), problems


def test_host_callback_in_scan_fails():
    contract = EngineContract("toy", {"identity": 0})

    def body(c, _):
        jax.debug.callback(lambda v: None, c)
        return c, None

    facts = jaxpr_facts(
        lambda x: jax.lax.scan(body, x, None, length=2),
        jax.ShapeDtypeStruct((4,), jnp.float32),
    )
    problems = check_jaxpr_facts(
        facts, contract, "identity", n_p=4, n_global=4
    )
    assert any("callback" in p for p in problems), problems


def test_vmem_budget_violation_fails():
    # a contract whose resident vectors at this width cannot fit VMEM
    contract = EngineContract(
        "toy", {"identity": 0}, resident_np_vectors=10
    )
    facts = jaxpr_facts(
        lambda x: jax.lax.scan(
            lambda c, _: (c, None), x, None, length=2
        ),
        jax.ShapeDtypeStruct((4,), jnp.float32),
    )
    problems = check_jaxpr_facts(
        facts, contract, "identity", n_p=1 << 20, n_global=1 << 20
    )
    assert any("VMEM budget" in p for p in problems), problems


TOY_HLO_2AG = """\
HloModule toy

ENTRY %main (x: f32[8]) -> f32[32] {
  %x = f32[8] parameter(0)
  %ag = f32[16] all-gather(%x), dimensions={0}
  ROOT %ag2 = f32[32] all-gather(%ag), dimensions={0}
}
"""


def test_hlo_collective_count_mismatch_fails():
    contract = EngineContract("toy", {"dense": 1})
    problems = check_hlo_text(TOY_HLO_2AG, contract, "dense", steps=1)
    assert any("2 collectives" in p and "'toy'" in p
               for p in problems), problems
    wide = TOY_HLO_2AG.replace("ROOT %ag2 = f32[32]",
                               "ROOT %ag2 = f64[32]")
    problems = check_hlo_text(wide, contract, "dense", steps=2)
    assert any("f64" in p for p in problems), problems


# ---------------------------------------------------------------------------
# the clean codebase passes
# ---------------------------------------------------------------------------


def test_matrix_covers_every_engine():
    assert {s.engine for s in contract_matrix()} == set(STEP_ENGINES)
    assert set(ENGINE_CONTRACTS) == set(STEP_ENGINES)


def test_clean_k1_row_passes():
    problems = run_case(
        CaseSpec("k1_fused", 1, "fused", "identity"), steps=2
    )
    assert problems == [], problems


def test_clean_k2_row_passes_subprocess():
    run_with_devices("""
        from repro.analysis.contracts import CaseSpec, run_case
        problems = run_case(
            CaseSpec("k2_split_dense_off", 2, "fused_split", "dense"),
            steps=2,
        )
        assert problems == [], problems
        print("ok")
    """, n_devices=2)


def test_clean_repo_repolint_passes():
    violations = repolint.lint_paths(
        [os.path.join(ROOT, "src")],
        tests_dir=os.path.join(ROOT, "tests"),
    )
    assert violations == [], "\n".join(str(v) for v in violations)


# ---------------------------------------------------------------------------
# repolint mutation fixtures
# ---------------------------------------------------------------------------


def _tree(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(tmp_path / "src")


def test_unhooked_raw_shard_write_fails(tmp_path):
    src = _tree(tmp_path, {
        "src/pkg/io/writer.py": '''
            def save_shard(path, data):
                with open(path, "wb") as f:
                    f.write(data)
        ''',
    })
    vs = repolint.lint_paths([src])
    rules = {v.rule for v in vs}
    assert "durable-write" in rules and "fault-hook" in rules, vs
    dw = [v for v in vs if v.rule == "durable-write"]
    assert any("writer.py" in v.path and "wb" in v.message for v in dw)
    fh = [v for v in vs if v.rule == "fault-hook"]
    assert any("save_shard" in v.message for v in fh), fh


def test_hooked_write_passes(tmp_path):
    src = _tree(tmp_path, {
        "src/pkg/io/writer.py": '''
            import io

            import numpy as np

            from ..durability import write_bytes_verified

            def save_shard(path, arr):
                buf = io.BytesIO()
                np.save(buf, arr)
                write_bytes_verified(path, buf.getvalue(), "shard_write")
        ''',
    })
    vs = repolint.lint_paths([src])
    assert vs == [], vs


def test_np_save_to_disk_fails(tmp_path):
    src = _tree(tmp_path, {
        "src/pkg/io/writer.py": '''
            import numpy as np

            def persist(path, arr):
                np.save(path, arr)
        ''',
    })
    vs = repolint.lint_paths([src])
    assert any(v.rule == "durable-write" and "np.save" in v.message
               for v in vs), vs


def test_lock_free_mutation_fails(tmp_path):
    src = _tree(tmp_path, {
        "src/pkg/io/state.py": '''
            import threading

            class Writer:
                _guarded_by_ = {"_err": "_lock"}

                def __init__(self):
                    self._err = []
                    self._lock = threading.Lock()

                def bad(self, e):
                    self._err.append(e)

                def also_bad(self, e):
                    if e:
                        self._err = [e]

                def good(self, e):
                    with self._lock:
                        self._err.append(e)

                def also_good(self, e):
                    with self._lock:
                        if e:
                            self._err.append(e)
        ''',
    })
    vs = [v for v in repolint.lint_paths([src])
          if v.rule == "lock-discipline"]
    assert len(vs) == 2, vs
    assert all("_err" in v.message and "_lock" in v.message for v in vs)
    bad_lines = sorted(v.line for v in vs)
    text = (tmp_path / "src/pkg/io/state.py").read_text().splitlines()
    assert "self._err.append(e)" in text[bad_lines[0] - 1]
    assert "self._err = [e]" in text[bad_lines[1] - 1]


def test_registry_incomplete_op_fails(tmp_path):
    src = _tree(tmp_path, {
        "src/pkg/kernels/ops.py": '''
            def register(op, backend):
                def deco(fn):
                    return fn
                return deco

            def _register_pallas(op):
                def deco(fn):
                    return fn
                return deco

            @register("alpha", "ref")
            def alpha_ref():
                pass

            _register_pallas("alpha")(alpha_ref)

            @register("beta", "ref")
            def beta_ref():
                pass
        ''',
        "tests/test_ops.py": '''
            def test_alpha_parity():
                assert "alpha"
        ''',
    })
    vs = [v for v in repolint.lint_paths([src])
          if v.rule == "registry-op"]
    assert any("'beta'" in v.message and "no Pallas" in v.message
               for v in vs), vs
    assert any("'beta'" in v.message and "no test" in v.message
               for v in vs), vs
    assert not any("'alpha'" in v.message for v in vs), vs


def test_unregistered_fault_site_fails(tmp_path):
    src = _tree(tmp_path, {
        "src/pkg/testing/faults.py": '''
            KNOWN_SITES = ("shard_write", "dead_site")

            def fault_point(site, path=None):
                pass
        ''',
        "src/pkg/io/writer.py": '''
            from ..testing.faults import fault_point

            def save_shard(path):
                fault_point("rogue_site", path)
        ''',
    })
    vs = [v for v in repolint.lint_paths([src])
          if v.rule == "fault-hook"]
    assert any("'rogue_site'" in v.message and "not registered"
               in v.message for v in vs), vs
    assert any("'dead_site'" in v.message and "dead" in v.message
               for v in vs), vs


def test_suppression_requires_justification(tmp_path):
    src = _tree(tmp_path, {
        "src/pkg/io/sidecar.py": '''
            def export_debug(path):
                # repolint: allow[durable-write] -- debug sidecar, not a durable artifact
                with open(path, "w") as f:
                    f.write("x")
        ''',
        "src/pkg/io/bare.py": '''
            def export_more(path):
                # repolint: allow[durable-write]
                with open(path, "w") as f:
                    f.write("x")
        ''',
    })
    vs = repolint.lint_paths([src])
    # justified suppression silences the sidecar file entirely
    assert not any("sidecar.py" in v.path for v in vs), vs
    bare = [v for v in vs if "bare.py" in v.path]
    assert any(v.rule == "suppress" and "justification" in v.message
               for v in bare), vs
    # and the unjustified suppression does NOT silence the violation
    assert any(v.rule == "durable-write" for v in bare), vs


def test_repolint_cli_exit_codes(tmp_path):
    src = _tree(tmp_path, {
        "src/pkg/io/writer.py": '''
            def save_shard(path, data):
                with open(path, "wb") as f:
                    f.write(data)
        ''',
    })
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    bad = subprocess.run(
        [sys.executable, "-m", "repro.analysis.repolint", src],
        env=env, capture_output=True, text=True,
    )
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "save_shard" in bad.stdout and "writer.py" in bad.stdout
    good = subprocess.run(
        [sys.executable, "-m", "repro.analysis.repolint",
         os.path.join(ROOT, "src")],
        env=env, capture_output=True, text=True, cwd=ROOT,
    )
    assert good.returncode == 0, good.stdout + good.stderr
