"""Engine-contract checker: verify every step engine's declared contract
against the program XLA actually builds.

The repo's per-step traffic discipline — one parts-axis collective per
step, every synapse panel crossing VMEM once, f32 state / s32 indices,
no host round-trips inside the scan — is what the dCSR paper's scaling
story rests on, but example-based tests only pin it for the
configurations they happen to run.  This module enumerates every
eligible configuration of the selector matrix (engine x exchange x
overlap x gather x k), lowers each one (interpret-mode Pallas, so the
whole matrix runs on a CPU runner), and checks the
:data:`repro.kernels.dispatch.ENGINE_CONTRACTS` declaration for the
selected engine on two independent views of the program:

* the **jaxpr** (``jax.make_jaxpr`` over the step scan): exact
  collective primitive counts *inside the scan body*, collective kinds,
  host-callback primitives (``pure_callback`` / ``io_callback`` /
  ``debug_callback`` — a device-to-host transfer inside the hot loop),
  and any f64/s64/u64 value anywhere in the trace;
* the **post-SPMD HLO** (``lower(...).compile().as_text()`` through
  :mod:`repro.analysis.hlo`): loop-corrected collective counts over the
  whole compiled program (``steps x per-step count``) and a wide-dtype
  sweep of what XLA materialized.

VMEM footprint is checked with the dispatcher's own arithmetic: the
contract declares how many full-length f32 vectors the engine keeps
resident, the checker multiplies by the *actual* widths of the lowered
program and asserts the product stays inside
``_FUSED_VECTOR_VMEM_BUDGET`` (resp. ``EVENT_IDS_VMEM_BUDGET`` for the
event id buffer) — the same inequalities behind ``FUSED_MAX_N_P`` and
friends — and cross-checks that no f32 vector wider than the exchanged
activity vector was materialized.

Run as ``python -m repro.analysis.contracts`` (exit 0 = every
configuration honors its contract).  The k>1 rows need >= 2 devices;
when ``XLA_FLAGS`` is unset the CLI provisions 8 fake host devices for
itself (a fresh process only — the flag is read at backend init).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from .hlo import analyze_hlo, wide_dtype_ops

# jaxpr primitive names that are parts-axis collectives
COLLECTIVE_PRIMITIVES = frozenset({
    "all_gather", "psum", "ppermute", "all_to_all", "pgather",
    "reduce_scatter", "psum_scatter",
})
# host round-trips: forbidden inside the scan body
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback",
})
# dtypes the engines must never materialize (f32 state / s32 indices)
WIDE_DTYPES = frozenset({"float64", "int64", "uint64", "complex128"})


@dataclasses.dataclass
class JaxprFacts:
    """What one traced step program actually contains."""

    scan_collectives: Dict[str, int]  # primitive -> count inside scan body
    outside_collectives: Dict[str, int]  # collectives outside any scan
    scan_callbacks: List[str]  # callback primitives inside scan body
    wide_values: List[Tuple[str, str]]  # (where, dtype) of 8-byte values
    max_f32_vector: int  # widest rank-1 f32 value anywhere
    n_scans: int


def _sub_jaxprs(eqn) -> List[Any]:
    out = []
    for v in eqn.params.values():
        for cand in v if isinstance(v, (tuple, list)) else (v,):
            # ClosedJaxpr first: it forwards .eqns, but only .jaxpr has
            # the .invars/.constvars the walker needs
            if hasattr(cand, "jaxpr") and hasattr(
                getattr(cand, "jaxpr"), "eqns"
            ):
                out.append(cand.jaxpr)
            elif hasattr(cand, "eqns"):  # a bare Jaxpr (pallas_call)
                out.append(cand)
    return out


def _walk(jaxpr, facts: JaxprFacts, in_scan: bool, where: str) -> None:
    for var in list(jaxpr.invars) + list(jaxpr.constvars):
        _note_aval(getattr(var, "aval", None), facts, where)
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        for var in eqn.outvars:
            _note_aval(getattr(var, "aval", None), facts,
                       f"{where}/{prim}")
        if prim in COLLECTIVE_PRIMITIVES:
            tgt = (facts.scan_collectives if in_scan
                   else facts.outside_collectives)
            tgt[prim] = tgt.get(prim, 0) + 1
        if in_scan and prim in CALLBACK_PRIMITIVES:
            facts.scan_callbacks.append(f"{where}/{prim}")
        child_in_scan = in_scan or prim == "scan"
        if prim == "scan":
            facts.n_scans += 1
        for sub in _sub_jaxprs(eqn):
            _walk(sub, facts, child_in_scan, f"{where}/{prim}")


def _note_aval(aval, facts: JaxprFacts, where: str) -> None:
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None:
        return
    name = str(dtype)
    if name in WIDE_DTYPES:
        entry = (where, name)
        if entry not in facts.wide_values:
            facts.wide_values.append(entry)
    if name == "float32" and shape is not None and len(shape) == 1:
        try:
            width = int(shape[0])
        except TypeError:  # symbolic dim: not a concrete footprint
            return
        facts.max_f32_vector = max(facts.max_f32_vector, width)


def jaxpr_facts(fn, *args) -> JaxprFacts:
    """Trace ``fn(*args)`` (ShapeDtypeStructs welcome) and collect the
    contract-relevant facts from its jaxpr."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    facts = JaxprFacts(
        scan_collectives={}, outside_collectives={}, scan_callbacks=[],
        wide_values=[], max_f32_vector=0, n_scans=0,
    )
    _walk(closed.jaxpr, facts, in_scan=False, where="entry")
    return facts


# ---------------------------------------------------------------------------
# Contract verdicts
# ---------------------------------------------------------------------------


def exchange_key(exchange: str, plastic: bool) -> str:
    """The ``collectives_per_step`` key for a configuration: the exchange
    flavour, ``+plastic`` when the exchange also carries the pre-trace
    vector."""
    return exchange + ("+plastic" if plastic else "")


def check_jaxpr_facts(
    facts: JaxprFacts,
    contract,
    key: str,
    *,
    n_p: int,
    n_global: int,
    overlap: str = "off",
    event_cap_frac: float = 0.05,
) -> List[str]:
    """Contract violations of a traced step program (empty = clean)."""
    from ..kernels.dispatch import _FUSED_VECTOR_VMEM_BUDGET, event_id_cap

    problems: List[str] = []
    expected = contract.collectives_per_step.get(key)
    if expected is None:
        problems.append(
            f"exchange {key!r} is not a declared configuration of engine "
            f"{contract.engine!r} (contract keys: "
            f"{sorted(contract.collectives_per_step)})"
        )
        return problems
    got = sum(facts.scan_collectives.values())
    if got != expected:
        problems.append(
            f"engine {contract.engine!r} [{key}]: {got} collective(s) per "
            f"step in the scan body ({facts.scan_collectives}), contract "
            f"says exactly {expected}"
        )
    bad_kinds = sorted(
        set(facts.scan_collectives) - set(contract.allowed_collectives)
    )
    if bad_kinds:
        problems.append(
            f"engine {contract.engine!r} [{key}]: collective kind(s) "
            f"{bad_kinds} not in the contract's allowed set "
            f"{contract.allowed_collectives}"
        )
    if facts.scan_callbacks:
        problems.append(
            f"engine {contract.engine!r} [{key}]: host callback inside "
            f"the scan body: {facts.scan_callbacks} (device-to-host "
            "round-trip in the hot loop)"
        )
    for where, dtype in facts.wide_values:
        problems.append(
            f"engine {contract.engine!r} [{key}]: {dtype} value at "
            f"{where} — unintended 8-byte promotion (engines are f32/s32)"
        )
    # -- VMEM footprint: the dispatcher's own inequalities, re-derived
    #    from the contract's vector counts and the actual widths
    np_bytes = contract.resident_np_vectors * 4 * n_p
    if np_bytes > _FUSED_VECTOR_VMEM_BUDGET:
        problems.append(
            f"engine {contract.engine!r} [{key}]: "
            f"{contract.resident_np_vectors} resident (n_p={n_p}) f32 "
            f"vectors = {np_bytes} bytes exceeds the "
            f"{_FUSED_VECTOR_VMEM_BUDGET}-byte VMEM budget — the "
            "selector should have refused this partition"
        )
    ng_vectors = contract.resident_nglobal_vectors
    if overlap != "off" and contract.overlap_nglobal_vectors is not None:
        ng_vectors = contract.overlap_nglobal_vectors
    ng_bytes = ng_vectors * 4 * n_global
    if ng_bytes > _FUSED_VECTOR_VMEM_BUDGET:
        problems.append(
            f"engine {contract.engine!r} [{key}]: {ng_vectors} resident "
            f"(n_global={n_global}) f32 vectors = {ng_bytes} bytes "
            f"exceeds the {_FUSED_VECTOR_VMEM_BUDGET}-byte VMEM budget"
        )
    if contract.id_buffer_budget is not None:
        id_bytes = 4 * event_id_cap(n_global, event_cap_frac)
        if id_bytes > contract.id_buffer_budget:
            problems.append(
                f"engine {contract.engine!r} [{key}]: compressed spike-id "
                f"buffer {id_bytes} bytes exceeds its "
                f"{contract.id_buffer_budget}-byte budget"
            )
    # cross-check against what was actually traced: every f32 vector must
    # stay within a small constant factor of the aligned activity width
    # (lane alignment to 128 plus the flattened padded delay ring /
    # event row blocks) — an O(n^2) or O(k*n_global) materialization
    # blows past this bound immediately
    aligned = -(-max(n_global, n_p) // 128) * 128
    bound = 8 * aligned
    if facts.max_f32_vector > bound:
        problems.append(
            f"engine {contract.engine!r} [{key}]: program materializes an "
            f"f32 vector of width {facts.max_f32_vector} — beyond "
            f"8x the aligned activity width ({bound}); the contract's "
            "footprint estimate no longer covers it"
        )
    return problems


def check_hlo_text(
    hlo_text: str, contract, key: str, steps: int
) -> List[str]:
    """Contract violations visible in the compiled post-SPMD HLO."""
    problems: List[str] = []
    expected = contract.collectives_per_step.get(key)
    if expected is None:
        return [f"exchange {key!r} not declared for {contract.engine!r}"]
    stats = analyze_hlo(hlo_text)
    got = stats.collective_count
    if got != expected * steps:
        problems.append(
            f"engine {contract.engine!r} [{key}]: compiled HLO executes "
            f"{got} collectives over {steps} steps "
            f"({stats.collective_counts}), contract says "
            f"{expected}/step = {expected * steps}"
        )
    allowed_hlo = {k.replace("_", "-") for k in contract.allowed_collectives}
    bad = sorted(
        k for k, v in stats.collective_counts.items()
        if v and k not in allowed_hlo
    )
    if bad:
        problems.append(
            f"engine {contract.engine!r} [{key}]: HLO collective kind(s) "
            f"{bad} not allowed by the contract"
        )
    for comp, instr, dtype in wide_dtype_ops(hlo_text):
        problems.append(
            f"engine {contract.engine!r} [{key}]: compiled HLO "
            f"materializes {dtype} at {comp}/%{instr}"
        )
    return problems


# ---------------------------------------------------------------------------
# The selector matrix
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CaseSpec:
    """One eligible configuration of the selector matrix."""

    name: str
    k: int
    engine: str  # expected selected engine
    exchange: str  # 'identity' | 'dense' | 'index'
    plastic: bool = False
    gather: str = "dense"
    overlap: str = "off"

    @property
    def key(self) -> str:
        return exchange_key(self.exchange, self.plastic)


def contract_matrix() -> List[CaseSpec]:
    """Every eligible (engine x exchange x overlap x gather x k) row the
    checker lowers.  k is capped at 2 — partition count scales widths,
    not program structure, and the contracts are per-step properties."""
    specs: List[CaseSpec] = [
        CaseSpec("k1_fused", 1, "fused", "identity"),
        CaseSpec("k1_fused_plastic", 1, "fused_plastic", "identity",
                 plastic=True),
        CaseSpec("k1_fused_event", 1, "fused_event", "identity",
                 gather="event"),
        CaseSpec("k1_unfused", 1, "unfused", "identity"),
        CaseSpec("k1_unfused_plastic", 1, "unfused", "identity",
                 plastic=True),
    ]
    for ex in ("dense", "index"):
        for ov in ("off", "local", "double_buffer"):
            specs.append(CaseSpec(
                f"k2_split_{ex}_{ov}", 2, "fused_split", ex, overlap=ov,
            ))
            specs.append(CaseSpec(
                f"k2_split_plastic_{ex}_{ov}", 2, "fused_split_plastic",
                ex, plastic=True, overlap=ov,
            ))
        for ov in ("off", "local"):
            specs.append(CaseSpec(
                f"k2_split_event_{ex}_{ov}", 2, "fused_split_event", ex,
                gather="event", overlap=ov,
            ))
    specs.append(CaseSpec("k2_unfused_dense", 2, "unfused", "dense"))
    specs.append(CaseSpec(
        "k2_unfused_index_plastic", 2, "unfused", "index", plastic=True,
    ))
    return specs


_NET_N = 160  # tiny fixed topology: contracts are structural, not scale


def _build_sim(spec: CaseSpec):
    """(sim, n_p, n_global) for a matrix row — interpret-mode Pallas for
    the fused engines (the TPU kernel bodies, lowerable on CPU), the ref
    oracles for the unfused fallback (its production CPU path)."""
    from ..core.partition import block_partition
    from ..snn.network import balanced_ei, to_dcsr
    from ..snn.simulator import SimConfig, Simulator

    net = balanced_ei(_NET_N, stdp=spec.plastic, seed=7, delay_steps=5)
    d = to_dcsr(
        net, assignment=block_partition(_NET_N, spec.k), uniform=True
    )
    fused = spec.engine != "unfused"
    cfg = SimConfig(
        backend="pallas_interpret" if fused else "ref",
        fused=fused,
        exchange="dense" if spec.exchange == "identity" else spec.exchange,
        gather=spec.gather,
        overlap=spec.overlap,
        record_raster=False,
        record_v=False,
    )
    if spec.k == 1:
        return Simulator(d, cfg), _NET_N, _NET_N
    from ..snn.dist_sim import DistSimulator

    dsim = DistSimulator(d, cfg)
    return dsim, _NET_N // spec.k, _NET_N


def _sds(tree):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
    )


def run_case(
    spec: CaseSpec, steps: int = 4, hlo: bool = True
) -> List[str]:
    """All contract violations of one matrix row (empty = clean)."""
    import jax

    from ..kernels.dispatch import ENGINE_CONTRACTS

    sim, n_p, n_global = _build_sim(spec)
    choice = sim.engine_choice
    problems: List[str] = []
    if choice.engine != spec.engine:
        problems.append(
            f"selector picked {choice.engine!r} ({choice.reason}), matrix "
            f"row expects {spec.engine!r}"
        )
        return problems
    if choice.overlap != spec.overlap:
        problems.append(
            f"selector resolved overlap={choice.overlap!r}, matrix row "
            f"expects {spec.overlap!r}"
        )
    contract = ENGINE_CONTRACTS[choice.engine]

    if spec.k == 1:
        state = _sds(jax.eval_shape(sim.init_state))

        def fn(st):
            return jax.lax.scan(sim._step, st, None, length=steps)

        facts = jaxpr_facts(fn, state)
        lowered = jax.jit(fn).lower(state) if hlo else None
    else:
        run_fn, args = sim._build_run(steps)
        state = _sds(jax.eval_shape(sim.init_state))
        sds_args = [_sds(a) for a in args]
        facts = jaxpr_facts(run_fn, *sds_args, state)
        lowered = (
            jax.jit(run_fn).lower(*sds_args, state) if hlo else None
        )

    problems += check_jaxpr_facts(
        facts, contract, spec.key, n_p=n_p, n_global=n_global,
        overlap=spec.overlap,
    )
    if lowered is not None:
        text = lowered.compile().as_text()
        problems += check_hlo_text(text, contract, spec.key, steps)
    return problems


def run_matrix(
    specs: Optional[List[CaseSpec]] = None,
    steps: int = 4,
    hlo: bool = True,
    verbose: bool = True,
) -> Tuple[List[Tuple[str, str]], int]:
    """((case name, violation) pairs, rows checked).  Also fails any
    engine that never appears in the matrix — a new engine must extend
    ``contract_matrix`` alongside its ``EngineContract``."""
    from ..kernels.dispatch import STEP_ENGINES

    specs = contract_matrix() if specs is None else specs
    uncovered = set(STEP_ENGINES) - {s.engine for s in contract_matrix()}
    violations: List[Tuple[str, str]] = [
        ("matrix", f"engine {e!r} has no contract_matrix row")
        for e in sorted(uncovered)
    ]
    for spec in specs:
        t0 = time.perf_counter()
        try:
            problems = run_case(spec, steps=steps, hlo=hlo)
        except Exception as e:  # a row that fails to lower IS a violation
            problems = [f"failed to lower: {type(e).__name__}: {e}"]
        dt = time.perf_counter() - t0
        for p in problems:
            violations.append((spec.name, p))
        if verbose:
            status = "FAIL" if problems else "ok"
            print(f"  {spec.name:<34} {status}  ({dt:.1f}s)", flush=True)
    return violations, len(specs)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.contracts",
        description="Verify every engine's declared contract against its "
                    "lowered program (see docs/ANALYSIS.md).",
    )
    ap.add_argument("--steps", type=int, default=4,
                    help="scan length to lower (default 4)")
    ap.add_argument("--only", default="",
                    help="run only matrix rows whose name contains this")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip the compile+HLO pass (jaxpr checks only)")
    ap.add_argument("--list", action="store_true",
                    help="print the matrix rows and exit")
    args = ap.parse_args(argv)

    specs = [
        s for s in contract_matrix()
        if not args.only or args.only in s.name
    ]
    if args.list:
        for s in specs:
            print(f"{s.name}: k={s.k} engine={s.engine} key={s.key} "
                  f"gather={s.gather} overlap={s.overlap}")
        return 0

    # the k>1 rows need >= 2 devices; a fresh process can provision fake
    # host devices for itself (XLA_FLAGS is read once, at backend init)
    if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    max_k = max(s.k for s in specs) if specs else 1
    if jax.device_count() < max_k:
        print(
            f"error: {jax.device_count()} device(s) but the matrix needs "
            f"{max_k} (XLA already initialized? run in a fresh process "
            "or set XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
        return 2

    print(f"engine-contract matrix: {len(specs)} row(s), "
          f"steps={args.steps}")
    t0 = time.perf_counter()
    violations, n = run_matrix(
        specs, steps=args.steps, hlo=not args.no_hlo
    )
    wall = time.perf_counter() - t0
    if violations:
        print(f"\n{len(violations)} contract violation(s):")
        for case, problem in violations:
            print(f"  {case}: {problem}")
        return 1
    print(f"OK: {n} configuration(s) honor their engine contracts "
          f"({wall:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
