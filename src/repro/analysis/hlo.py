"""Post-SPMD HLO analysis: loop-corrected FLOPs / HBM bytes / collective
bytes — the §Roofline inputs — parsed from ``compiled.as_text()`` (the
per-device program *after* GSPMD partitioning; the only place collectives
and the real per-device work exist).

Why not ``compiled.cost_analysis()``: XLA's cost analysis counts a
``while`` body (every ``lax.scan``: the simulation step loop, layer
stacks, attention K/V chunk loops) exactly ONCE, underestimating
scan-based programs by the trip count.  This module:

  1. splits the module into computation blocks,
  2. recovers each while's trip count from the comparison constant in its
     *condition* region and propagates multipliers through nested loops,
  3. counts dot FLOPs (2 x prod(result dims) x prod(contracted dims) —
     >= 99% of model FLOPs; elementwise flops are ignored by design),
  4. counts HBM traffic at fusion granularity (operands + result of each
     top-level op; instructions inside fused computations are free),
  5. charges each collective its operand bytes.

All three x the enclosing loop multiplier.  Raw cost_analysis numbers are
recorded alongside for reference.

Beyond the roofline terms, the parser feeds the engine-contract checker
(:mod:`repro.analysis.contracts`): per-kind collective *counts* pin the
one-collective-per-step contract of the split engines against the
compiled program, and :func:`dtype_census` / :func:`wide_dtype_ops`
surface any f64/s64 promotion XLA actually materialized.

This module absorbed ``repro.launch.hlo_analysis`` (now a deprecated
compat shim re-exporting from here).

Hardware model (TPU v5e target): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link per chip

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# dtypes a simulation step must never materialize: the engines are
# pinned to f32 state / s32 indices, so any 8-byte (or complex) result
# in the compiled program is an accidental promotion
WIDE_DTYPES = ("f64", "s64", "u64", "c128")
# ...except the threefry RNG's own counter: jax lowers the (seed, t) noise
# draw's counter as a u64 iota (op_name ``.../iota_2x32_shape``) shifted
# right by a scalar u64 literal and split into two u32 words; that u64
# never leaves the RNG.  A scalar literal carries no data, so it is exempt
# wherever XLA hoists it.
RNG_COUNTER_OP = '/iota_2x32_shape"'


def _rng_counter_u64(line: str, shape: str, op: str) -> bool:
    return RNG_COUNTER_OP in line or (
        op == "constant" and shape.startswith("u64[]")
    )

# ops that move no HBM bytes / are bookkeeping
_FREE_OPS = {
    "bitcast", "get-tuple-element", "tuple", "parameter", "constant",
    "while", "conditional", "call", "after-all", "partition-id",
    "replica-id", "custom-call",
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_WHILE_ATTR_RE = re.compile(
    r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)"
)
_CONST_RE = re.compile(r"=\s*[su]\d+\[\]\s+constant\((\d+)\)")
_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _parse_instr(line: str):
    """Parse '  [ROOT] %name = <type> op(operands), attrs' with a scanner
    that survives tuple types and nested parens.  Returns
    (name, type_str, op, operands, tail) or None."""
    line = _COMMENT_RE.sub("", line).strip()
    if line.startswith("ROOT "):
        line = line[5:]
    if " = " not in line or not line.startswith("%"):
        return None
    name, rest = line.split(" = ", 1)
    rest = rest.strip()
    if rest.startswith("("):  # tuple type: skip balanced parens
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    type_str = rest[: i + 1]
                    rest = rest[i + 1:].strip()
                    break
        else:
            return None
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        type_str = rest[:sp]
        rest = rest[sp + 1:].strip()
    par = rest.find("(")
    if par < 0:
        return None
    op = rest[:par].strip()
    if not op or not re.fullmatch(r"[\w\-]+", op):
        return None
    depth = 0
    operands = ""
    for i in range(par, len(rest)):
        ch = rest[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                operands = rest[par + 1 : i]
                tail = rest[i + 1:]
                return (name.strip().lstrip("%"), type_str, op,
                        operands, tail)
    return None


def _split_operands(operands: str) -> List[str]:
    """Split an operand list on top-level commas only: shapes
    (``f32[64,64]{1,0}``), tuple types, and nested calls all carry commas
    inside brackets that a bare ``str.split(',')`` would tear apart."""
    out: List[str] = []
    depth = 0
    cur: List[str] = []
    for ch in operands:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        out.append(tail)
    return [t for t in out if t]


def _parse_shape(type_str: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _parse_shape(type_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo_text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if cur is None:
            m = _COMP_RE.match(stripped)
            if m:
                cur = m.group(1)
                comps[cur] = []
        else:
            if line.startswith("}"):  # unindented computation close
                cur = None
                continue
            comps[cur].append(line)
    return comps


_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _loop_multipliers(
    comps: Dict[str, List[str]],
) -> Tuple[Dict[str, float], Dict[str, Tuple[float, ...]]]:
    edges: List[Tuple[str, str, float]] = []
    for comp, lines in comps.items():
        for line in lines:
            if " while(" in line:
                m = _WHILE_ATTR_RE.search(line)
                if m:
                    cond, body = m.groups()
                    consts = [
                        float(c.group(1))
                        for l in comps.get(cond, ())
                        if (c := _CONST_RE.search(l))
                    ]
                    trip = max(consts) if consts else 1.0
                    edges.append((comp, body, max(trip, 1.0)))
                    edges.append((comp, cond, max(trip, 1.0)))
                    continue
            mc = _CALLS_RE.search(line)
            if mc and " sort(" not in line and " reduce(" not in line \
                    and " map(" not in line and " scatter(" not in line \
                    and " select-and-scatter(" not in line \
                    and " reduce-window(" not in line \
                    and " all-reduce(" not in line \
                    and " reduce-scatter(" not in line:
                edges.append((comp, mc.group(1), 1.0))
            mb = _BRANCH_RE.search(line)
            if mb:
                for b in mb.group(1).split(","):
                    edges.append((comp, b.strip().lstrip("%"), 1.0))
    mult: Dict[str, float] = {c: 1.0 for c in comps}
    chain: Dict[str, Tuple[float, ...]] = {c: () for c in comps}
    for _ in range(16):
        changed = False
        for parent, body, trip in edges:
            want = mult.get(parent, 1.0) * trip
            want_chain = chain.get(parent, ()) + (
                (trip,) if trip > 1 else ()
            )
            if mult.get(body, 1.0) != want:
                mult[body] = want
                chain[body] = want_chain
                changed = True
        if not changed:
            break
    return mult, chain


@dataclasses.dataclass
class HloStats:
    flops: float  # loop-corrected dot flops (per device)
    hbm_bytes: float  # loop-corrected fusion-level traffic (per device)
    collective_bytes_by_kind: Dict[str, int]
    collective_counts: Dict[str, int]
    largest_collectives: List[Tuple[str, int]]
    collective_text_bytes: int  # uncorrected single-count total
    n_whiles: int
    max_multiplier: float

    @property
    def collective_bytes(self) -> int:
        return int(sum(self.collective_bytes_by_kind.values()))

    @property
    def collective_count(self) -> int:
        """Loop-corrected total number of collectives executed (all
        kinds) — the quantity the engine contracts pin per step."""
        return int(sum(self.collective_counts.values()))


def analyze_hlo(hlo_text: str, top: int = 10) -> HloStats:
    comps = _split_computations(hlo_text)
    mult, chains = _loop_multipliers(comps)
    fused = {c for c in comps if c.startswith("fused") or ".fused" in c
             or c.startswith("wrapped")}

    # symbol table: name -> type_str
    types: Dict[str, str] = {}
    parsed: Dict[str, List] = {}
    for comp, lines in comps.items():
        plist = []
        for line in lines:
            m = _parse_instr(line)
            if m:
                plist.append(m)
                types[m[0]] = m[1]
        parsed[comp] = plist

    flops = 0.0
    hbm = 0.0
    by_kind: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    counts: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    largest: List[Tuple[str, int]] = []
    text_total = 0
    n_whiles = 0

    for comp, plist in parsed.items():
        factor = mult.get(comp, 1.0)
        in_fusion = comp in fused
        for name, type_str, op, operands, tail in plist:
            if op == "while":
                n_whiles += 1

            # -- dot flops (counted even inside fusions: compute is compute)
            if op in ("dot", "convolution"):
                res = _parse_shape(type_str)
                out_elems = 0
                for _, dims in res:
                    n = 1
                    for d in dims:
                        n *= d
                    out_elems += n
                contract = 1
                dm = _DIMS_RE.search(tail)
                toks = _split_operands(operands)
                first_operand = toks[0] if toks else ""
                parts = first_operand.split()
                lhs_name = parts[-1].lstrip("%") if parts else ""
                lhs_type = types.get(lhs_name, first_operand)
                lhs_shapes = _parse_shape(lhs_type)
                if dm and lhs_shapes:
                    dims = lhs_shapes[0][1]
                    for idx in dm.group(1).split(","):
                        if idx and int(idx) < len(dims):
                            contract *= dims[int(idx)]
                flops += 2.0 * out_elems * contract * factor

            if in_fusion:
                continue  # no HBM / collective accounting inside fusions

            # -- collective bytes
            kind = None
            for c in COLLECTIVES:
                if op == c or op == c + "-start":
                    kind = c
                    break
            ob = 0
            if op not in _FREE_OPS:
                res_bytes = _shape_bytes(type_str)
                trips = set(chains.get(comp, ()))
                op_toks = _split_operands(operands)
                for tok in op_toks:
                    parts = tok.split()
                    cand = parts[-1].lstrip("%") if parts else tok
                    tstr = types.get(cand, tok)
                    b = _shape_bytes(tstr)
                    # stacked operand sliced per loop iteration (fused
                    # dynamic-slice): one of the two leading dims equals an
                    # enclosing trip count (>= 8 to avoid small-dim
                    # collisions) -> charge one slice per iteration
                    shp = _parse_shape(tstr)
                    if shp and shp[0][1]:
                        match = max(
                            (d for d in shp[0][1][:2]
                             if d >= 8 and float(d) in trips),
                            default=0,
                        )
                        if match:
                            b //= match
                    ob += b
                if op in ("dynamic-slice", "gather"):
                    # reads only the slice/rows it produces, not the
                    # whole operand (critical inside layer loops where the
                    # operand is the full stacked parameter array)
                    traffic = 2 * res_bytes
                elif op == "dynamic-update-slice":
                    upd = op_toks[1] if len(op_toks) > 1 else ""
                    cand = upd.split()[-1].lstrip("%") if upd else ""
                    ub = _shape_bytes(types.get(cand, upd))
                    traffic = 2 * ub
                elif op == "scatter":
                    ub = 0
                    if len(op_toks) >= 3:
                        cand = op_toks[2].split()[-1].lstrip("%")
                        ub = _shape_bytes(types.get(cand, op_toks[2]))
                    traffic = 3 * ub
                elif op in ("broadcast", "iota", "rng", "rng-bit-generator"):
                    traffic = res_bytes
                else:
                    traffic = ob + res_bytes
                hbm += traffic * factor
            if kind is not None and not op.endswith("-done"):
                by_kind[kind] += ob * factor
                counts[kind] += factor
                text_total += ob
                largest.append((kind, int(ob * factor)))

    largest.sort(key=lambda t: -t[1])
    return HloStats(
        flops=flops,
        hbm_bytes=hbm,
        collective_bytes_by_kind={k: int(v) for k, v in by_kind.items()},
        collective_counts={k: int(v) for k, v in counts.items()},
        largest_collectives=largest[:top],
        collective_text_bytes=text_total,
        n_whiles=n_whiles,
        max_multiplier=max(mult.values()) if mult else 1.0,
    )


def dtype_census(hlo_text: str) -> Dict[str, int]:
    """Instruction-result counts per element dtype across the module —
    how much of the compiled program runs at each precision."""
    census: Dict[str, int] = {}
    for lines in _split_computations(hlo_text).values():
        for line in lines:
            m = _parse_instr(line)
            if m is None:
                continue
            for dt, _ in _parse_shape(m[1]):
                census[dt] = census.get(dt, 0) + 1
    return census


def wide_dtype_ops(
    hlo_text: str, forbidden: Tuple[str, ...] = WIDE_DTYPES
) -> List[Tuple[str, str, str]]:
    """Every instruction whose *result* carries a forbidden (8-byte)
    dtype: ``(computation, instruction name, dtype)``.  The one exemption
    is the RNG's u64 counter (``RNG_COUNTER_OP``); any other wide
    result is reported — an empty return is the contract."""
    out: List[Tuple[str, str, str]] = []
    for comp, lines in _split_computations(hlo_text).items():
        for line in lines:
            m = _parse_instr(line)
            if m is None:
                continue
            for dt, _ in _parse_shape(m[1]):
                if dt in forbidden and not (
                    dt == "u64" and _rng_counter_u64(line, m[1], m[2])
                ):
                    out.append((comp, m[0], dt))
    return out


def roofline_terms(
    flops_per_device: float,
    bytes_per_device: float,
    collective_bytes: float,
    chips: int = 1,
) -> Dict[str, float]:
    """The three §Roofline terms in seconds (per-device inputs)."""
    return dict(
        compute_s=flops_per_device / PEAK_FLOPS,
        memory_s=bytes_per_device / HBM_BW,
        collective_s=collective_bytes / ICI_BW,
    )


def dominant_term(terms: Dict[str, float]) -> str:
    return max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k]
    )
