"""HLO analyzer: loop-trip recovery, collective operand charging, dot
flop counting — on a hand-written miniature HLO module and on a real
lowered program.  The parser lives in ``repro.analysis.hlo``;
``repro.launch.hlo_analysis`` remains as a deprecated compat shim and
both import paths are covered here."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import cost_analysis
from repro.analysis.hlo import (
    analyze_hlo, _split_computations, _loop_multipliers, _parse_instr,
    roofline_terms, dominant_term, dtype_census, wide_dtype_ops,
)

MINI_HLO = """\
HloModule mini

%cond.1 (p: (s32[])) -> pred[] {
  %p = (s32[]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%body.2 (p: (s32[])) -> (s32[]) {
  %p = (s32[]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,128] parameter(1)
  %ar = f32[8,128] all-reduce(%x), replica_groups={}, to_apply=%sum.3
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[]) tuple(%ni)
}

%sum.3 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main.9 (x: f32[16,64], w: f32[64,32]) -> f32[16,32] {
  %x = f32[16,64] parameter(0)
  %w = f32[64,32] parameter(1)
  %init = (s32[]) tuple()
  %loop = (s32[]) while(%init), condition=%cond.1, body=%body.2
  %ag = f32[32,64] all-gather(%x), dimensions={0}
  ROOT %d = f32[16,32] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_parse_instr_tuple_types():
    r = _parse_instr(
        "  %w.1 = (s32[], f32[4,8]{1,0}, /*index=2*/f32[2]{0}) "
        "while(%t), condition=%c, body=%b"
    )
    assert r is not None
    name, type_str, op, operands, tail = r
    assert name == "w.1" and op == "while" and operands == "%t"
    assert "condition=%c" in tail


def test_mini_hlo_loop_and_collectives():
    s = analyze_hlo(MINI_HLO)
    # all-reduce inside 12-trip loop: operand f32[8,128] = 4096 B x 12
    assert s.collective_bytes_by_kind["all-reduce"] == 4096 * 12
    # all-gather at top level: operand f32[16,64] = 4096 B x 1
    assert s.collective_bytes_by_kind["all-gather"] == 4096
    assert s.collective_counts["all-reduce"] == 12
    # dot: 2 * 16*32 * 64
    assert s.flops == 2 * 16 * 32 * 64
    assert s.n_whiles == 1
    assert s.max_multiplier == 12.0


def test_real_lowering_scan_flops_corrected():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    lo = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((9, 64, 64), jnp.float32),
    )
    comp = lo.compile()
    s = analyze_hlo(comp.as_text())
    want = 2 * 64 * 64 * 64 * 9
    assert abs(s.flops - want) / want < 0.05, (s.flops, want)
    # XLA's own analysis undercounts by the trip count (the bug this
    # module exists to fix)
    xla = cost_analysis(comp)["flops"]
    assert xla < want / 4


def test_roofline_terms_and_dominant():
    t = roofline_terms(197e12, 819e9 * 2, 50e9 * 3)
    assert t["compute_s"] == 1.0
    assert t["memory_s"] == 2.0
    assert t["collective_s"] == 3.0
    assert dominant_term(t) == "collective_s"


def test_dtype_census_and_wide_ops():
    census = dtype_census(MINI_HLO)
    assert census["f32"] > 0 and census["s32"] > 0
    assert wide_dtype_ops(MINI_HLO) == []
    wide = MINI_HLO.replace(
        "ROOT %d = f32[16,32] dot", "ROOT %d = f64[16,32] dot"
    )
    hits = wide_dtype_ops(wide)
    assert any(instr == "d" and dtype == "f64" for _, instr, dtype
               in hits), hits


def test_compat_shim_warns_and_matches():
    import importlib

    import repro.launch.hlo_analysis as shim

    shim._DEPRECATION_WARNED.clear()
    with pytest.warns(DeprecationWarning, match="repro.analysis.hlo"):
        fn = shim.analyze_hlo
    assert fn is analyze_hlo
    # warn-once: a second access of the same name stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert shim.analyze_hlo is analyze_hlo
    # the old from-import form resolves every legacy name
    mod = importlib.import_module("repro.launch.hlo_analysis")
    for name in ("_split_computations", "_loop_multipliers",
                 "_parse_instr", "roofline_terms", "dominant_term",
                 "PEAK_FLOPS"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            assert getattr(mod, name) is not None
    s = shim.analyze_hlo(MINI_HLO)
    assert s.collective_counts["all-reduce"] == 12


def test_wide_ops_exempt_only_the_rng_counter():
    """The threefry RNG's u64 counter iota (and the scalar u64 shift
    literal beside it) is not a promotion; any other u64 result is."""
    rng = (
        'ENTRY %main () -> u64[8] {\n'
        '  %c = u64[] constant(32), metadata={op_name="jit(f)/shard_map"}\n'
        '  ROOT %i = u64[8]{0} iota(), iota_dimension=0, metadata={op_name='
        '"jit(f)/jit(_uniform)/iota_2x32_shape"}\n'
        '}\n'
    )
    assert wide_dtype_ops(rng) == []
    leak = rng.replace("/iota_2x32_shape", "/add")
    assert [(i, d) for _, i, d in wide_dtype_ops(leak)] == [("i", "u64")]
