"""The reader of the program's own trace content (``bench/xspace.py``):
the wire-format decoder against ``jax.profiler.ProfileData`` and a small
trace recorded on a TPU v5e, the scope and span reduction on hand-built
events, and a CPU trace of a ``Session`` run."""
import os

import pytest

import bench_helpers  # noqa: F401  (puts the repository on the path)
from bench import trace, xspace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU_SMALL = os.path.join(DATA, "tpu_small.xplane.pb")
MAIN, WRITER = ("/host:CPU", 0), ("/host:CPU", 1)


def test_tf_op_names_the_device_ops():
    device, spans = xspace.read_xplane(TPU_SMALL)
    (ops,) = device.values()
    gathers = {stack for _, _, hlo, stack in ops if "kind=kCustom" in hlo}
    assert gathers == {"jit(<lambda>)/jit(_take)/gather"}
    assert {s.name for s in spans} == {
        trace.WINDOW_SPAN, "bench.chunk", "bench.monitor"}


def test_decoder_reads_every_event_as_profile_data_does():
    from jax.profiler import ProfileData

    ours = xspace.read_planes(TPU_SMALL)
    profile = ProfileData.from_file(TPU_SMALL)  # owns what its planes view
    theirs = list(profile.planes)
    assert [p.name for p in ours] == [p.name for p in theirs]
    n = 0
    for a, b in zip(theirs, ours):
        assert [line.name for line in a.lines] == [line.name for line in b.lines]
        for la, lb in zip(a.lines, b.lines):
            want = [(e.start_ns, e.duration_ns, e.name) for e in la.events]
            got = [(e.start_ns, e.end_ns - e.start_ns, e.name) for e in lb.events]
            assert got == want
            n += len(got)
    assert n > 100


def test_a_trace_without_program_names_reduces_to_todays_numbers():
    """The recorded trace holds no ``snn.*`` span or scope: busy time, op
    times and every idle gap's name are ``trace.reduce_xplane``'s."""
    pt = xspace.reduce_xplane(TPU_SMALL)
    assert pt.summary == trace.reduce_xplane(TPU_SMALL)
    assert pt.scope_seconds == {xspace.UNSCOPED: pytest.approx(pt.summary.busy_s)}
    assert pt.program_spans == {}


def _hand_built():
    # times in microseconds (the reduction takes ns): window 0..100 on the
    # run loop's thread; the writer thread writes 40..90
    us = 1000
    spans = [xspace.Span(th, a * us, b * us, name, stats) for th, a, b, name, stats in (
        (MAIN, 0, 100, trace.WINDOW_SPAN, {}),
        (MAIN, 0, 45, "bench.chunk", {}),
        (MAIN, 0, 44, "snn.chunk", {}),
        (MAIN, 1, 30, "snn.fetch", {}),
        (MAIN, 45, 60, "snn.ckpt", {}),
        (MAIN, 45, 59, "bench.save", {}),
        (MAIN, 46, 55, "snn.ckpt.sync", {"bytes": 100}),
        (MAIN, 55, 58, "snn.ckpt.capture", {"bytes": 40}),
        (MAIN, 70, 100, "bench.monitor", {}),
        (WRITER, 40, 90, "snn.write", {"bytes": 140}),
        (WRITER, 41, 89, "snn.write.part", {"bytes": 140}),
        (MAIN, 120, 130, "snn.chunk", {}),  # after the window
    )]
    ops = [(a * us, b * us, hlo, stack) for a, b, hlo, stack in (
        (10, 30, "%g = f32[8] fusion(f32[8] %x), kind=kCustom",
         "jit(_run)/while/body/closed_call/snn.deliver/d3/jit(_take)/gather"),
        (20, 40, "%s = f32[8] fusion(f32[8] %x), kind=kLoop",
         "jit(_run)/while/body/closed_call/snn.stdp/d3/mul"),
        (60, 65, "%n = f32[8] add(f32[8] %x, f32[8] %y)",
         "jit(_run)/while/body/closed_call/snn.neuron/add"),
        (65, 70, "%c = s32[] add(s32[] %i, s32[] %one)",
         "jit(_run)/while/body/add"),
        (0, 100, "%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t)",
         "jit(_run)/while"),
    )]
    return {"/device:TPU:0": ops}, spans


def test_scopes_spans_and_gaps_by_hand():
    device, spans = _hand_built()
    pt = xspace.reduce_events(device, spans)
    # the loop op holds the others and is not busy time; deliver and stdp
    # overlap 20..30, each keeps its own union
    assert pt.scope_seconds == {
        "snn.deliver": pytest.approx(20e-6), "snn.stdp": pytest.approx(20e-6),
        "snn.neuron": pytest.approx(5e-6), xspace.UNSCOPED: pytest.approx(5e-6)}
    assert pt.summary.busy_s == pytest.approx(40e-6)
    # each gap by the innermost span of either prefix on the run loop's
    # thread, never by the writer's spans that overlap it
    assert pt.summary.idle_gaps == [
        ("bench.monitor", pytest.approx(30e-6)),
        ("snn.ckpt.sync", pytest.approx(20e-6)),
        ("snn.fetch", pytest.approx(10e-6)),
    ]
    assert pt.program_spans == {
        "snn.chunk": [pytest.approx(44e-6)], "snn.fetch": [pytest.approx(29e-6)],
        "snn.ckpt": [pytest.approx(15e-6)], "snn.ckpt.sync": [pytest.approx(9e-6)],
        "snn.ckpt.capture": [pytest.approx(3e-6)], "snn.write": [pytest.approx(50e-6)],
        "snn.write.part": [pytest.approx(48e-6)]}
    rows = {r[0]: r[1:] for r in xspace.span_rows(pt.spans)}
    assert rows["snn.ckpt"] == (1, pytest.approx(15e-6), pytest.approx(1e-6), 0)
    assert rows["snn.ckpt.sync"] == (1, pytest.approx(9e-6), pytest.approx(9e-6), 100)
    assert rows["snn.write"] == (1, pytest.approx(50e-6), pytest.approx(2e-6), 140)
    assert "snn.ckpt.capture" in xspace.table(pt, steps=2)


def test_without_program_spans_the_gaps_are_todays():
    device, spans = _hand_built()
    bench_only = [s for s in spans if s.name.startswith(trace.SPAN_PREFIX)]
    pt = xspace.reduce_events(device, bench_only)
    today = trace.reduce_events(
        {chip: [e[:3] for e in evs] for chip, evs in device.items()},
        [(s.start_ns, s.end_ns, s.name) for s in bench_only])
    assert pt.summary == today


def test_top_scope():
    assert xspace.top_scope("jit(_run)/while/body/closed_call/snn.deliver/d1/gather") \
        == "snn.deliver"
    assert xspace.top_scope("jit(_run)/while/body/add") == xspace.UNSCOPED
    assert xspace.top_scope("") == xspace.UNSCOPED


def test_cpu_trace_of_a_session(tmp_path, capsys):
    """A trace without a window span: the whole trace is the window, and
    the program's spans carry their bytes."""
    import jax

    from repro.builder.rules import balanced_ei_rules
    from repro.snn import Session, SimConfig

    ses = Session(balanced_ei_rules(n=200, seed=1), SimConfig(align_k=8))
    ses.run(20, chunk_size=20)
    jax.profiler.start_trace(str(tmp_path))
    ses.run(40, chunk_size=20, checkpoint_every=20,
            checkpoint_dir=str(tmp_path / "ckpt"))
    ses.close()
    jax.profiler.stop_trace()
    pt = xspace.reduce_xplane(xspace.find_xplane(str(tmp_path)))
    ps = pt.program_spans
    assert len(ps["snn.chunk"]) == len(ps["snn.ckpt"]) == 2
    rows = {r[0]: r[1:] for r in xspace.span_rows(pt.spans)}
    assert rows["snn.write"][0] == 2 and rows["snn.write"][3] > 0
    assert xspace.main([str(tmp_path), "--steps", "40"]) == 0
    assert "snn.ckpt.sync" in capsys.readouterr().out
