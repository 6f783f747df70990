"""The program's own profiler names (``repro.obs``): every op of the step
sits in an ``snn.*`` scope, the run loop, checkpoint, write, restore and
build open their ``snn.*`` spans, and tracing changes no result."""
import glob
import os
import re
from collections import Counter

import jax
import numpy as np
import pytest

from repro import obs
from repro.builder.rules import balanced_ei_rules
from repro.io import load_binary
from repro.snn import Session, SimConfig
from repro.snn.monitors import RasterMonitor
from repro.snn.simulator import Simulator

# ops that only hold, move or call other ops
_TRIVIAL = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "copy", "copy-start", "copy-done", "call", "while",
})
# the scan's own loop counter and the stacking of each step's outputs
_SCAN_OWN = re.compile(r"^jit\(_run\)/while/body/(add|dynamic_update_slice)$")
_OPCODE = re.compile(r"=\s*(?:\([^()]*\)|\S+)\s+([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _computations(text):
    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            comps[cur].append(re.sub(r"/\*.*?\*/", "", line))
    return comps


def _scan_body_scopes(text):
    """(scopes seen, ops of the scan body outside any ``snn.`` scope) of a
    compiled chunk program.  A fusion without metadata of its own is named
    by the ops fused into it; ops XLA made from no traced op carry no name
    at all and are left to the chip's ``unscoped`` reading."""
    comps = _computations(text)
    scopes, unscoped, seen = Counter(), [], set()

    def walk(comp):
        if comp in seen:
            return
        seen.add(comp)
        for line in comps[comp]:
            op = _OPCODE.search(line).group(1)
            for key, callee in re.findall(r"(body|to_apply)=%?([\w.\-]+)",
                                          line):
                if key == "body" or op == "call":
                    walk(callee)
            if op in _TRIVIAL:
                continue
            names = set(_OP_NAME.findall(line))
            if not names and op == "fusion":
                callee = re.search(r"calls=%?([\w.\-]+)", line).group(1)
                names = set(_OP_NAME.findall("\n".join(comps[callee])))
            for name in names:
                top = re.search(r"(?:^|/)(snn\.[\w.]+)", name)
                if top:
                    scopes[top.group(1)] += 1
                elif not _SCAN_OWN.match(name):
                    unscoped.append((op, name))

    loops = [line for lines in comps.values() for line in lines
             if " while(" in line
             and 'op_name="jit(_run)/while"' in line]
    assert len(loops) == 1, loops
    walk(re.search(r"body=%?([\w.\-]+)", loops[0]).group(1))
    return scopes, unscoped


@pytest.mark.parametrize("plastic", [False, True])
def test_every_step_op_is_scoped(plastic):
    ses = Session(balanced_ei_rules(n=200, stdp=plastic, seed=1),
                  SimConfig(align_k=8, fused=False, record_raster=True))
    d = ses.describe()
    assert (d["step_engine"], d["backend"]) == ("unfused", "ref")
    sim = ses._current_engine.sim
    lowered = Simulator._run.lower(
        sim, sim.dev, sim._noise_ids, sim._touch(), sim.init_state(0),
        steps=3,
    )
    scopes, unscoped = _scan_body_scopes(lowered.compile().as_text())
    assert unscoped == []
    # at k = 1 the exchange is the identity: it emits no op
    want = set(obs.SCOPES) - {obs.EXCHANGE} - (set() if plastic
                                               else {obs.STDP})
    assert set(scopes) == want


# -- host spans -------------------------------------------------------------

def _traced(fn, tmp):
    """``fn()`` under the profiler; returns its result and the trace's
    ``snn.*`` host events as (thread, start, end, name, stats); each
    thread is a line of its own."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("snn."):
                    events.append(((plane.name, i), ev.start_ns,
                                   ev.start_ns + ev.duration_ns, ev.name,
                                   dict(ev.stats)))
    return out, events


def _inside(events, child, parent):
    """How many ``child`` events lie in a ``parent`` event of their
    thread."""
    return sum(
        any(p[3] == parent and p[0] == line and p[1] <= a and b <= p[2]
            for p in events)
        for line, a, b, name, _ in events if name == child
    )


def _workflow(root, steps=60):
    """Build from rules, run with checkpoints, save, restore, continue."""
    cfg = SimConfig(align_k=8, seed=3)
    ses = Session(balanced_ei_rules(n=200, stdp=True, seed=1), cfg)
    ras = RasterMonitor()
    ses.run(steps, monitors=[ras], chunk_size=20, checkpoint_every=20,
            checkpoint_dir=os.path.join(root, "ckpt"), max_to_keep=2)
    ses.save(os.path.join(root, "final"))
    ses2 = Session.restore(os.path.join(root, "final"), cfg=cfg)
    again = RasterMonitor()
    ses2.run(20, monitors=[again], chunk_size=20)
    ses.close()
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(ses2.state)]
    return ras.raster, again.raster, leaves


def test_run_checkpoint_restore_and_build_open_their_spans(tmp_path):
    _, ev = _traced(lambda: _workflow(str(tmp_path / "run")),
                    str(tmp_path / "trace"))
    names = Counter(e[3] for e in ev)
    # three chunks with checkpoints, one after the restore
    assert names[obs.CHUNK] == names[obs.READOUT] == 4
    assert _inside(ev, obs.DISPATCH, obs.CHUNK) == 4
    assert _inside(ev, obs.FETCH, obs.CHUNK) == 4
    assert names[obs.CKPT] == 3
    for child in (obs.CKPT_SYNC, obs.CKPT_CAPTURE):
        # one in each boundary, one in the save called directly
        assert names[child] == 4
        assert _inside(ev, child, obs.CKPT) == 3
        assert all(e[4]["bytes"] > 0 for e in ev if e[3] == child)
    # each boundary queues its write and a retention GC; the direct
    # save its write
    assert names[obs.CKPT_ENQUEUE] == 7
    assert _inside(ev, obs.CKPT_ENQUEUE, obs.CKPT) == 6
    main = {e[0] for e in ev if e[3] == obs.CHUNK}
    writes = [e for e in ev if e[3] == obs.WRITE]
    assert len(writes) == 4
    assert all(e[0] not in main and e[4]["bytes"] > 0 for e in writes)
    assert _inside(ev, obs.WRITE_PART, obs.WRITE) == names[obs.WRITE_PART] \
        == 4
    assert names[obs.RESTORE_READ] == 1
    assert obs.RESTORE_RESHARD not in names  # restored at the same k
    assert names[obs.BUILD_RULES] == 1
    # the built and the restored session each repack their partition
    assert names[obs.BUILD_ELL] >= 2
    assert names[obs.BUILD_PLACE] >= 2
    assert set(names) <= set(obs.SPANS)


def test_tracing_changes_no_result(tmp_path):
    off = _workflow(str(tmp_path / "off"))
    on, _ = _traced(lambda: _workflow(str(tmp_path / "on")),
                    str(tmp_path / "trace"))
    for a, b in zip(off[:2], on[:2]):
        np.testing.assert_array_equal(a, b)
    assert len(off[2]) == len(on[2])
    for a, b in zip(off[2], on[2]):
        np.testing.assert_array_equal(a, b)
    net_a, sim_a, t_a = load_binary(str(tmp_path / "off" / "final"))
    net_b, sim_b, t_b = load_binary(str(tmp_path / "on" / "final"))
    assert t_a == t_b
    for pa, pb in zip(net_a.parts, net_b.parts):
        for key in ("row_ptr", "col_idx", "vtx_state", "edge_state"):
            np.testing.assert_array_equal(getattr(pa, key), getattr(pb, key))
    for p in sim_a:
        for key in sim_a[p]:
            np.testing.assert_array_equal(sim_a[p][key], sim_b[p][key])
