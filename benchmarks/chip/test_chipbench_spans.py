"""The program's spans on the profiler's clock, and the readers built on
them: a toy server traced on the CPU, a hand-made trace with known
answers, and small traces recorded on a TPU v5e."""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import programtrace as pt  # noqa: E402
from chipbench import tracereduce as tr  # noqa: E402

TOY = {"arch": "din", "n_items": 1000, "n_cats": 50, "embed_dim": 8,
       "short_len": 8, "long_len": 32, "mlp_hidden": [32, 16], "ctx_dim": 4,
       "m": 12, "tau": 2}
FIXTURES = {"fp32_read": "trace_fp32_read_spans.json",
            "int8f_read": "trace_int8f_read_spans.json",
            "fp32x4_read": "trace_fp32x4_read_spans.json"}
CONFIGS = {"fp32_read": "sdim_paper_fp32",
           "int8f_read": "sdim_paper_int8_fused",
           "fp32x4_read": "sdim_paper_fp32x4_fused"}
READERS = ("assemble_ms", "bse_read_ms", "sdim_query_roofline",
           "sdim_fused_serve_roofline", "mfu", "idle_share", "host_idle_ms",
           "to_host_ms", "scorer_device_ms", "embed_device_ms",
           "allreduce_device_ms")
# the recorded cells in which a reader finds what it reads; it must read
# nothing in the others (the four-chip cell's candidate embedding runs on
# chip 0, and the readers read its last chip)
ONLY = {"sdim_query_roofline": {"fp32_read"},
        "sdim_fused_serve_roofline": {"int8f_read", "fp32x4_read"},
        "embed_device_ms": {"int8f_read"},
        "allreduce_device_ms": {"fp32x4_read"}}


def toy_server(tracer, fused):
    import jax.numpy as jnp

    from chipbench import harness
    from repro.launch.serve import build_ctr_server, synthetic_requests

    cfg = harness.model_config(TOY)
    _, _, server = build_ctr_server(
        cfg, backend="auto", fused=fused, tracer=tracer,
        table_dtype=jnp.int8 if fused else jnp.float32)
    return server, synthetic_requests(cfg, 3, 16)


def profiled_burst(tmp_path, server, reqs):
    """One ``handle_requests`` call under the JAX profiler, annotated as
    the benchmark annotates it; the trace's file."""
    import jax
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(tr.WINDOW), TraceAnnotation(tr.BURST):
            server.handle_requests(reqs)
    finally:
        jax.profiler.stop_trace()
    return tr.find_xplane(str(tmp_path))


@pytest.mark.parametrize("fused", [False, True],
                         ids=["two_dispatch", "fused"])
def test_every_span_reaches_the_profiler_trace(tmp_path, fused):
    from repro.serve.tracing import Tracer

    tracer = Tracer(enabled=False)
    server, reqs = toy_server(tracer, fused)
    server.handle_requests(reqs)          # ingest and compile, untraced
    tracer.enabled = True
    path = profiled_burst(tmp_path, server, reqs)
    assert [h[0] for h in tr.load(path).host] == [tr.WINDOW, tr.BURST]
    progs = pt.load(path)

    spans = [s for t in tracer.finished() for s in t.spans]
    names = {s.name for s in spans}
    assert {"ctr.request", "ctr.assemble", "ctr.lookup", "bse.lookup",
            "ctr.score", "ctr.device_wait", "ctr.to_host"} <= names
    assert ({"ctr.embed_targets", "bse.serve_candidates"} <= names) == fused
    assert ("bse.fetch_many" in names) != fused
    assert sorted(e[0] for e in progs.program) == sorted(
        s.name for s in spans)

    # pair each span with the event of its name in the same order
    event = {}
    for name in names:
        mine = sorted((s for s in spans if s.name == name),
                      key=lambda s: s.t0)
        theirs = sorted(e for e in progs.program if e[0] == name)
        event.update((s.span_id, e) for s, e in zip(mine, theirs))
    for s in spans:
        _, a, b = event[s.span_id]
        dur = (b - a) * 1e-9
        assert abs(dur - s.duration_s) <= max(0.05 * s.duration_s, 50e-6), (
            s.name, dur, s.duration_s)
        if s.parent_id is not None:
            _, pa, pb = event[s.parent_id]
            assert pa <= a and b <= pb, (s.name, "outside its parent")


@pytest.mark.parametrize("mode", ["no_tracer", "disabled"])
def test_no_program_event_without_an_enabled_tracer(tmp_path, mode):
    from repro.serve.tracing import Tracer

    tracer = None if mode == "no_tracer" else Tracer(enabled=False)
    server, reqs = toy_server(tracer, False)
    server.handle_requests(reqs)
    path = profiled_burst(tmp_path, server, reqs)
    assert [h[0] for h in tr.load(path).host] == [tr.WINDOW, tr.BURST]
    assert pt.load(path).program == []


# window 0..1000; bursts 100..500 and 600..900 with a wait between.
# Burst 1: request 110..490, assemble 110..200, score 300..420 holding a
# device wait 320..420, to_host 420..480. Burst 2: request 610..890, score
# 700..800 holding a device wait 720..800. The device runs the embedding
# 200..260 and the scorer 330..400 and 730..790: idle inside each device
# wait (320..330, 400..420, 720..730, 790..800) is the wait's, not the
# host's.
HAND = tr.Trace(
    ops=[[("%copy = f32[8]{0} copy(x)", 200, 260),
          ("%fusion.1 = f32[8]{0} fusion(x)", 330, 400),
          ("%fusion.1 = f32[8]{0} fusion(x)", 730, 790)]],
    host=[(tr.WINDOW, 0, 1000), (tr.BURST, 100, 500), (tr.WAIT, 500, 600),
          (tr.BURST, 600, 900)])
HAND_PROGS = pt.Programs(
    program=[("ctr.request", 110, 490), ("ctr.assemble", 110, 200),
             ("ctr.score", 300, 420), ("ctr.device_wait", 320, 420),
             ("ctr.to_host", 420, 480), ("ctr.request", 610, 890),
             ("ctr.score", 700, 800), ("ctr.device_wait", 720, 800)],
    modules=[[("jit_ctr_embed_targets(7)", 200, 260),
              ("jit_ctr_score_interest(9)", 330, 400),
              ("jit_ctr_score_interest(9)", 730, 790)]])


def hand_context(**kw):
    red = tr.reduce(HAND)
    return types.SimpleNamespace(
        trace=HAND, reduced=red, tracereduce=tr, programs=HAND_PROGS,
        bursts=[(s, e, 1, 128) for s, e in red.bursts], spans=[], **kw)


def test_innermost_span_labels():
    assert pt.innermost([("a", 0, 10), ("b", 2, 4), ("c", 4, 6)]) == [
        (0, 2, "a"), (2, 4, "b"), (4, 6, "c"), (6, 10, "a")]
    assert pt.covered([(0, 10), (20, 30)], 5, 25) == 10
    assert pt.covered([(0, 10), (20, 30)], 10, 20) == 0


def test_idle_by_span_on_the_hand_trace():
    red = tr.reduce(HAND)
    assert pt.device_lead(HAND_PROGS) == 0
    got = pt.idle_by_span(red, HAND_PROGS)
    want = {"ctr.request": 230, "other": 200, tr.WAIT: 100,
            "ctr.assemble": 90, "ctr.to_host": 60, "ctr.device_wait": 50,
            tr.BURST: 40, "ctr.score": 40}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert list(got) == sorted(got, key=lambda k: -got[k])
    # every idle nanosecond of the window is labelled once
    assert sum(got.values()) == pytest.approx(
        red.window_s - red.busy_s)


def test_new_readers_on_the_hand_trace():
    from chipbench import spec

    span = lambda name, ms: types.SimpleNamespace(name=name,
                                                  duration_s=ms / 1e3)
    ctx = hand_context()
    ctx.spans = [span("ctr.to_host", 2.0), span("ctr.score", 9.0),
                 span("ctr.to_host", 3.0)]
    # host idle: 380 - 60 (embed) - 100 (the wait) = 220 and 280 - 80 = 200
    assert spec.metric_reader("host_idle_ms")(ctx) == pytest.approx(210e-6)
    assert spec.metric_reader("to_host_ms")(ctx) == pytest.approx(2.5)
    assert spec.metric_reader("scorer_device_ms")(ctx) == pytest.approx(
        65e-6)
    assert spec.metric_reader("embed_device_ms")(ctx) == pytest.approx(30e-6)


@pytest.mark.parametrize("early", [0, 10])
def test_device_events_are_moved_onto_the_host_clock(early):
    """The scorer's dispatch opens at 40 and the device runs it 40..80;
    a profiler that puts the device ``early`` ns ahead is corrected."""
    from chipbench import spec

    trace = tr.Trace(
        ops=[[("%fusion = f32[8]{0} fusion(x)", 40 - early, 80 - early)]],
        host=[(tr.WINDOW, 0, 100), (tr.BURST, 0, 100)])
    progs = pt.Programs(
        program=[("ctr.request", 0, 100), ("ctr.score", 40, 90),
                 ("ctr.device_wait", 50, 90)],
        modules=[[("jit_ctr_score_tables(1)", 40 - early, 80 - early)]])
    red = tr.reduce(trace)
    assert pt.device_lead(progs) == early
    # an execution with no dispatch to pair it with gives no lead
    unpaired = pt.Programs(progs.program, [progs.modules[0] * 2])
    assert pt.device_lead(unpaired) == 0
    assert pt.idle_by_span(red, progs) == pytest.approx(
        {"ctr.request": 50e-9, "ctr.device_wait": 10e-9})
    ctx = types.SimpleNamespace(reduced=red, tracereduce=tr, programs=progs)
    assert spec.metric_reader("host_idle_ms")(ctx) == pytest.approx(50e-6)


def test_new_readers_read_nothing_without_program_spans():
    """A trace of a program that opens no annotations (the committed
    trace, recorded before they existed) gives the new readers nothing."""
    from chipbench import spec

    with open(os.path.join(HERE, "fixtures", "trace_fp32_read.json")) as f:
        d = json.load(f)
    assert pt.Programs.from_json(d) == pt.Programs()
    trace = tr.Trace.from_json(d)
    red = tr.reduce(trace)
    ctx = types.SimpleNamespace(trace=trace, reduced=red, tracereduce=tr,
                                programs=pt.Programs.from_json(d), spans=[],
                                bursts=[(s, e, 16, 128)
                                        for s, e in red.bursts])
    for name in ("host_idle_ms", "to_host_ms", "scorer_device_ms",
                 "embed_device_ms"):
        assert spec.metric_reader(name)(ctx) is None
    assert set(pt.idle_by_span(red, ctx.programs)) <= {
        tr.BURST, tr.WAIT, "other"}


def as_spans(program):
    """The tracer's spans that a recorded trace's program events stand
    for: each parented to the innermost event that holds it."""
    out, stack = [], []
    for i, (name, s, e) in enumerate(sorted(program,
                                            key=lambda x: (x[1], -x[2]))):
        while stack and stack[-1].t1 <= s:
            stack.pop()
        sp = types.SimpleNamespace(
            name=name, span_id=i, t1=e, duration_s=(e - s) * 1e-9,
            parent_id=stack[-1].span_id if stack else None)
        out.append(sp)
        stack.append(sp)
    return out


def recorded(cell):
    from chipbench import peaks, shapes, spec

    with open(os.path.join(HERE, "fixtures", FIXTURES[cell])) as f:
        d = json.load(f)
    with open(os.path.join(HERE, "configs", CONFIGS[cell] + ".json")) as f:
        cfg = json.load(f)
    trace, progs = tr.Trace.from_json(d), pt.Programs.from_json(d)
    red = tr.reduce(trace)
    return types.SimpleNamespace(
        trace=trace, reduced=red, tracereduce=tr, programs=progs,
        cell=types.SimpleNamespace(chips=spec.load_cell(cell).chips),
        shapes=shapes, cfg=cfg,
        peaks=peaks.peaks_for("TPU v5 lite"),
        spans=as_spans(progs.program),
        bursts=[(s * 1e-9, e * 1e-9, 16, 128) for s, e in red.bursts])


@pytest.mark.parametrize("cell", sorted(FIXTURES))
def test_every_reader_on_a_recorded_trace(cell):
    """A few bursts of each cell recorded on a TPU v5e with the program's
    spans on: each reader reads what its cell runs, in range."""
    from chipbench import spec

    ctx = recorded(cell)
    burst_ms = max(e - s for s, e, _, _ in ctx.bursts) * 1e3
    for name in READERS:
        value = spec.metric_reader(name)(ctx)
        if cell not in ONLY.get(name, {cell}):
            assert value is None, name
            continue
        assert value is not None, name
        if name.endswith("_ms"):
            assert 0.0 < value < burst_ms, name
        else:
            assert 0.0 < value < 100.0, name


@pytest.mark.parametrize("cell", sorted(FIXTURES))
def test_idle_time_of_a_recorded_trace_falls_in_named_spans(cell):
    ctx = recorded(cell)
    by = pt.idle_by_span(ctx.reduced, ctx.programs)
    inside = sum(v for k, v in by.items() if k not in (tr.WAIT, "other"))
    busy_s = tr.length(ctx.reduced.busy[tr.READ]) * 1e-9
    assert sum(by.values()) == pytest.approx(ctx.reduced.window_s - busy_s)
    assert by.get(pt.REQUEST, 0.0) + by.get(tr.BURST, 0.0) < 0.1 * inside


@pytest.mark.parametrize("fused", [False, True],
                         ids=["two_dispatch", "fused"])
def test_program_names_match_the_module_readers(fused):
    """The module readers find the CTR server's programs by the names of
    its jitted functions: renaming one loses its metric."""
    import re

    from chipbench import spec
    from repro.serve.ctr_server import CTRServer

    server, reqs = toy_server(None, fused)
    calls = []
    for attr in ("_embed_targets", "_score_many_table",
                 "_score_many_interest"):
        jitted = getattr(server, attr)
        setattr(server, attr, lambda *a, _j=jitted: calls.append(
            (_j, a)) or _j(*a))
    server.handle_requests(reqs)
    names = [re.match(r"HloModule (\S+),", fn.lower(*a).compile().as_text())
             .group(1) for fn, a in calls]
    scorer = spec.metric_reader("scorer_device_ms").__globals__["MODULE"]
    embed = spec.metric_reader("embed_device_ms").__globals__["MODULE"]
    assert [bool(re.search(scorer, n)) for n in names] == (
        [False, True] if fused else [True])
    assert [bool(re.search(embed, n)) for n in names] == (
        [True, False] if fused else [False])
    raw = CTRServer(server.model, server.params, mode="inline")
    assert re.search(scorer, "jit_" + raw._score_many_raw.__name__)
