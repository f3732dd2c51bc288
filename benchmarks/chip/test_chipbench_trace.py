"""The reduction from a profiler trace to busy time, idle share, kernel
time and the breakdown, on a hand-made trace with known answers and on a
small trace recorded on a TPU v5e."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import tracereduce as tr  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "trace_fp32_read.json")

# window 0..100; bursts 10..40 and 60..90; device busy 20..30, 25..40
# (overlapping) and 70..80; the query kernel runs 70..72.
HAND = tr.Trace(
    ops=[[("%fusion.1 = f32[8]{0} fusion(x)", 20, 30),
          ("%copy.13 = f32[10000000,64]{1,0} copy(y)", 25, 40),
          ("%_query.1 = f32[8,128,128]{2,1,0} custom-call(a, b, c)", 70, 72),
          ("%fusion.2 = f32[8]{0} fusion(x)", 72, 80),
          ("%before = f32[1]{0} add(x)", -10, -5)]],
    host=[("bench.window", 0, 100), ("bench.handle_requests", 10, 40),
          ("bench.wait_arrival", 40, 60), ("bench.handle_requests", 60, 90)])


def test_union_and_intersection():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) == [
        (1, 4), (5, 8)]
    assert tr.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10),
                                                             (20, 25)]
    assert tr.length([(1, 4), (5, 8)]) == 6


def test_hand_trace():
    red = tr.reduce(HAND)
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy == [[(20, 40), (70, 80)]]
    assert red.busy_s == pytest.approx(30e-9)
    # bursts last 60; the device is busy 20 + 10 of them
    assert red.idle_share() == pytest.approx(0.5)
    assert tr.op_time(HAND, red, r"^%_query(\.\d+)? = .*custom-call\(") == (
        pytest.approx(2e-9), 1)
    ops = tr.device_ops(HAND, red)
    assert ops[0] == ["copy.13 f32[10000000,64]", pytest.approx(15e-9)]
    assert [o[0] for o in ops] == ["copy.13 f32[10000000,64]",
                                   "fusion.1 f32[8]", "fusion.2 f32[8]",
                                   "_query.1 f32[8,128,128]"]
    gaps = dict(red.idle_gaps())
    # idle: 0..20 (labelled by its midpoint 10: inside a burst), 40..70
    # (midpoint 55: waiting for an arrival), 80..100 (midpoint 90: neither)
    assert gaps["bench.handle_requests total"] == pytest.approx(20e-9)
    assert gaps["bench.wait_arrival total"] == pytest.approx(30e-9)
    assert gaps["other total"] == pytest.approx(20e-9)
    assert len(red.idle_gaps()) <= 10


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(HAND.ops, HAND.host[1:]))


def test_round_trip_through_json(tmp_path):
    p = tmp_path / "t.json"
    tr.save(HAND, str(p))
    again = tr.Trace.from_json(json.loads(p.read_text()))
    assert again.ops == HAND.ops and again.host == HAND.host


def test_recorded_chip_trace():
    """A few bursts of ``fp32_read`` recorded on a TPU v5e."""
    with open(FIXTURE) as f:
        trace = tr.Trace.from_json(json.load(f))
    red = tr.reduce(trace)
    bursts = [h for h in trace.host if h[0] == tr.BURST]
    assert 0 < red.busy_s < red.window_s
    assert 0.0 < red.idle_share() < 1.0
    sec, calls = tr.op_time(trace, red, r"^%_query(\.\d+)? = .*custom-call\(")
    assert calls == len(bursts) and sec > 0
    top = tr.device_ops(trace, red)
    assert top[0][0].startswith("copy") and "10000000,64" in top[0][0]
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def reader_context(trace, B):
    import types

    from chipbench import peaks, shapes

    with open(os.path.join(HERE, "configs", "sdim_paper_fp32.json")) as f:
        cfg = json.load(f)
    red = tr.reduce(trace)
    bursts = [(s, e, B, 128) for n, s, e in trace.host if n == tr.BURST]
    return types.SimpleNamespace(
        trace=trace, reduced=red, tracereduce=tr, shapes=shapes, cfg=cfg,
        peaks=peaks.peaks_for("TPU v5 lite"), bursts=bursts, spans=[])


def test_readers_on_the_recorded_trace():
    from chipbench import spec

    with open(FIXTURE) as f:
        trace = tr.Trace.from_json(json.load(f))
    ctx = reader_context(trace, B=16)
    share = spec.metric_reader("sdim_query_roofline")(ctx)
    assert 0.0 < share < 100.0
    assert 0.0 < spec.metric_reader("idle_share")(ctx) < 100.0
    assert 0.0 < spec.metric_reader("mfu")(ctx) < 1.0
    # what a cell does not run, or spans a run did not record, read nothing
    assert spec.metric_reader("sdim_fused_serve_roofline")(ctx) is None
    assert spec.metric_reader("assemble_ms")(ctx) is None
    assert spec.metric_reader("bse_read_ms")(ctx) is None


def test_span_readers():
    import types

    from chipbench import spec

    def span(name, sid, parent, ms):
        return types.SimpleNamespace(name=name, span_id=sid,
                                     parent_id=parent, duration_s=ms / 1e3)

    spans = [span("ctr.request", 1, None, 30.0),
             span("ctr.assemble", 2, 1, 3.0),
             span("bse.fetch_many", 3, 1, 2.0),
             span("ctr.request", 4, None, 30.0),
             span("ctr.assemble", 5, 4, 5.0),
             span("bse.serve_candidates", 6, 4, 4.0)]
    ctx = types.SimpleNamespace(spans=spans)
    assert spec.metric_reader("assemble_ms")(ctx) == pytest.approx(4.0)
    assert spec.metric_reader("bse_read_ms")(ctx) == pytest.approx(3.0)
    ctx.spans = []
    assert spec.metric_reader("assemble_ms")(ctx) is None
    assert spec.metric_reader("bse_read_ms")(ctx) is None


def test_end_to_end_latency_covers_every_served_request():
    from chipbench import harness, spec

    run = harness.Run(spec.load_cell("fp32_read"), 1, 1.0, False)
    # a request never served (nan) is counted in failed, not here
    run.latency = np.concatenate([np.arange(1.0, 101.0) / 1e3, [np.nan]])
    got = run.end_to_end(setup_s=12.5)
    assert set(got) == {m["name"] for m in run.cell.end_to_end}
    assert got["p50_ms"]["value"] == pytest.approx(50.5)
    assert got["setup_s"] == {"value": 12.5, "unit": "s"}
