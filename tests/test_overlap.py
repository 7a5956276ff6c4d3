"""Device-timeline overlap profiler tests (telemetry/overlap.py).

Synthetic-trace fixtures pin the exposure attribution EXACTLY — fully
overlapped collective -> 0 exposed, serialized -> 100% exposed, partial
overlap computed to the second, multi-stream and comm-vs-comm cases — plus
critical-path extraction, Chrome trace-event ingestion (device-lane
filtering, us->s), the comm_stats wire-byte join, the prefetch advisor,
the analytic serialized schedule, report validation, and the
``attach_overlap`` -> ``summary()["overlap"]`` -> schema path.
"""

import gzip
import json
import os

import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import overlap as ov

SCHEMA_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deepspeed_tpu", "telemetry",
    "summary.schema.json")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.close()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


def _dev(*ivs):
    return {"d0": list(ivs)}


def _compute(start, end, name="matmul", device="d0", stream=0):
    return ov.make_interval(name, start, end, kind="compute", device=device,
                            stream=stream)


def _comm(start, end, op="all_reduce", axis="dp", nbytes=1 << 20,
          device="d0", stream=0, **kw):
    return ov.make_interval(f"comm:{op}", start, end, kind="comm", op=op,
                            axis=axis, nbytes=nbytes, device=device,
                            stream=stream, **kw)


# ---------------------------------------------------------------------------
# segment algebra
# ---------------------------------------------------------------------------

def test_segment_algebra():
    assert ov.merge_segments([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert ov.segments_length([(0, 2), (3, 4)]) == 3
    union = [(0, 2), (3, 4)]
    assert ov.overlap_length(1, 3.5, union) == pytest.approx(1.5)
    assert ov.subtract_segments(1, 3.5, union) == [(2, 3)]
    assert ov.subtract_segments(5, 6, union) == [(5, 6)]
    assert ov.subtract_segments(0.5, 1.5, union) == []


def test_classify_op_spellings():
    # XLA thunk/fusion spellings AND our own comm: events
    assert ov.classify_op("all-reduce-start.1") == "all_reduce"
    assert ov.classify_op("fusion.all_gather.3") == "all_gather"
    assert ov.classify_op("reduce-scatter.2") == "reduce_scatter"
    assert ov.classify_op("all-to-all.7") == "all_to_all"
    assert ov.classify_op("collective-permute-done") == "collective_permute"
    assert ov.classify_op("comm:all_to_all_quant") == "all_to_all_quant"
    assert ov.classify_op("fusion.123") is None
    assert ov.classify_op("loop_convert_fusion") is None


# ---------------------------------------------------------------------------
# exposure attribution — the exact cases ISSUE 8 pins
# ---------------------------------------------------------------------------

def test_fully_overlapped_collective_zero_exposed():
    att = ov.attribute(_dev(_compute(0.0, 10.0), _comm(2.0, 5.0)))
    tot = att["totals"]
    assert tot["exposed_comm_s"] == pytest.approx(0.0)
    assert tot["overlapped_comm_s"] == pytest.approx(3.0)
    assert tot["comm_s"] == pytest.approx(3.0)
    assert tot["compute_s"] == pytest.approx(10.0)
    assert tot["gap_s"] == pytest.approx(0.0)
    assert tot["step_s"] == pytest.approx(10.0)
    rep = ov.overlap_report(_dev(_compute(0.0, 10.0), _comm(2.0, 5.0)))
    assert rep["overlap_fraction"] == pytest.approx(1.0)
    assert rep["exposed_fraction"] == pytest.approx(0.0)
    assert rep["advice"] == []  # nothing exposed, nothing to advise


def test_serialized_collective_fully_exposed():
    att = ov.attribute(_dev(_compute(0.0, 4.0), _comm(4.0, 7.0)))
    tot = att["totals"]
    assert tot["exposed_comm_s"] == pytest.approx(3.0)
    assert tot["overlapped_comm_s"] == pytest.approx(0.0)
    rep = ov.overlap_report(_dev(_compute(0.0, 4.0), _comm(4.0, 7.0)))
    assert rep["exposed_fraction"] == pytest.approx(1.0)
    assert rep["collectives"][0]["exposure_fraction"] == pytest.approx(1.0)


def test_partial_overlap_computed_exactly():
    # compute [0,3], comm [2,6]: hidden [2,3] = 1s, exposed [3,6] = 3s
    att = ov.attribute(_dev(_compute(0.0, 3.0), _comm(2.0, 6.0)))
    tot = att["totals"]
    assert tot["exposed_comm_s"] == pytest.approx(3.0)
    assert tot["overlapped_comm_s"] == pytest.approx(1.0)
    iv = att["comm_intervals"][0]
    assert iv["exposed_segments"] == [(3.0, 6.0)]
    # and exposure survives a compute island in the middle of the comm:
    # compute [0,3]+[4,5], comm [2,6] -> exposed [3,4]+[5,6] = 2s
    att2 = ov.attribute(_dev(_compute(0.0, 3.0), _compute(4.0, 5.0),
                             _comm(2.0, 6.0)))
    assert att2["totals"]["exposed_comm_s"] == pytest.approx(2.0)
    assert att2["comm_intervals"][0]["exposed_segments"] == \
        [(3.0, 4.0), (5.0, 6.0)]


def test_multi_stream_collective():
    # comm on its own stream, compute concurrent on another stream of the
    # SAME device: exposure is per-device, streams don't partition it
    per = _dev(_compute(0.0, 10.0, stream=0),
               _comm(8.0, 12.0, stream=1))
    att = ov.attribute(per)
    tot = att["totals"]
    assert tot["overlapped_comm_s"] == pytest.approx(2.0)
    assert tot["exposed_comm_s"] == pytest.approx(2.0)
    assert tot["step_s"] == pytest.approx(12.0)


def test_comm_does_not_hide_comm():
    # two overlapping collectives with no compute: both fully exposed
    att = ov.attribute(_dev(_comm(0.0, 4.0, op="all_gather"),
                            _comm(2.0, 6.0, op="reduce_scatter")))
    assert att["totals"]["comm_s"] == pytest.approx(8.0)
    assert att["totals"]["exposed_comm_s"] == pytest.approx(8.0)


def test_gap_attribution():
    att = ov.attribute(_dev(_compute(0.0, 1.0), _comm(2.0, 3.0)))
    assert att["totals"]["gap_s"] == pytest.approx(1.0)
    assert att["totals"]["step_s"] == pytest.approx(3.0)


def test_multi_device_totals_sum():
    per = {"d0": [_compute(0.0, 2.0), _comm(2.0, 3.0)],
           "d1": [_compute(0.0, 2.0, device="d1"),
                  _comm(0.5, 1.5, device="d1")]}
    tot = ov.attribute(per)["totals"]
    assert tot["comm_s"] == pytest.approx(2.0)
    assert tot["exposed_comm_s"] == pytest.approx(1.0)  # d0 only
    rep = ov.overlap_report(per)
    assert rep["devices"] == 2


# ---------------------------------------------------------------------------
# critical path
# ---------------------------------------------------------------------------

def test_critical_path_serialized_chain():
    per = _dev(_compute(0.0, 4.0), _comm(4.0, 7.0, op="all_gather"),
               _compute(7.0, 9.0, name="matmul2"))
    cp = ov.critical_path(per)
    assert [o["name"] for o in cp["ops"]] == \
        ["matmul", "comm:all_gather", "matmul2"]
    assert cp["length_s"] == pytest.approx(9.0)
    assert cp["compute_s"] == pytest.approx(6.0)
    assert cp["comm_s"] == pytest.approx(3.0)
    assert cp["exposed_comm_s"] == pytest.approx(3.0)
    assert cp["device"] == "d0"


def test_critical_path_skips_hidden_branch():
    # overlapped comm [1,3] ends before the long compute [0,10]: the path
    # is just the compute (the comm is not a last-finisher predecessor)
    per = _dev(_compute(0.0, 10.0), _comm(1.0, 3.0))
    cp = ov.critical_path(per)
    assert [o["name"] for o in cp["ops"]] == ["matmul"]
    assert cp["exposed_comm_s"] == pytest.approx(0.0)


def test_critical_path_picks_last_finishing_device():
    per = {"d0": [_compute(0.0, 2.0)],
           "d1": [_compute(0.0, 5.0, device="d1")]}
    assert ov.critical_path(per)["device"] == "d1"
    assert ov.critical_path({}) == {
        "device": None, "length_s": 0.0, "compute_s": 0.0, "comm_s": 0.0,
        "exposed_comm_s": 0.0, "ops": []}


# ---------------------------------------------------------------------------
# per-collective rollup + advisor
# ---------------------------------------------------------------------------

def test_rollup_joins_comm_stats_wire_bytes():
    # the trace knew the op but not the payload: bytes + wire bytes come
    # from telemetry comm_stats ((op, axis) -> [count, bytes, secs, algbw,
    # busbw, wire_bytes])
    per = _dev(_compute(0.0, 1.0),
               _comm(1.0, 2.0, op="all_to_all_quant", nbytes=0))
    stats = {("all_to_all_quant", "dp"): [2, 999, 0.01, 1.0, 1.0, 555]}
    rep = ov.overlap_report(per, comm_stats=stats)
    c = rep["collectives"][0]
    assert c["bytes"] == 999 and c["wire_bytes"] == 555
    # summary()["comm"]["ops"] nested shape joins identically
    nested = {"all_to_all_quant": {"dp": {"count": 2, "bytes": 999,
                                          "wire_bytes": 555}}}
    c2 = ov.overlap_report(per, comm_stats=nested)["collectives"][0]
    assert c2["bytes"] == 999 and c2["wire_bytes"] == 555


def test_advisor_names_adjacent_compute():
    # serialized: comm [4,7] follows compute [0,4] -> prefetchable, saving
    # bounded by min(exposed 3, adjacent 4) = 3
    rep = ov.overlap_report(_dev(_compute(0.0, 4.0), _comm(4.0, 7.0)))
    assert len(rep["advice"]) == 1
    a = rep["advice"][0]
    assert a["op"] == "all_reduce" and a["axis"] == "dp"
    assert a["exposed_s"] == pytest.approx(3.0)
    assert a["adjacent_compute_s"] == pytest.approx(4.0)
    assert a["potential_saving_s"] == pytest.approx(3.0)
    assert "prefetch" in a["hint"]
    # exposed comm with NO adjacent compute anywhere: no advice
    rep2 = ov.overlap_report(_dev(_comm(0.0, 3.0)))
    assert rep2["advice"] == []


# ---------------------------------------------------------------------------
# trace-event ingestion
# ---------------------------------------------------------------------------

def _chrome_events():
    return [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0 (pf)"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "python main thread"}},
        # device lane: 1ms fusion then a 1ms all-reduce half-hidden under it
        {"ph": "X", "name": "fusion.1", "pid": 1, "tid": 0,
         "ts": 0, "dur": 1000},
        {"ph": "X", "name": "all-reduce-start.2", "pid": 1, "tid": 1,
         "ts": 500, "dur": 1000, "args": {"axis": "dp", "bytes": 4096}},
        # host lane noise that must NOT count as device compute
        {"ph": "X", "name": "python_dispatch", "pid": 2, "tid": 0,
         "ts": 0, "dur": 50000},
        {"ph": "C", "name": "counter", "pid": 1, "ts": 0,
         "args": {"v": 1}},
        {"ph": "i", "name": "marker", "pid": 1, "ts": 10},
    ]


def test_intervals_from_trace_device_filter_and_units():
    per = ov.intervals_from_trace(_chrome_events())
    assert list(per) == ["/device:TPU:0 (pf)"]
    ivs = per["/device:TPU:0 (pf)"]
    assert len(ivs) == 2
    rep = ov.overlap_report(per)
    assert rep["compute_s"] == pytest.approx(1e-3)
    assert rep["comm_s"] == pytest.approx(1e-3)
    assert rep["exposed_comm_s"] == pytest.approx(0.5e-3)
    assert rep["collectives"][0]["op"] == "all_reduce"
    assert rep["collectives"][0]["axis"] == "dp"
    assert rep["collectives"][0]["bytes"] == 4096


def test_intervals_from_trace_no_metadata_fallback():
    # our own exported traces / fixtures carry no device process names:
    # every pid with duration events becomes a timeline
    events = [{"ph": "X", "name": "op", "pid": 7, "tid": 0,
               "ts": 0, "dur": 100}]
    per = ov.intervals_from_trace(events)
    assert list(per) == ["pid:7"]


def test_load_trace_events_file_gz_and_dir(tmp_path):
    events = _chrome_events()
    plain = tmp_path / "t.json"
    plain.write_text(json.dumps({"traceEvents": events}))
    assert len(ov.load_trace_events(str(plain))) == len(events)
    # bare-list form + gz (named so the dir-scan below doesn't collect it)
    gz = tmp_path / "t2.json.gz"
    with gzip.open(gz, "wt") as f:
        json.dump(events, f)
    assert len(ov.load_trace_events(str(gz))) == len(events)
    # profiler-dir layout: nested *.trace.json.gz files are all collected
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    assert len(ov.load_trace_events(str(tmp_path))) == len(events)
    with pytest.raises(FileNotFoundError):
        ov.load_trace_events(str(tmp_path / "plugins" / "profile" / "empty"))


def test_intervals_from_jsonl_records():
    # span records emit at END (ts) with duration in value; comm records
    # carry seconds in tags — both reconstruct [ts-dur, ts]
    records = [
        {"kind": "span", "name": "fwd", "ts": 1.0, "value": 1.0},
        {"name": "comm/all_reduce", "ts": 1.5, "value": 4096,
         "tags": {"axis": "dp", "seconds": 1.0}},
        {"kind": "gauge", "name": "loss", "ts": 1.6, "value": 2.5},
    ]
    per = ov.intervals_from_jsonl_records(records, host="h0")
    att = ov.attribute(per)
    # comm [0.5,1.5] vs compute [0,1]: hidden 0.5, exposed 0.5
    assert att["totals"]["exposed_comm_s"] == pytest.approx(0.5)
    assert att["totals"]["overlapped_comm_s"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# analytic mode + validation
# ---------------------------------------------------------------------------

def test_analytic_schedule_fully_exposed():
    per = ov.analytic_intervals(1e-3, [
        {"op": "all_gather", "axis": "dp", "bytes": 1 << 20,
         "seconds": 2e-4, "count": 2},
        {"op": "all_reduce", "axis": "dp", "bytes": 4096, "seconds": 1e-4}])
    rep = ov.overlap_report(per, mode="analytic")
    assert rep["comm_s"] == pytest.approx(5e-4)
    assert rep["exposed_comm_s"] == pytest.approx(5e-4)
    assert rep["exposed_fraction"] == pytest.approx(1.0)
    assert rep["gap_s"] == pytest.approx(0.0)
    # the whole serialized schedule IS the critical path
    assert len(rep["critical_path"]["ops"]) == 4
    assert ov.validate_report(rep) == []


def test_comm_roofline_ring_factors():
    from deepspeed_tpu.autotuning import kernel_tuner as kt
    link = kt.LINK_BYTES_PER_S["tpu_v5e"]
    lat = 1e-6
    n = 8
    ar = kt.comm_roofline_seconds("all_reduce", 1 << 30, n=n,
                                  device_kind="tpu_v5e")
    ag = kt.comm_roofline_seconds("all_gather", 1 << 30, n=n,
                                  device_kind="tpu_v5e")
    assert ar == pytest.approx((1 << 30) * 2 * (n - 1) / n / link + lat)
    assert ag == pytest.approx((1 << 30) * (n - 1) / n / link + lat)
    # all_reduce moves ~2x the bytes of all_gather on a ring
    assert ar > ag
    sec = kt.roofline_compute_seconds(197e12, 0, device_kind="tpu_v5e")
    assert sec == pytest.approx(1.0)


def test_validate_report_catches_malformed():
    rep = ov.overlap_report(_dev(_compute(0.0, 1.0), _comm(0.5, 2.0)))
    assert ov.validate_report(rep) == []
    bad = json.loads(json.dumps(rep))
    bad["exposed_comm_s"] = bad["comm_s"] + 1.0
    assert any("exposed_comm_s" in e for e in ov.validate_report(bad))
    bad2 = json.loads(json.dumps(rep))
    bad2["overlap_fraction"] = float("nan")
    assert ov.validate_report(bad2)
    bad3 = json.loads(json.dumps(rep))
    bad3["mode"] = "vibes"
    assert any("mode" in e for e in ov.validate_report(bad3))
    bad4 = json.loads(json.dumps(rep))
    del bad4["critical_path"]
    assert any("critical_path" in e for e in ov.validate_report(bad4))
    assert ov.validate_report("nope")


# ---------------------------------------------------------------------------
# attach_overlap -> summary() -> schema
# ---------------------------------------------------------------------------

def test_attach_overlap_rides_summary_and_schema():
    telemetry.configure(enabled=True)
    telemetry.record_comm("all_reduce", 1 << 20, 0.001, axis="dp")
    rep = ov.overlap_report(
        _dev(_compute(0.0, 4.0), _comm(4.0, 7.0)),
        comm_stats=telemetry.get_telemetry().comm_stats)
    assert telemetry.attach_overlap(rep) is rep
    s = telemetry.summary()
    assert s["overlap"]["exposed_comm_s"] == pytest.approx(3.0)
    assert s["ledger"]["in_jit_opaque_s"] == s["ledger"]["seconds"]["compute"]
    jsonschema = pytest.importorskip("jsonschema")
    jsonschema.validate(s, json.load(open(SCHEMA_PATH)))
    # surfaced in the human table and the monitor bridge
    assert "overlap[trace]" in telemetry.format_summary()
    names = [n for n, _v, _s in telemetry.monitor_events(1)]
    assert any("Overlap/exposed_comm_s" in n for n in names)
    # malformed attach must raise, not silently pollute the summary
    with pytest.raises(ValueError):
        telemetry.attach_overlap({"mode": "trace"})
    # reset drops the report
    telemetry.reset()
    telemetry.configure(enabled=True)
    assert "overlap" not in telemetry.summary()
