"""Flight recorder + postmortem bundle tests (telemetry/flightrec.py,
scripts/postmortem.py).

Pins the black-box contract: a randomized ring property test against a
naive keep-last-N reference, the O(1)/one-clock-read/zero-allocation
recording guarantees, Fault/Recovery mirroring while telemetry is
DISABLED, crash-consistent bundle publish (schema, atomicity, the
one-bundle-per-process guard), the classifier signature catalogue, the
faults long-sleep flush and the watchdog flush, and the analyzer CLI
end to end.
"""

import importlib.util
import json
import os
import random
import tracemalloc

import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import flightrec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pm():
    """scripts/postmortem.py, loaded standalone (it is not a package
    module on purpose: it must run on hosts without jax)."""
    spec = importlib.util.spec_from_file_location(
        "pm_under_test", os.path.join(REPO, "scripts", "postmortem.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean():
    """Fresh, unconfigured recorder and DISABLED telemetry per test."""
    flightrec.reset()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    flightrec.reset()
    telemetry.close()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

class _NaiveRecorder:
    """The obvious O(n) reference: append everything, slice the tail."""

    def __init__(self, capacity):
        self.capacity = capacity
        self.all = []

    def record(self, kind, name, detail, ts):
        self.all.append(
            {"seq": len(self.all), "ts": ts, "kind": kind, "name": name,
             "detail": detail})

    def events(self):
        return self.all[-self.capacity:]


@pytest.mark.parametrize("capacity", [1, 3, 7, 64])
def test_ring_matches_naive_reference(capacity):
    """Randomized equivalence: for any append sequence the ring holds
    exactly the newest ``capacity`` events in seq order, and the lifetime
    counters (total, per-kind, dropped) survive eviction."""
    rng = random.Random(1000 + capacity)
    ring = flightrec.FlightRecorder(capacity)
    naive = _NaiveRecorder(capacity)
    kinds = ("fault", "recovery", "watchdog", "memory", "slo")
    for i in range(rng.randrange(2 * capacity, 6 * capacity + 10)):
        kind = rng.choice(kinds)
        detail = {"i": i} if rng.random() < 0.5 else None
        seq = ring.record(kind, f"{kind}/e{i}", detail=detail, ts=float(i))
        naive.record(kind, f"{kind}/e{i}", detail, float(i))
        assert seq == i
        assert ring.events() == naive.events()
        assert ring.total_count == len(naive.all)
        assert ring.dropped == max(len(naive.all) - capacity, 0)
    want_counts = {}
    for ev in naive.all:
        want_counts[ev["kind"]] = want_counts.get(ev["kind"], 0) + 1
    assert ring.counts_by_kind == want_counts
    snap = ring.snapshot()
    assert snap["capacity"] == capacity
    assert snap["total_count"] == ring.total_count
    assert snap["dropped"] == ring.dropped
    assert snap["events"] == naive.events()


def test_record_overhead_one_clock_read_zero_growth(monkeypatch):
    """The always-on guarantee: exactly one wall-clock read per event
    (zero when the caller stamps ``ts``), and once the ring is full,
    recording allocates nothing inside flightrec (in-place eviction)."""
    reads = [0]

    def _clock():
        reads[0] += 1
        return 123.0

    monkeypatch.setattr(flightrec, "_now_wall", _clock)
    ring = flightrec.FlightRecorder(32)
    for i in range(50):
        ring.record("fault", "Fault/x")
    assert reads[0] == 50
    ring.record("fault", "Fault/x", ts=1.0)
    assert reads[0] == 50, "caller-stamped events must not read the clock"

    # allocation growth must be bounded by CAPACITY (the live slot
    # contents), never by event count: 5x the events, same footprint
    def _grown(n):
        tracemalloc.start()
        snap0 = tracemalloc.take_snapshot()
        for _ in range(n):
            ring.record("fault", "Fault/x", ts=1.0)
        snap1 = tracemalloc.take_snapshot()
        tracemalloc.stop()
        filt = [tracemalloc.Filter(True, flightrec.__file__)]
        return sum(st.size_diff for st in
                   snap1.filter_traces(filt).compare_to(
                       snap0.filter_traces(filt), "lineno")
                   if st.size_diff > 0)

    for _ in range(64):  # warm: every slot materialized, eviction engaged
        ring.record("fault", "Fault/x", ts=1.0)
    g1 = _grown(2000)
    g2 = _grown(10000)
    assert g1 <= 64 * ring.capacity, f"footprint not capacity-bounded: {g1}B"
    assert g2 <= g1 + 256, \
        f"record() allocation scales with event count: {g1}B -> {g2}B"


def test_fault_events_mirrored_while_telemetry_disabled():
    """The whole point of the black box: Fault/* and Recovery/* land in
    the ring even when telemetry is off, and telemetry itself stays a
    strict no-op (summary still reports disabled)."""
    assert not telemetry.enabled()
    base = flightrec.get_recorder().total_count
    telemetry.record("Fault/slice.lost", 1, kind="counter", hit=1)
    telemetry.record("Recovery/readmit", 1, kind="counter")
    telemetry.record("loss", 1.0)  # ordinary metric: NOT ring-worthy
    evs = flightrec.get_recorder().events()
    tail = [e for e in evs if e["seq"] >= base]
    assert [(e["kind"], e["name"]) for e in tail] == [
        ("fault", "Fault/slice.lost"), ("recovery", "Recovery/readmit")]
    assert tail[0]["detail"] == {"hit": 1}
    assert telemetry.summary() == {"enabled": False}


# ---------------------------------------------------------------------------
# bundle publish
# ---------------------------------------------------------------------------

def test_flush_without_destination_is_noop(tmp_path):
    flightrec.record("fault", "Fault/x")
    assert flightrec.flush_bundle("stall") is None
    assert flightrec.last_bundle() is None


def test_bundle_schema_atomicity_and_classification(tmp_path):
    pm = _pm()
    flightrec.configure(dir=str(tmp_path))
    flightrec.record("fault", "Fault/slice.lost", {"hit": 1})
    flightrec.record("recovery", "Recovery/emergency_save")
    path = flightrec.flush_bundle("slice_loss", detail="drill", exit_code=84,
                                  extra={"fault_point": "slice.lost"})
    assert path and os.path.isdir(path)
    assert os.path.basename(path).startswith(flightrec.BUNDLE_PREFIX)
    # atomic publish: no tmp sibling survives, all five payloads present
    assert not [n for n in os.listdir(tmp_path) if ".tmp." in n]
    for name in (flightrec.MANIFEST_NAME, flightrec.EVENTS_NAME,
                 flightrec.SUMMARY_NAME, flightrec.STATE_NAME,
                 flightrec.STACKS_NAME):
        assert os.path.isfile(os.path.join(path, name)), name
    assert pm.validate_bundle(path) == []

    b = pm.load_bundle(path)
    man = b["manifest"]
    assert man["reason"] == "slice_loss" and man["exit_code"] == 84
    assert man["pid"] == os.getpid()
    assert man["extra"]["fault_point"] == "slice.lost"
    assert man["counts_by_kind"]["fault"] >= 1
    names = [e["name"] for e in b["events"]]
    assert "Fault/slice.lost" in names
    assert "postmortem/flush" in names, "the flush itself rides in the ring"
    assert b["summary"] == {"enabled": False}
    assert "env" in b["state"] and "faults" in b["state"]
    typ, evidence = pm.classify_bundle(b)
    assert typ == "slice_loss", (typ, evidence)


def test_one_bundle_per_process_guard_and_force(tmp_path):
    flightrec.configure(dir=str(tmp_path))
    first = flightrec.flush_bundle("stall")
    again = flightrec.flush_bundle("watchdog_stall")
    assert again == first, "second abnormal path must reuse the artifact"
    assert flightrec.last_bundle() == first
    names = [e["name"] for e in flightrec.get_recorder().events()]
    assert "postmortem/skipped" in names
    forced = flightrec.flush_bundle("oom", force=True)
    assert forced and forced != first
    assert len([n for n in os.listdir(tmp_path)
                if n.startswith(flightrec.BUNDLE_PREFIX)]) == 2


def test_failing_collector_is_captured_not_fatal(tmp_path):
    pm = _pm()
    flightrec.configure(dir=str(tmp_path))

    def _bad():
        raise RuntimeError("census exploded")

    flightrec.register_collector("fleet/bad", _bad)
    flightrec.register_collector("fleet/good", lambda: {"pages": 7})
    path = flightrec.flush_bundle("replica_loss")
    state = pm.load_bundle(path)["state"]
    assert state["collectors"]["fleet/good"] == {"pages": 7}
    assert state["collectors"]["fleet/bad"]["error"].startswith(
        "RuntimeError")
    assert pm.validate_bundle(path) == []


# ---------------------------------------------------------------------------
# classifier signature catalogue
# ---------------------------------------------------------------------------

def _bundle(reason="unhandled_exception", events=(), exit_code=None,
            run_id="r", extra=None):
    return {"path": f"/x/postmortem-0-0-{reason}",
            "manifest": {"format_version": 1, "kind": "postmortem_bundle",
                         "reason": reason, "host": "h", "pid": 1,
                         "run_id": run_id, "created_unix": 0.0,
                         "exit_code": exit_code, "extra": extra or {}},
            "events": [{"seq": i, "ts": float(i), "kind": "fault", "name": n}
                       for i, n in enumerate(events)],
            "summary": None, "state": None}


def test_classifier_direct_reasons():
    pm = _pm()
    for reason, want in [("oom", "oom"), ("stall", "stall"),
                         ("watchdog_stall", "stall"),
                         ("preemption", "preemption"),
                         ("slice_loss", "slice_loss"),
                         ("replica_loss", "replica_loss"),
                         ("corrupt_ckpt", "corrupt_ckpt"),
                         ("backend_unavailable", "backend_unavailable")]:
        typ, _ = pm.classify_bundle(_bundle(reason=reason))
        assert typ == want, (reason, typ)


def test_classifier_event_signatures_and_exit_codes():
    pm = _pm()
    cases = [
        (_bundle(events=["Fault/slice.lost"]), "slice_loss"),
        (_bundle(events=["Fault/replica.lost"]), "replica_loss"),
        (_bundle(events=["Fault/step.hang"]), "stall"),
        (_bundle(events=["Fault/ckpt.write"]), "corrupt_ckpt"),
        (_bundle(events=["Fault/oom"]), "oom"),
        (_bundle(extra={"fault_point": "comm.partition"}), "slice_loss"),
        (_bundle(exit_code=83), "preemption"),
        (_bundle(exit_code=84), "slice_loss"),
        (_bundle(exit_code=85), "stall"),
        (_bundle(), "unknown"),
    ]
    for b, want in cases:
        typ, evidence = pm.classify_bundle(b)
        assert typ == want, (b["manifest"], typ, evidence)


def test_incident_merge_by_run_id_and_tiebreak():
    """Bundles sharing a run_id are one incident; ties between concrete
    types resolve to the earliest catalogue entry (most root-cause-ish),
    and the merged timeline is wall-clock ordered across processes."""
    pm = _pm()
    a = _bundle(reason="stall", run_id="gang1")
    b = _bundle(reason="slice_loss", run_id="gang1", exit_code=84)
    inc = pm.classify_incident([b, a])
    assert inc["incident"] == "stall"  # stall precedes slice_loss
    assert inc["run_id"] == "gang1"
    assert sorted(inc["reasons"]) == ["slice_loss", "stall"]
    assert inc["exit_codes"] == [84]


# ---------------------------------------------------------------------------
# producers: faults long-sleep flush + watchdog flush
# ---------------------------------------------------------------------------

def test_faults_long_sleep_flushes_before_stalling(tmp_path, monkeypatch):
    """A sleep-action fault at or above STALL_FLUSH_MIN_SLEEP_S is a
    wedge: the bundle must hit disk BEFORE the sleep starts, so a SIGKILL
    landing inside the window still leaves the artifact. Short chaos
    sleeps must NOT flush."""
    pm = _pm()
    from deepspeed_tpu.resilience import faults
    slept = []
    monkeypatch.setattr(faults.time, "sleep", lambda s: slept.append(s))
    flightrec.configure(dir=str(tmp_path))
    try:
        faults.configure("step.hang:once!sleep60")
        faults.maybe_fail("step.hang")
        assert slept == [60.0]
        bundles = pm.find_bundles([str(tmp_path)])
        assert len(bundles) == 1
        typ, _ = pm.classify_bundle(pm.load_bundle(bundles[0]))
        assert typ == "stall"
        # below the wedge threshold: chaos latency, no artifact
        flightrec.reset()
        short_dir = tmp_path / "short"
        flightrec.configure(dir=str(short_dir))
        faults.configure("step.hang:once!sleep2")
        faults.maybe_fail("step.hang")
        assert slept[-1] == 2.0
        assert pm.find_bundles([str(short_dir)]) == []
        assert flightrec.last_bundle() is None
    finally:
        faults.reset()


def test_watchdog_fire_flushes_stall_bundle(tmp_path):
    """The watchdog's non-abort fire path leaves a classifiable bundle
    (abort=True takes the identical path before os._exit — exercised as
    a real subprocess by scripts/fault_drill.py --drill watchdog)."""
    pm = _pm()
    from deepspeed_tpu.resilience.watchdog import StepWatchdog
    flightrec.configure(dir=str(tmp_path))
    wd = StepWatchdog(abort=False, min_interval_s=1.0)
    wd.beat(step_seconds=0.5)
    report = wd._fire(12.0, 1.0)
    assert "no step progress" in report
    bundles = pm.find_bundles([str(tmp_path)])
    assert len(bundles) == 1
    b = pm.load_bundle(bundles[0])
    assert b["manifest"]["reason"] == "watchdog_stall"
    assert b["manifest"]["exit_code"] is None, "abort=False carries no code"
    names = [e["name"] for e in b["events"]]
    assert "watchdog/beat" in names, "heartbeats ride in the black box"
    assert "Fault/hang" in names
    typ, _ = pm.classify_bundle(b)
    assert typ == "stall"


# ---------------------------------------------------------------------------
# analyzer CLI
# ---------------------------------------------------------------------------

def test_postmortem_cli_end_to_end(tmp_path, capsys):
    pm = _pm()
    flightrec.configure(dir=str(tmp_path / "pm"))
    flightrec.record("fault", "Fault/preemption", {"signal": 15})
    assert flightrec.flush_bundle("preemption", exit_code=83)
    json_out = tmp_path / "report.json"
    rc = pm.main([str(tmp_path / "pm"), "--json-out", str(json_out)])
    assert rc == 0
    report = json.loads(json_out.read_text())
    assert report["schema"] == pm.REPORT_SCHEMA
    assert report["bundles"] == 1 and report["malformed"] == 0
    (inc,) = report["incidents"]
    assert inc["incident"] == "preemption"
    assert inc["exit_codes"] == [83]
    out = capsys.readouterr()
    assert out.out.strip().splitlines()[-1] == json.dumps(
        report, sort_keys=True, default=str), "stdout is ONE json line"
    assert "PREEMPTION" in out.err


def test_trace_merge_folds_bundles_into_flightrec_lanes(tmp_path,
                                                        monkeypatch):
    """--bundles: a dead process's ring lands on its OWN host track (same
    host:pid label as its telemetry JSONL) as a tid-2 ``flightrec`` lane;
    bundle-only hosts get fresh tracks; lane timestamps zero-base on the
    earliest ring event so cross-process order survives the merge."""
    spec = importlib.util.spec_from_file_location(
        "tm_under_test", os.path.join(REPO, "scripts", "trace_merge.py"))
    tm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tm)

    pm_dir = tmp_path / "pm"
    monkeypatch.setattr(flightrec, "_now_wall", lambda: 102.0)
    monkeypatch.setattr(flightrec, "_identity", lambda: ("host-a", 1, "r1"))
    flightrec.configure(dir=str(pm_dir))
    flightrec.record("fault", "Fault/step.hang", ts=100.0)
    assert flightrec.flush_bundle("stall", exit_code=85)
    flightrec.reset()
    monkeypatch.setattr(flightrec, "_identity", lambda: ("host-b", 2, "r1"))
    flightrec.configure(dir=str(pm_dir))
    flightrec.record("watchdog", "watchdog/beat", ts=101.0)
    assert flightrec.flush_bundle("slice_loss", exit_code=84)

    jl = tmp_path / "a.jsonl"
    jl.write_text(json.dumps(
        {"kind": "span", "name": "fwd", "ts": 2.0, "value": 1.0,
         "host": "host-a", "pid": 1, "run_id": "r1"}) + "\n")
    doc, report = tm.merge([str(jl)], bundles=[str(pm_dir)])
    assert report["flightrec"] == {
        "bundles": 2, "hosts": ["host-a:1", "host-b:2"],
        "reasons": ["slice_loss", "stall"]}
    assert doc["otherData"]["hosts"] == ["host-a:1", "host-b:2"]

    evs = doc["traceEvents"]
    lane = [e for e in evs if e.get("cat") == "flightrec"]
    assert lane and all(e["tid"] == 2 for e in lane)
    span_pid = next(e["pid"] for e in evs if e.get("cat") == "span")
    a_lane = [e for e in lane if e["pid"] == span_pid]
    assert any(e["name"] == "Fault/step.hang" for e in a_lane), \
        "the dead host's ring must ride its existing telemetry track"
    b_lane = [e for e in lane if e["pid"] != span_pid]
    assert any(e["name"] == "watchdog/beat" for e in b_lane)
    # zero-based on the earliest ring event (100.0): host-a fault at 0us,
    # host-b beat at 1s, flush markers stamped from manifest created_unix
    assert min(e["ts"] for e in a_lane) == 0.0
    assert any(e["ts"] == pytest.approx(1e6) for e in b_lane)
    markers = sorted(e["name"] for e in lane
                     if e["name"].startswith("postmortem:"))
    assert markers == ["postmortem:slice_loss", "postmortem:stall"]


def test_postmortem_cli_rejects_empty_and_malformed(tmp_path, capsys):
    pm = _pm()
    assert pm.main([str(tmp_path)]) == 2  # nothing to classify
    bad = tmp_path / "postmortem-1-1-x"
    bad.mkdir()
    (bad / "manifest.json").write_text("{not json")
    assert pm.main([str(tmp_path)]) == 2
    capsys.readouterr()
