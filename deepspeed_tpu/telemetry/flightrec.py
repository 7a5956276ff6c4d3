"""Black-box flight recorder + postmortem bundles (docs/OBSERVABILITY.md).

An always-on, bounded, stdlib-only incident recorder: a fixed-size ring of
high-signal events — every ``Fault/*`` and ``Recovery/*`` event, replica
lifecycle transitions, handoff retries, SLO violations, watchdog beats,
checkpoint publish edges, memory samples — that keeps recording even when
full telemetry is disabled. The ring is the airplane black box: when a
process dies abnormally (watchdog stall exit 85, preemption 83, slice loss
84, OOM, corrupt-checkpoint quarantine, fleet replica loss, an armed fault
action, a wedged TPU backend), the abnormal path calls :func:`flush_bundle`
and the last ``capacity`` events plus a full state snapshot land on disk as
one crash-consistent **postmortem bundle** directory that
``scripts/postmortem.py`` can classify after the fact.

Design constraints (pinned by tests/test_flightrec.py):

* ``record()`` is O(1): preallocated slots, in-place eviction, exactly one
  wall-clock read per event (none when the caller passes ``ts``), no
  allocation growth once the ring is full.
* Lifetime counters (``total_count``, ``counts_by_kind``) survive eviction
  — the bundle always says how much history the ring dropped.
* Bundles are written only when a destination is configured (the
  ``DS_TPU_POSTMORTEM_DIR`` env var, ``resilience.postmortem_dir`` config,
  or an explicit ``dir=``) so ordinary test/bench runs never litter the
  working tree. At most one bundle per process unless ``force=True`` —
  competing abnormal paths (an injected stall then the watchdog firing on
  it) yield one artifact, not a pile.
* Bundle publish reuses the checkpoint publish pattern: write everything
  into a ``<final>.tmp.<pid>`` sibling, fsync files and directory, then one
  atomic ``os.rename`` — a reader never observes a half-written bundle.

Everything here is stdlib-only and import-safe from any layer (telemetry
core, resilience, fleet, bench, scripts); jax and the rest of the package
are imported lazily inside :func:`flush_bundle` and guarded.
"""

import json
import os
import platform
import re
import socket
import sys
import threading
import time
import traceback
import weakref

FORMAT_VERSION = 1

#: default ring capacity (events); overridable via :func:`configure`.
DEFAULT_CAPACITY = 512

#: env var naming the bundle destination directory (created on demand).
ENV_DIR = "DS_TPU_POSTMORTEM_DIR"

#: bundle directory name prefix — ``postmortem-<unix_ms>-<pid>-<reason>``.
BUNDLE_PREFIX = "postmortem-"

MANIFEST_NAME = "manifest.json"
EVENTS_NAME = "events.jsonl"
SUMMARY_NAME = "summary.json"
STATE_NAME = "state.json"
STACKS_NAME = "stacks.txt"

#: env keys captured into the bundle (prefix match) — enough to reconstruct
#: the accelerator/run context without dumping the whole (secret-bearing)
#: environment.
ENV_PREFIXES = ("JAX_", "XLA_", "DS_TPU_", "DS_ELASTIC_", "DS_BENCH_",
                "TPU_", "LIBTPU", "MEGASCALE_")
ENV_EXACT = ("RANK", "HOSTNAME", "CLOUDSDK_CONFIG")

# injectable clock (tests monkeypatch this module alias, never time.time)
_now_wall = time.time

_SLOT_FIELDS = ("seq", "ts", "kind", "name", "detail")


class FlightRecorder:
    """Fixed-size event ring. O(1) append, in-place eviction, lifetime
    counters that survive eviction (the ``SeriesRing`` contract)."""

    __slots__ = ("capacity", "_slots", "_lock", "total_count",
                 "counts_by_kind")

    def __init__(self, capacity=DEFAULT_CAPACITY):
        capacity = int(capacity)
        if capacity < 1:
            raise ValueError(f"flightrec capacity must be >= 1: {capacity}")
        self.capacity = capacity
        self._slots = [None] * capacity
        self._lock = threading.Lock()
        self.total_count = 0
        self.counts_by_kind = {}

    def record(self, kind, name, detail=None, ts=None):
        """Append one event; returns its lifetime sequence number. One
        clock read when ``ts`` is None, zero otherwise."""
        if ts is None:
            ts = _now_wall()
        with self._lock:
            seq = self.total_count
            self.total_count = seq + 1
            self.counts_by_kind[kind] = self.counts_by_kind.get(kind, 0) + 1
            i = seq % self.capacity
            slot = self._slots[i]
            if slot is None:
                self._slots[i] = [seq, ts, kind, name, detail]
            else:  # evict in place: five stores, no allocation
                slot[0] = seq
                slot[1] = ts
                slot[2] = kind
                slot[3] = name
                slot[4] = detail
        return seq

    @property
    def dropped(self):
        """Events evicted from the ring over this recorder's lifetime."""
        return max(self.total_count - self.capacity, 0)

    def events(self):
        """Live ring contents as dicts, oldest first."""
        with self._lock:
            live = [list(s) for s in self._slots if s is not None]
        live.sort(key=lambda s: s[0])
        return [dict(zip(_SLOT_FIELDS, s)) for s in live]

    def snapshot(self):
        with self._lock:
            counts = dict(self.counts_by_kind)
            total = self.total_count
        return {"format_version": FORMAT_VERSION,
                "capacity": self.capacity,
                "total_count": total,
                "dropped": max(total - self.capacity, 0),
                "counts_by_kind": counts,
                "events": self.events()}

    def reset(self):
        with self._lock:
            self._slots = [None] * self.capacity
            self.total_count = 0
            self.counts_by_kind = {}


# ---------------------------------------------------------------------------
# process-global recorder + bundle plumbing

_RECORDER = FlightRecorder()
_STATE_LOCK = threading.Lock()
_dir = None            # configured bundle destination ("" / None = unset)
_env_checked = False   # ENV_DIR consulted lazily, once (faults.py pattern)
_bundle_path = None    # first bundle written by this process
_collectors = {}       # name -> zero-arg callable, snapshotted into bundles
_prev_excepthook = None


def get_recorder():
    return _RECORDER


def record(kind, name, detail=None, ts=None):
    """Module-level append to the process-global ring."""
    return _RECORDER.record(kind, name, detail=detail, ts=ts)


def configure(dir=None, capacity=None):
    """Set the bundle destination and/or resize the ring. ``dir=None``
    leaves the destination alone; ``dir=""`` explicitly disables bundle
    writes (env is still consulted unless :func:`reset` marked it checked).
    Resizing replaces the ring (events are dropped — configure early)."""
    global _dir, _env_checked, _RECORDER
    with _STATE_LOCK:
        if dir is not None:
            _dir = dir or None
            _env_checked = True  # explicit config wins over the env var
        if capacity is not None and int(capacity) != _RECORDER.capacity:
            _RECORDER = FlightRecorder(int(capacity))
    if _resolve_dir():
        _install_excepthook()


def reset():
    """Test/drill hygiene: clear the ring, destination, per-process bundle
    guard and collectors. Like ``faults.reset()``, the env var is marked
    checked so a reset process stays unconfigured until told otherwise."""
    global _dir, _env_checked, _bundle_path
    with _STATE_LOCK:
        _RECORDER.reset()
        _dir = None
        _env_checked = True
        _bundle_path = None
        _collectors.clear()


def register_collector(name, fn):
    """Register a zero-arg callable whose return value is snapshotted into
    ``state.json["collectors"][name]`` at bundle-flush time (KV page
    census, fleet/router reports, config digests). Re-registering a name
    overwrites — the newest owner wins. A bound method is held weakly: the
    registry must not keep an engine (and its device buffers — a KV pool, a
    training state) alive after its owner dropped it."""
    if hasattr(fn, "__self__"):
        fn = weakref.WeakMethod(fn)
    with _STATE_LOCK:
        _collectors[name] = fn


def unregister_collector(name):
    with _STATE_LOCK:
        _collectors.pop(name, None)


def last_bundle():
    """Path of the bundle this process already flushed (None if none)."""
    return _bundle_path


def _resolve_dir():
    global _env_checked, _dir
    with _STATE_LOCK:
        if not _env_checked:
            _env_checked = True
            env = os.environ.get(ENV_DIR)
            if env:
                _dir = env
        return _dir


def _identity():
    """(host, pid, run_id) — shared with the telemetry JSONL stamp when the
    pipeline is importable, self-computed otherwise."""
    pid = os.getpid()
    try:
        from deepspeed_tpu import telemetry
        t = telemetry.get_telemetry()
        return t.host, pid, t.run_id
    except Exception:
        try:
            host = socket.gethostname()
        except Exception:
            host = "unknown"
        run_id = os.environ.get("DS_TPU_HARNESS_RUN_ID",
                                f"{pid}-{int(_now_wall())}")
        return host, pid, run_id


def _format_stacks():
    """All-thread stack dump (stdlib re-implementation of
    ``watchdog.format_all_stacks`` so bundles never import resilience)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in sorted(sys._current_frames().items()):
        out.append(f"--- thread {names.get(tid, '?')} ({tid}) ---")
        out.append("".join(traceback.format_stack(frame)))
    return "\n".join(out)


def _captured_env():
    out = {}
    for k in sorted(os.environ):
        if k.startswith(ENV_PREFIXES) or k in ENV_EXACT:
            out[k] = os.environ[k][:500]
    return out


def _fsync_file(path):
    with open(path, "rb") as f:
        os.fsync(f.fileno())


def _fsync_dir(path):
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())


def _collect(guarded_fn, fallback=None):
    try:
        return guarded_fn()
    except Exception as e:  # forensics must never raise into the fault path
        return {"error": f"{type(e).__name__}: {e}"[:300]} \
            if fallback is None else fallback


def flush_bundle(reason, detail=None, exit_code=None, dir=None, force=False,
                 extra=None):
    """Publish one crash-consistent postmortem bundle directory and return
    its path (None when no destination is configured).

    At most one bundle per process unless ``force=True``: a second call
    records a ``postmortem/skipped`` ring event and returns the existing
    path, so stacked abnormal paths (injected stall → watchdog abort)
    leave exactly one artifact. Never raises — every collection step is
    individually guarded and an I/O failure returns None.
    """
    global _bundle_path
    try:
        return _flush_bundle(reason, detail, exit_code, dir, force, extra)
    except Exception:
        try:
            record("postmortem", "postmortem/flush_failed",
                   {"reason": reason,
                    "error": traceback.format_exc(limit=2)[-300:]})
        except Exception:
            pass
        return None


def _flush_bundle(reason, detail, exit_code, dir, force, extra):
    global _bundle_path
    out_root = dir or _resolve_dir()
    if not out_root:
        return None
    with _STATE_LOCK:
        if _bundle_path is not None and not force:
            existing = _bundle_path
            collectors = {}
        else:
            existing = None
            collectors = {}
            for name, fn in _collectors.items():
                if isinstance(fn, weakref.WeakMethod):
                    fn = fn()       # None once the owner was collected
                if fn is not None:
                    collectors[name] = fn
    if existing is not None:
        record("postmortem", "postmortem/skipped",
               {"reason": reason, "existing": existing})
        return existing

    host, pid, run_id = _identity()
    created = _now_wall()
    slug = re.sub(r"[^A-Za-z0-9_.-]+", "_", str(reason))[:60] or "unknown"
    final = os.path.join(
        os.path.abspath(out_root),
        f"{BUNDLE_PREFIX}{int(created * 1000)}-{pid}-{slug}")
    tmp = f"{final}.tmp.{pid}"

    # the flush event itself belongs in the ring the bundle carries
    record("postmortem", "postmortem/flush",
           {"reason": reason, "detail": detail, "exit_code": exit_code},
           ts=created)
    snap = _RECORDER.snapshot()

    manifest = {
        "format_version": FORMAT_VERSION,
        "kind": "postmortem_bundle",
        "reason": str(reason),
        "detail": str(detail)[:500] if detail is not None else None,
        "exit_code": exit_code,
        "host": host,
        "pid": pid,
        "run_id": run_id,
        "created_unix": round(created, 6),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "argv": [str(a)[:200] for a in sys.argv[:8]],
        "event_total": snap["total_count"],
        "event_dropped": snap["dropped"],
        "counts_by_kind": snap["counts_by_kind"],
    }
    if extra:
        manifest["extra"] = _collect(
            lambda: json.loads(json.dumps(extra, default=str)))

    def _summary():
        from deepspeed_tpu import telemetry
        return telemetry.summary()

    def _faults_state():
        from deepspeed_tpu.resilience import faults
        inj = faults.get_injector()
        return {"armed": inj.armed, "rules": inj.describe(),
                "trips": inj.trip_count()}

    state = {"format_version": FORMAT_VERSION,
             "faults": _collect(_faults_state),
             "env": _collect(_captured_env, fallback={}),
             "collectors": {}}
    for cname in sorted(collectors):
        state["collectors"][cname] = _collect(collectors[cname])

    os.makedirs(out_root, exist_ok=True)
    if os.path.isdir(tmp):
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    with open(os.path.join(tmp, EVENTS_NAME), "w") as f:
        for ev in snap["events"]:
            f.write(json.dumps(ev, default=str) + "\n")
        f.flush()
        os.fsync(f.fileno())
    _write_json(os.path.join(tmp, SUMMARY_NAME), _collect(_summary))
    _write_json(os.path.join(tmp, STATE_NAME), state)
    with open(os.path.join(tmp, STACKS_NAME), "w") as f:
        f.write(_collect(_format_stacks, fallback="") or "")
        f.flush()
        os.fsync(f.fileno())
    # manifest last: inside the tmp dir it marks payload completeness, and
    # the rename below makes the whole directory appear atomically
    _write_json(os.path.join(tmp, MANIFEST_NAME), manifest)
    _fsync_dir(tmp)
    os.rename(tmp, final)
    _fsync_dir(os.path.dirname(final))

    with _STATE_LOCK:
        if _bundle_path is None:
            _bundle_path = final
    record("postmortem", "postmortem/flushed",
           {"reason": reason, "path": final})
    return final


def _install_excepthook():
    """Once a destination is configured, any *unhandled* exception flushes
    a bundle before the interpreter prints the traceback — an InjectedFault
    that no recovery path caught still leaves evidence. ``SystemExit``
    never reaches the hook (the clean 83/84 paths flush explicitly)."""
    global _prev_excepthook
    with _STATE_LOCK:
        if _prev_excepthook is not None:
            return
        _prev_excepthook = sys.excepthook or sys.__excepthook__
        prev = _prev_excepthook

    def _hook(tp, val, tb):
        try:
            flush_bundle("unhandled_exception",
                         detail=f"{tp.__name__}: {val}"[:300])
        except Exception:
            pass
        prev(tp, val, tb)

    sys.excepthook = _hook
