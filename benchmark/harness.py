"""What every cell's run shares: the cell's files, the span recorder, the
device check, percentiles, and the result line."""

import contextlib
import importlib
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Cell:
    """One entry of BENCHMARK.json's ``workloads`` with its configuration and
    traffic files read in. Everything a kind of cell needs beyond these comes
    from the driver named in the configuration file."""

    def __init__(self, name, bench_path=None):
        with open(bench_path or os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"benchmark: no workload {name!r}; known: {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in self.bench["configs"]}[self.entry["config"]]
        base = os.path.dirname(os.path.abspath(bench_path)) if bench_path else ROOT
        cfg_file = os.path.join(base, cfg_entry["file"])
        with open(cfg_file) as f:
            self.config = json.load(f)
        # configs/, traffic/ and metrics/ are siblings
        home = os.path.dirname(os.path.dirname(cfg_file))
        with open(os.path.join(home, "traffic", self.entry["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.metrics_dir = os.path.join(home, "metrics")

    def _listed(self, group):
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    @property
    def end_to_end(self):
        return self._listed("end_to_end")

    @property
    def per_layer(self):
        return self._listed("per_layer")

    def limit(self, name):
        limits = {**self.config.get("limits", {}), **self.traffic.get("limits", {})}
        return limits[name]


class Recorder:
    """Host spans in memory: (name, start, end, attrs) on ``perf_counter``.
    No span ever waits for the device. With ``annotate`` the same spans go
    into the profiler's trace as ``bench/<name>``."""

    def __init__(self, annotate=False):
        self.spans = []
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name, **attrs):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench/" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.append((name, t0, t1, attrs))

    def named(self, name):
        return [s for s in self.spans if s[0] == name]


def percentile(values, q):
    """Nearest-rank percentile of all the values (q in 0..100)."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, -(-len(xs) * q // 100))          # ceil
    return xs[int(rank) - 1]


def require_chips(n):
    """The TPU devices of this machine, at least ``n``; anything else ends
    the run with exit code 3 and no result line."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise SystemExit(
            f"benchmark: needs {n} TPU chip(s), found {len(devices)} x "
            f"{devices[0].platform}; a CPU run gives no device number")
    return devices[:n]


def memory_peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def load(kind, name):
    """The module ``benchmark/<kind>/<name>.py``: a reader, a reference or a
    driver, found by the name a data file gives."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def out_dir(cell, seed, trace):
    path = os.path.join(ROOT, ".bench_out", cell.name, f"seed{seed}_trace{trace}")
    os.makedirs(path, exist_ok=True)
    return path
