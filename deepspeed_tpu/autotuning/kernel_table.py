"""Persistent block-size tuning tables for the Pallas TPU kernels.

Every Pallas dispatch in ``ops/registry`` resolves its block sizes through a
table keyed by ``(kernel, shape-bucket, dtype, device_kind)``; the historical
hardcoded ladders stay as the fallback, so a missing/stale table can never
break a dispatch — it only costs performance.  Tables are JSON files under
``deepspeed_tpu/autotuning/tables/`` (one per device kind, e.g.
``tpu_v5e.json``) regenerated by ``scripts/tune_kernels.py``
(see ``docs/AUTOTUNING.md``).

This module is deliberately importable WITHOUT jax (stdlib only at module
scope): ``scripts/perf_gate.py`` loads it standalone to schema-check the
checked-in table in the tier-1 ``--dry-run`` lane.  Anything that needs a
backend (device-kind detection) imports jax lazily.

Shape buckets
-------------
A kernel's lookup key uses a *bucket* of its shape, not the exact shape:
dims listed in ``_POW2_DIMS`` (the data-dependent ones — sequence lengths,
row counts) are rounded up to the next power of two; structural dims (head
dim, feature dims, group size) stay exact.  A tuned entry therefore covers a
range of nearby shapes; ``resolve`` still validates the tuned blocks against
the *exact* shape (divisibility etc. via the kernel's ``validate`` hook) and
falls back to the ladder when the bucketed pick does not fit.
"""

import dataclasses
import json
import os
import threading

FORMAT_VERSION = 1

TABLES_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tables")

#: kernel -> tunable knob names, in canonical order. Kernels with no free
#: block knobs (paged_mha's block size comes from the KV pool layout,
#: sparse_mha's from the caller's sparsity layout) still dispatch through the
#: table so coverage/telemetry treat every kernel uniformly.
KERNEL_KNOBS = {
    "flash_mha": ("block_q", "block_k"),
    "paged_mha": (),
    "sparse_mha": (),
    # one tile_m for the FFN's three GEMMs, and tile_k / tile_n for each of
    # its two GEMM shapes: up [rows, d] @ [d, f], down [rows, f] @ [f, d]
    "moe_ffn_gmm": ("tile_m", "up_tile_k", "up_tile_n",
                    "down_tile_k", "down_tile_n"),
    "quantized_matmul": ("block_m", "block_n", "block_k"),
    "block_quantize": ("block_g",),
    "block_dequantize_reduce": ("block_g",),
}

#: kernel -> canonical shape-signature dim names, in key order.
KERNEL_DIMS = {
    "flash_mha": ("tq", "tk", "dh"),
    "paged_mha": ("bs", "dh"),
    "sparse_mha": ("s", "block", "dh"),
    "moe_ffn_gmm": ("rows", "d", "f"),
    "quantized_matmul": ("m", "k", "n", "g"),
    "block_quantize": ("rows", "g", "bits"),
    "block_dequantize_reduce": ("peers", "rows", "g", "bits"),
}

#: dims rounded up to the next power of two when forming the bucket key.
_POW2_DIMS = {
    "flash_mha": ("tq", "tk"),
    "paged_mha": (),
    "sparse_mha": ("s",),
    "moe_ffn_gmm": ("rows",),
    "quantized_matmul": ("m",),
    "block_quantize": ("rows",),
    "block_dequantize_reduce": ("peers", "rows"),
}

#: canonical shapes the bench/AOT lanes exercise; ``perf_gate.py
#: --dry-run`` requires the checked-in table to cover every one of these.
BENCH_SHAPES = {
    "flash_mha": [
        ({"tq": 1024, "tk": 1024, "dh": 64}, "bfloat16"),  # gpt2 bench ladder
        ({"tq": 512, "tk": 512, "dh": 64}, "bfloat16"),    # AOT smoke leg
    ],
    "paged_mha": [({"bs": 16, "dh": 64}, "bfloat16")],
    "sparse_mha": [({"s": 1024, "block": 128, "dh": 64}, "bfloat16")],
    "moe_ffn_gmm": [({"rows": 128, "d": 128, "f": 256}, "bfloat16")],
    "quantized_matmul": [({"m": 16, "k": 512, "n": 256, "g": 128},
                          "bfloat16")],
    # ZeRO++ wire kernels (ops/pallas/quant_collective.py): int4 is the qgZ
    # intra-slice (ICI) leg, int8 the inter-slice (DCN) leg and qwZ
    "block_quantize": [
        ({"rows": 64, "g": 2048, "bits": 4}, "float32"),
        ({"rows": 64, "g": 2048, "bits": 8}, "float32"),
    ],
    "block_dequantize_reduce": [
        # dtype is the wire dtype: half-split-packed uint8 (int4) / int8
        ({"peers": 4, "rows": 64, "g": 2048, "bits": 4}, "uint8"),
        ({"peers": 4, "rows": 64, "g": 2048, "bits": 8}, "int8"),
    ],
}

#: raw ``jax.Device.device_kind`` strings -> table slug. Unknown kinds
#: normalise to a slug too (lowercased, underscored) so a user can drop in a
#: table for hardware we have not met yet.
_DEVICE_ALIASES = {
    "tpu v5 lite": "tpu_v5e",
    "tpu v5litepod": "tpu_v5e",
    "tpu v5e": "tpu_v5e",
    "v5e": "tpu_v5e",
    "tpu v5": "tpu_v5p",
    "tpu v5p": "tpu_v5p",
    "v5p": "tpu_v5p",
    "tpu v4": "tpu_v4",
    "v4": "tpu_v4",
    "tpu v6 lite": "tpu_v6e",
    "tpu v6e": "tpu_v6e",
    "v6e": "tpu_v6e",
}

_lock = threading.Lock()
_cache = {}  # path -> (mtime, parsed doc)


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """An explicit, hashable block-size choice for one kernel dispatch.

    ``source`` records where the pick came from: ``"table"`` (tuning-table
    hit), ``"env"`` (user override), ``"ladder"`` (hardcoded fallback) or
    ``"sweep"`` (mid-autotune candidate).
    """

    kernel: str
    values: tuple  # ((knob_name, int), ...) in KERNEL_KNOBS order
    source: str = "ladder"

    @classmethod
    def make(cls, kernel, source="ladder", **knobs):
        names = KERNEL_KNOBS.get(kernel)
        if names is None:
            raise ValueError(f"unknown kernel {kernel!r}; expected one of "
                             f"{sorted(KERNEL_KNOBS)}")
        extra = set(knobs) - set(names)
        if extra:
            raise ValueError(f"{kernel}: unknown knob(s) {sorted(extra)}; "
                             f"tunable knobs are {list(names)}")
        missing = [n for n in names if n not in knobs]
        if missing:
            raise ValueError(f"{kernel}: missing knob(s) {missing}")
        vals = tuple((n, int(knobs[n])) for n in names)
        for n, v in vals:
            if v <= 0:
                raise ValueError(f"{kernel}: knob {n} must be a positive "
                                 f"int, got {v}")
        return cls(kernel=kernel, values=vals, source=source)

    def get(self, name, default=None):
        for n, v in self.values:
            if n == name:
                return v
        return default

    def as_dict(self):
        return dict(self.values)

    def __repr__(self):
        body = ", ".join(f"{n}={v}" for n, v in self.values) or "-"
        return f"BlockConfig({self.kernel}: {body} [{self.source}])"


def _pow2_ceil(x):
    x = max(int(x), 1)
    return 1 << (x - 1).bit_length()


def normalize_device_kind(kind):
    """``jax.Device.device_kind`` (or any free-form string) -> table slug."""
    if not kind:
        return "unknown"
    k = str(kind).strip().lower()
    if k in _DEVICE_ALIASES:
        return _DEVICE_ALIASES[k]
    return k.replace(" ", "_").replace("-", "_")


def default_device_kind():
    """Slug for the live backend, honouring ``DS_TPU_KERNEL_TABLE_DEVICE``
    (which lets CPU tests and chip-free runs target e.g. ``tpu_v5e``)."""
    forced = os.environ.get("DS_TPU_KERNEL_TABLE_DEVICE", "")
    if forced:
        return normalize_device_kind(forced)
    try:  # lazy: this module must import without jax
        import jax
        return normalize_device_kind(jax.devices()[0].device_kind)
    except Exception:
        return "unknown"


def bucket_key(kernel, dims, dtype):
    """``(kernel, shape-bucket, dtype)`` -> the table entry key string.

    ``dims`` maps the kernel's canonical dim names (``KERNEL_DIMS``) to ints.
    """
    names = KERNEL_DIMS.get(kernel)
    if names is None:
        raise ValueError(f"unknown kernel {kernel!r}")
    missing = [n for n in names if n not in dims]
    if missing:
        raise ValueError(f"{kernel}: shape signature missing dim(s) "
                         f"{missing}; expected {list(names)}")
    pow2 = _POW2_DIMS[kernel]
    parts = []
    for n in names:
        v = int(dims[n])
        parts.append(f"{n}{_pow2_ceil(v) if n in pow2 else v}")
    return f"{kernel}|{','.join(parts)}|{str(dtype)}"


def table_path(device_kind):
    return os.path.join(TABLES_DIR, f"{normalize_device_kind(device_kind)}.json")


def validate_table(doc):
    """Schema-check a parsed table doc. Returns a list of error strings
    (empty = valid). Used by ``scripts/perf_gate.py`` and the tuner."""
    errs = []
    if not isinstance(doc, dict):
        return [f"table must be a JSON object, got {type(doc).__name__}"]
    if doc.get("format_version") != FORMAT_VERSION:
        errs.append(f"format_version must be {FORMAT_VERSION}, got "
                    f"{doc.get('format_version')!r}")
    if not isinstance(doc.get("device_kind"), str) or not doc.get("device_kind"):
        errs.append("device_kind must be a non-empty string")
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        return errs + ["entries must be an object"]
    for key, entry in entries.items():
        kernel = key.split("|", 1)[0]
        knobs = KERNEL_KNOBS.get(kernel)
        if knobs is None:
            errs.append(f"entry {key!r}: unknown kernel {kernel!r}")
            continue
        if key.count("|") != 2:
            errs.append(f"entry {key!r}: key must be kernel|bucket|dtype")
            continue
        if not isinstance(entry, dict):
            errs.append(f"entry {key!r}: value must be an object")
            continue
        blocks = entry.get("blocks")
        if not isinstance(blocks, dict):
            errs.append(f"entry {key!r}: missing blocks object")
            continue
        if set(blocks) != set(knobs):
            errs.append(f"entry {key!r}: blocks must have exactly "
                        f"{list(knobs)}, got {sorted(blocks)}")
            continue
        for n, v in blocks.items():
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                errs.append(f"entry {key!r}: knob {n} must be a positive "
                            f"int, got {v!r}")
    return errs


def load_table(device_kind=None, path=None):
    """Load (and cache by mtime) the table for a device kind.

    Returns the parsed doc, or None when no table exists or it fails
    validation (a broken table must never break a dispatch).
    ``DS_TPU_KERNEL_TABLE`` overrides the path outright.
    """
    if path is None:
        path = os.environ.get("DS_TPU_KERNEL_TABLE", "")
    if not path:
        path = table_path(device_kind if device_kind is not None
                          else default_device_kind())
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return None
    with _lock:
        cached = _cache.get(path)
        if cached is not None and cached[0] == mtime:
            return cached[1]
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if validate_table(doc):
        doc = None
    with _lock:
        _cache[path] = (mtime, doc)
    return doc


def clear_cache():
    with _lock:
        _cache.clear()


def lookup(kernel, dims, dtype, device_kind=None, path=None):
    """Table lookup. Returns the tuned knobs dict (possibly empty for
    knob-free kernels) or None on table/bucket miss."""
    doc = load_table(device_kind=device_kind, path=path)
    if doc is None:
        return None
    entry = doc["entries"].get(bucket_key(kernel, dims, dtype))
    if entry is None:
        return None
    return dict(entry["blocks"])


def resolve(kernel, dims, dtype, device_kind=None, path=None, validate=None):
    """Full table-first resolution for one dispatch.

    Returns ``(BlockConfig_or_None, reason)`` where reason is ``"tuned"``
    (table hit, valid for the exact shape) or ``"ladder_fallback"`` (no
    table, bucket miss, or tuned blocks invalid for this exact shape —
    caller must use its hardcoded ladder). ``validate`` receives the knobs
    dict and the exact ``dims`` and returns truthy when the tuned blocks fit.
    """
    blocks = lookup(kernel, dims, dtype, device_kind=device_kind, path=path)
    if blocks is None:
        return None, "ladder_fallback"
    try:
        if validate is not None and not validate(blocks, dims):
            return None, "ladder_fallback"
        cfg = BlockConfig.make(kernel, source="table", **blocks)
    except (TypeError, ValueError):
        return None, "ladder_fallback"
    return cfg, "tuned"


def save_table(path, device_kind, entries, generated_by, extra=None,
               sort=True):
    """Write a table doc atomically. ``entries`` maps bucket keys to
    ``{"blocks": {...}, ...provenance...}``; ``sort=False`` keeps their
    order (a sweep of some kernels leaves the rest of the file as it is)."""
    doc = {
        "format_version": FORMAT_VERSION,
        "device_kind": normalize_device_kind(device_kind),
        "generated_by": generated_by,
        "entries": dict(sorted(entries.items()) if sort else entries),
    }
    if extra:
        doc.update(extra)
    errs = validate_table(doc)
    if errs:
        raise ValueError("refusing to write invalid table: " +
                         "; ".join(errs[:5]))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")
    os.replace(tmp, path)
    clear_cache()
    return doc
