"""Cross-host KV fabric: wire format, delta-shipping, flow control, NVMe.

Layered like the fabric itself. Pure wire-format units first (frame
roundtrip, CRC localization, version-skew rejection, the int8-vs-fp32 byte
ratio the perf gate ratchets). Then the allocator/store NVMe fifth state
(demotion order, restore-through, the extended swap identity). Then fleet
integration over the serialized codec: greedy parity with the monolithic
reference through encode->CRC->decode, delta-shipping suppressing
already-held prefix blocks, injected corruption driving the typed
retry-then-fallback ladder, and flow-control backpressure surfacing in the
router's TTFT prediction. The two-process leg (decode in a separate OS
process) is pinned by the ``slow`` test at the bottom and by the checked-in
``onchip_results/serving_kvfabric_baseline.json``.
"""

import dataclasses

import numpy as np
import pytest

import jax

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2.fleet import (FlowControl,
                                              PrefillDecodeFleet)
from deepspeed_tpu.inference.v2.fleet import wire
from deepspeed_tpu.inference.v2.fleet.wire import (WireCRCError,
                                                   WireVersionError)
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import \
    BlockedAllocator
from deepspeed_tpu.inference.v2.replica_group import build_replica
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.close()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


# ---------------------------------------------------------------------------
# wire format units (no engine, no devices)
# ---------------------------------------------------------------------------

def _int8_handle(n=3, bucket=4, L=2, H=2, bs=8, hd=32, seed=0):
    """Synthetic quantized export handle: int8 data + fp32 per-token scales
    in the pool layout, padded to the pow2 transfer bucket."""
    rng = np.random.default_rng(seed)
    kd = rng.integers(-128, 128, (L, bucket, H, bs, hd)).astype(np.int8)
    vd = rng.integers(-128, 128, (L, bucket, H, bs, hd)).astype(np.int8)
    ks = rng.random((L, bucket, H, 1, bs)).astype(np.float32)
    vs = rng.random((L, bucket, H, 1, bs)).astype(np.float32)
    seqs = [{"uid": 7, "n": n, "seen_tokens": n * bs,
             "tokens": list(range(n * bs))}]
    return {"n": n, "k": (kd, ks), "v": (vd, vs), "seqs": seqs}


def test_wire_roundtrip_int8_lossless():
    """int8 pages + scales ship byte-for-byte: decode returns exactly the
    first n pool rows, re-padded to the pow2 bucket with zero rows."""
    h = _int8_handle(n=3, bucket=4)
    frame = wire.encode_handle(h)
    out = wire.decode_frame(frame)
    assert out["n"] == 3 and out["wire_nbytes"] == len(frame)
    for src, dst in ((h["k"], out["k"]), (h["v"], out["v"])):
        for a, b in zip(src, dst):
            np.testing.assert_array_equal(np.asarray(a)[:, :3], b[:, :3])
            assert not b[:, 3:].any(), "bucket padding must be zero rows"
    assert out["seqs"][0]["uid"] == 7
    assert out["seqs"][0]["tokens"] == list(range(24))


def test_wire_roundtrip_delta_digests():
    """Delta-shipped sequences carry skipped counts + chain digests through
    the frame (hex in meta, bytes on both ends)."""
    h = _int8_handle(n=2)
    h["seqs"] = [{"uid": 1, "n": 2, "seen_tokens": 40, "tokens": [1, 2],
                  "skipped": 3, "skipped_digests": [b"\x01" * 32,
                                                    b"\x02" * 32,
                                                    b"\xff" * 32]}]
    out = wire.decode_frame(wire.encode_handle(h))
    m = out["seqs"][0]
    assert m["skipped"] == 3
    assert m["skipped_digests"] == [b"\x01" * 32, b"\x02" * 32, b"\xff" * 32]


def test_wire_int8_page_under_fp32_ratio():
    """The ratchet's arithmetic: an int8 wire page (hd data + 4 scale bytes
    per token row) must cost <= 0.3x the fp32 bytes it replaces at the
    bench geometry (hd=32 -> 36/128 = 0.28125)."""
    h = _int8_handle(n=4, bucket=4, hd=32)
    pw = wire.page_wire_nbytes(h["k"], h["v"])
    pf = wire.page_fp32_nbytes(h["k"], h["v"])
    assert pw / pf == pytest.approx(0.28125)
    assert pw / pf <= 0.3


def test_wire_fp_pool_quantizes_at_wire():
    """fp32 pools quantize at the wire (lossy leg): the frame ships int8 +
    scales, decode returns dequantized fp32 close to the source."""
    rng = np.random.default_rng(3)
    n, L, H, bs, hd = 2, 2, 2, 4, 32
    k = rng.standard_normal((L, 2, H, bs, hd)).astype(np.float32)
    v = rng.standard_normal((L, 2, H, bs, hd)).astype(np.float32)
    h = {"n": n, "k": k, "v": v,
         "seqs": [{"uid": 0, "n": n, "seen_tokens": 8, "tokens": []}]}
    frame = wire.encode_handle(h, wire_quantize=True)
    raw = wire.encode_handle(h, wire_quantize=False)
    assert len(frame) < 0.5 * len(raw), "wire quantization must shrink fp32"
    out = wire.decode_frame(frame)
    np.testing.assert_allclose(out["k"][:, :n], k[:, :n], atol=2e-2)
    np.testing.assert_allclose(out["v"][:, :n], v[:, :n], atol=2e-2)


def test_wire_crc_flip_detected_and_localized():
    """One flipped payload byte -> WireCRCError carrying the page index;
    the flip in the LAST page must not implicate earlier pages."""
    h = _int8_handle(n=3)
    frame = wire.encode_handle(h)
    with pytest.raises(WireCRCError) as ei:
        wire.decode_frame(wire.corrupt(frame))
    assert ei.value.page == 2


def test_wire_version_skew_rejected():
    """Bad magic, unknown version, and truncation are deterministic
    rejects (WireVersionError / truncated-frame CRC) — never silently
    mis-parsed."""
    frame = wire.encode_handle(_int8_handle(n=1))
    with pytest.raises(WireVersionError, match="bad magic"):
        wire.decode_frame(b"XKVX" + frame[4:])
    skew = bytearray(frame)
    skew[4] ^= 0x7F  # version u16 little-endian low byte
    with pytest.raises(WireVersionError, match="version"):
        wire.decode_frame(bytes(skew))
    with pytest.raises(WireVersionError, match="too short"):
        wire.decode_frame(frame[:6])
    with pytest.raises(WireCRCError, match="truncated"):
        wire.decode_frame(frame[:-5])


# ---------------------------------------------------------------------------
# NVMe fifth state: allocator + store units
# ---------------------------------------------------------------------------

class _Store:
    def __init__(self):
        self._next = 0
        self.payloads = {}

    def write(self, payload):
        self._next += 1
        self.payloads[self._next] = payload
        return self._next

    def read(self, key):
        return self.payloads[key]

    def drop(self, key):
        del self.payloads[key]


class _ParkAll:
    """Prefix-cache stand-in that parks every refcount-0 block."""

    def park_if_cached(self, block):
        return True


def _spillable(a, n):
    """Allocate n blocks and park them (cached, refcount 0) so spill()
    accepts them."""
    blocks = a.allocate(n)
    a.free(blocks)
    return blocks


def test_allocator_nvme_demotes_oldest_host_record():
    """A full host tier demotes its OLDEST record to NVMe on the next
    spill; the demoted handle stays restorable (read back through the
    store) and the extended identity holds throughout."""
    a = BlockedAllocator(4, host_capacity=2)
    a.bind_cache(_ParkAll())
    st = _Store()
    a.bind_nvme(st, capacity=2)
    b1, b2, b3 = _spillable(a, 3)
    r1 = a.spill(b1, "one")
    r2 = a.spill(b2, "two")
    assert a.counts()["nvme"] == 0
    r3 = a.spill(b3, "three")  # host full -> r1 demotes to nvme
    hs = a.host_swap_stats()
    assert hs["nvme_demotions"] == 1 and hs["nvme_resident"] == 1
    assert hs["resident"] == 2
    assert hs["spilled"] == hs["restored"] + hs["dropped"] \
        + hs["resident"] + hs["nvme_resident"]
    assert a.restore(r1) == "one"  # through the store
    assert not st.payloads, "restore must drop the NVMe key"
    assert a.restore(r2) == "two" and a.restore(r3) == "three"
    hs = a.host_swap_stats()
    assert hs["restored"] == 3 and hs["resident"] == hs["nvme_resident"] == 0


def test_allocator_nvme_full_drops_spill():
    """Both tiers full -> can_spill goes False (pressure order falls
    through to evict/preempt); drop_host on a demoted record cleans the
    store key."""
    a = BlockedAllocator(4, host_capacity=1)
    a.bind_cache(_ParkAll())
    st = _Store()
    a.bind_nvme(st, capacity=1)
    b1, b2, b3 = _spillable(a, 3)
    r1 = a.spill(b1, "a")
    r2 = a.spill(b2, "b")  # demotes r1
    assert not a.can_spill()
    with pytest.raises(ValueError, match="host tier full"):
        a.spill(b3, "c")
    a.drop_host(r1)  # nvme-resident record
    assert not st.payloads
    a.drop_host(r2)
    hs = a.host_swap_stats()
    assert hs["dropped"] == 2
    assert hs["spilled"] == hs["restored"] + hs["dropped"] \
        + hs["resident"] + hs["nvme_resident"]


def test_nvme_kv_store_roundtrip(tmp_path):
    """The in-tree aio-path store: write/read/drop of a page payload
    roundtrips through real files in the swap dir."""
    from deepspeed_tpu.runtime.swap_tensor.nvme_kv_store import NVMeKVStore
    st = NVMeKVStore(str(tmp_path))
    arrs = [np.arange(24, dtype=np.float32).reshape(2, 3, 4),
            np.arange(6, dtype=np.int8)]
    key = st.write(arrs)
    back = st.read(key)
    assert len(back) == 2
    np.testing.assert_array_equal(back[0], arrs[0])
    np.testing.assert_array_equal(back[1], arrs[1])
    st.drop(key)
    with pytest.raises(ValueError, match="unknown nvme key"):
        st.read(key)


# ---------------------------------------------------------------------------
# flow control units
# ---------------------------------------------------------------------------

def test_flow_control_window_and_backpressure():
    """admit() reserves per-(src,dst) bytes up to the window, defers the
    overflow (queued bytes -> link-time backpressure), and always admits
    into an empty window so a single oversized ship can't wedge."""
    f = FlowControl(max_inflight_bytes=100, link_gbps=8e-9)  # 1 byte/s
    f.open_round()
    assert f.admit("p0", "d0", 80)
    assert not f.admit("p0", "d0", 40), "window full -> defer"
    assert f.admit("p1", "d0", 500), "empty (src,dst) window always admits"
    assert f.inflight_bytes() == 580
    assert f.queued_bytes("p0") == 40
    assert f.backpressure_s("p0") == pytest.approx(40.0)
    assert f.backpressure_s("p1") == 0.0
    st = f.stats()
    assert st["deferrals"] == 1 and st["peak_inflight_bytes"] == 580
    f.open_round()
    assert f.queued_bytes() == 0 and f.inflight_bytes() == 0
    assert f.admit("p0", "d0", 40), "deferred work clears next round"


def test_router_prediction_includes_link_backpressure():
    """SLORouter.predicted_ttft adds the backend's link_backpressure_s —
    an oversubscribed fabric link makes a prefill replica look slower
    instead of stalling the ship."""
    from deepspeed_tpu.inference.v2.fleet.router import SLORouter

    class _Target:
        budget = 48

        def kv_stats(self):
            return {"occupancy": 0.0}

    class _Backend:
        def router_targets(self):
            return [(None, _Target()), (None, _Target())]

        def link_backpressure_s(self, i):
            return 2.5 if i == 0 else 0.0

    r = SLORouter(_Backend(), slo_ttft_s=1e9)
    assert r.predicted_ttft(0, 16) - r.predicted_ttft(1, 16) \
        == pytest.approx(2.5)


# ---------------------------------------------------------------------------
# fleet integration over the serialized codec
# ---------------------------------------------------------------------------

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 3,
    reason="fleet tests need >= 3 devices (prefill + decode + reference)")

ENG = {"state_manager": {"max_ragged_sequence_count": 12,
                         "max_ragged_batch_size": 64,
                         "max_context": 96,
                         "num_kv_blocks": 128,
                         "kv_dtype": "int8"},
       "kv_cache": {"block_size": 8, "cache_dtype": "fp32"},
       "prefix_caching": True}


@pytest.fixture(scope="module")
def served():
    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return cfg, model, params


def _prefix_requests(cfg, pools=2, per_pool=2, seed=11):
    """Groups sharing a 24-token prefix (the delta leg's savings); suffix
    lengths stagger by a full block so batched exports land on non-pow2
    page counts and the wire frame actually drops bucket padding."""
    rng = np.random.default_rng(seed)
    out = {}
    for g in range(pools):
        prefix = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)
        for i in range(per_pool):
            uid = g * per_pool + i
            sfx = rng.integers(1, cfg.vocab_size,
                               4 + 8 * uid).astype(np.int32)
            out[uid] = np.concatenate([prefix, sfx])
    return out


def _reference(model, params, prompts, max_new=6):
    mesh, sched = build_replica(model, params, [jax.devices()[0]],
                                engine_config=ENG, token_budget=48)
    with mesh:
        for uid, p in prompts.items():
            sched.submit(uid, p, max_new_tokens=max_new, temperature=0.0,
                         seed=3)
        return {u: np.asarray(v, np.int32)
                for u, v in sched.run_to_completion().items()}


def _run_fleet(model, params, prompts, max_new=6, **kw):
    kw.setdefault("engine_config", ENG)
    kw.setdefault("token_budget", 48)
    kw.setdefault("prefill_replicas", 1)
    kw.setdefault("decode_replicas", 1)
    fleet = PrefillDecodeFleet(model, params, codec="wire", **kw)
    for uid, p in prompts.items():
        fleet.submit(uid, p, max_new_tokens=max_new, temperature=0.0,
                     seed=3)
    out = fleet.run_to_completion()
    return fleet, {u: np.asarray(v, np.int32) for u, v in out.items()}


def _assert_parity(got, want):
    assert set(got) == set(want)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid],
                                      err_msg=f"uid {uid}")


@pytest.fixture(scope="module")
def ref6(served):
    """Monolithic single-replica greedy outputs for the standard prefix
    trace, computed ONCE. Per-request output is batch-composition
    independent (the repo's pinned serving invariant), so tests running
    any subset of these prompts slice their expected tokens from here."""
    cfg, model, params = served
    return _reference(model, params, _prefix_requests(cfg))


@needs_devices
def test_delta_shipping_skips_held_prefix_blocks(served, ref6):
    """Wire codec end to end, no-delta vs delta. The plain leg pins the
    serialized path bit-exact against the monolithic reference (encode ->
    CRC verify -> decode -> import; int8 pools make the wire lossless)
    with serialized bytes under the padded device page bytes. The delta
    leg's digest exchange then ships measurably fewer wire bytes for the
    later members of each prefix group — and stays bit-exact (the decode
    side re-binds the held blocks by digest)."""
    cfg, model, params = served
    prompts = _prefix_requests(cfg)
    f_plain, got_plain = _run_fleet(model, params, prompts,
                                    delta_shipping=False)
    f_delta, got_delta = _run_fleet(model, params, prompts,
                                    delta_shipping=True)
    _assert_parity(got_plain, ref6)
    _assert_parity(got_delta, ref6)
    plain, delta = f_plain.transport.stats(), f_delta.transport.stats()
    assert plain["codec"] == "wire"
    assert plain["wire_bytes_shipped"] > 0
    assert plain["crc_failures"] == 0 and plain["failed_handoffs"] == 0
    # serialized int8 wire bytes undercut the padded device page bytes
    assert plain["wire_bytes_shipped"] < plain["bytes_shipped"]
    assert delta["delta_shipping"] and not plain["delta_shipping"]
    assert delta["pages_delta_skipped"] > 0
    assert delta["wire_bytes_saved"] > 0
    assert delta["wire_bytes_shipped"] < plain["wire_bytes_shipped"]


@needs_devices
def test_crc_corruption_retries_wire_leg_then_succeeds(served, ref6):
    """A single injected in-flight corruption: CRC catches it, the typed
    WireCRCError retries ONLY the encode->decode leg (the export is not
    idempotent and must not re-run), and the handoff completes
    bit-exactly."""
    cfg, model, params = served
    prompts = _prefix_requests(cfg, pools=1, per_pool=2)
    faults.configure(spec="transport.corrupt:once")
    fleet, got = _run_fleet(model, params, prompts)
    _assert_parity(got, {u: ref6[u] for u in prompts})
    st = fleet.transport.stats()
    assert st["crc_failures"] == 1, "the flipped byte must be detected"
    assert st["retry_trips"] >= 1
    assert st["failed_handoffs"] == 0
    assert fleet.handoff_fallbacks == 0


@needs_devices
def test_crc_corruption_exhausted_falls_back_to_reprefill(served, ref6):
    """Every attempt corrupted: retries exhaust into a typed
    HandoffError(transfer), the fleet re-prefills on the decode side, and
    the output is STILL bit-exact — a poisoned link degrades throughput,
    never correctness."""
    cfg, model, params = served
    prompts = _prefix_requests(cfg, pools=1, per_pool=2)
    faults.configure(spec="transport.corrupt:always")
    fleet, got = _run_fleet(model, params, prompts)
    faults.reset()
    _assert_parity(got, {u: ref6[u] for u in prompts})
    st = fleet.transport.stats()
    assert st["failed_handoffs"] >= 1
    assert fleet.handoff_fallbacks == len(prompts)


@needs_devices
def test_flow_control_accounts_ships_and_completes(served, ref6):
    """Flow control in the handoff path: every ship reserves its estimated
    wire bytes on the (src, dst) link (peak > 0 proves the admissions went
    through the ledger), the fleet exposes the ledger to the router
    (load_report + link_backpressure_s), and a 1-byte window still
    completes every request bit-exactly — a group arriving at an empty
    link window always admits, so a mega-handoff ships alone rather than
    wedging. (Deferral + backpressure arithmetic for a CONTENDED window is
    unit-pinned in test_flow_control_window_and_backpressure.)"""
    cfg, model, params = served
    prompts = _prefix_requests(cfg)
    flow = FlowControl(max_inflight_bytes=1)
    fleet, got = _run_fleet(model, params, prompts, flow=flow,
                            delta_shipping=True)
    _assert_parity(got, ref6)
    st = flow.stats()
    assert st["peak_inflight_bytes"] > 0, "ships must reserve link bytes"
    assert fleet.load_report()["flow"] == st
    assert fleet.link_backpressure_s(0) == 0.0, "drained fleet: no backlog"


@needs_devices
def test_fleet_decode_speculative_default_on(served):
    """Fleet decode replicas default speculative decoding ON (the model
    has a verify forward); prefill replicas never speculate (they emit one
    token); output stays bit-exact through the handoff (satellite a)."""
    cfg, model, params = served
    prompts = _prefix_requests(cfg)
    want = _reference(model, params, prompts, max_new=8)
    fleet, got = _run_fleet(model, params, prompts, max_new=8)
    _assert_parity(got, want)
    assert fleet.decode[0][1]._spec, "spec-default must arm decode replicas"
    assert not fleet.prefill[0][1]._spec


def test_with_speculative_default_gating():
    """The default only fills a MISSING key on dict/None configs for
    models with a verify forward: an explicit setting always wins, and
    unsupported models are left untouched."""
    f = PrefillDecodeFleet._with_speculative_default
    m = LlamaForCausalLM(LlamaConfig.tiny())
    assert f(None, m)["speculative"] == {"enabled": True}
    assert f({}, m)["speculative"] == {"enabled": True}
    explicit = {"speculative": {"enabled": False}}
    assert f(explicit, m) is explicit, "explicit config must win"

    class MixtralConfig:  # resolve_verify_fn keys on the config class NAME
        pass

    class _NoVerify:
        config = MixtralConfig()
    assert f(None, _NoVerify()) is None, "no verify fn -> no default"
    assert f({}, _NoVerify()) == {}


@needs_devices
def test_wire_telemetry_reports_true_wire_bytes(served):
    """Satellite b: handoff telemetry reports SERIALIZED wire bytes, not
    padded device page bytes — the aggregate's wire_bytes matches the
    transport counter and undercuts the device-byte figure."""
    cfg, model, params = served
    prompts = _prefix_requests(cfg)
    telemetry.configure(enabled=True, jsonl_path="", chrome_trace_path="")
    fleet, _ = _run_fleet(model, params, prompts)
    agg = telemetry.summary()["fleet"]["handoff"]
    st = fleet.transport.stats()
    assert agg["count"] == len(prompts)
    assert agg["wire_bytes"] == pytest.approx(
        st["wire_bytes_shipped"], rel=0.01)
    assert agg["wire_bytes"] < agg["bytes"], \
        "telemetry must report serialized bytes, not padded device bytes"


@needs_devices
def test_engine_nvme_tier_spills_past_host_capacity(served):
    """1M-token-regime pressure order (spill -> NVMe -> evict): a tiny
    pool with a tiny host tier and an NVMe tier demotes parked prefix
    blocks to disk, restores them on reuse, and keeps the extended
    identity kv_spilled == kv_restored + kv_dropped + host_kv_blocks +
    nvme_kv_blocks (satellite: the allocator property test's identity,
    live on an engine)."""
    cfg, model, params = served
    eng = {"state_manager": {"max_ragged_sequence_count": 4,
                             "max_ragged_batch_size": 32,
                             "max_context": 96,
                             "num_kv_blocks": 10,
                             "kv_dtype": "int8",
                             "host_kv_blocks": 2,
                             "nvme_kv_blocks": 8},
           "kv_cache": {"block_size": 8, "cache_dtype": "fp32"},
           "prefix_caching": True}
    mesh, sched = build_replica(model, params, [jax.devices()[0]],
                                engine_config=eng, token_budget=32)
    rng = np.random.default_rng(5)
    # three distinct 5-block prefixes, served round-robin: each arrival
    # evicts the others' parked blocks (pool 10 can't hold two working
    # sets), so a prefix returning on its next turn finds its blocks in
    # the host/NVMe tiers and must RESTORE them
    prefixes = [rng.integers(1, cfg.vocab_size, 40).astype(np.int32)
                for _ in range(3)]
    with mesh:
        for uid in range(9):
            sfx = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
            sched.submit(uid, np.concatenate([prefixes[uid % 3], sfx]),
                         max_new_tokens=4, temperature=0.0, seed=1)
            sched.run_to_completion()
    st = sched.kv_stats()
    assert st["kv_spilled"] == st["kv_restored"] + st["kv_dropped"] \
        + st["host_kv_blocks"] + st["nvme_kv_blocks"]
    assert st["nvme_kv_demotions"] > 0, \
        "host tier (2 blocks) must overflow into NVMe under this pressure"
    assert st["kv_restored"] > 0, "prefix reuse must restore spilled blocks"


# ---------------------------------------------------------------------------
# two-process leg (real OS process boundary)
# ---------------------------------------------------------------------------

def test_two_process_framing_roundtrip():
    """The control-channel framing (length-prefixed JSON header + binary
    payload over a Pipe) roundtrips both directions without jax or a
    child interpreter."""
    import multiprocessing as mp
    from deepspeed_tpu.inference.v2.fleet.two_process import _recv, _send
    a, b = mp.Pipe()
    _send(a, {"op": "ship", "adopts": [{"uid": 3}]}, b"\x00\x01payload")
    header, payload = _recv(b)
    assert header == {"op": "ship", "adopts": [{"uid": 3}]}
    assert payload == b"\x00\x01payload"
    _send(b, {"op": "ack", "bound": 5})
    header, payload = _recv(a)
    assert header == {"op": "ack", "bound": 5} and payload == b""
    a.close()
    b.close()


@pytest.mark.slow
@needs_devices
def test_two_process_fleet_bit_exact(served, ref6):
    """Prefill parent + decode child in a SEPARATE OS process: every page
    crosses the pipe as a CRC32-checked wire frame, delta-shipping works
    across the boundary, and greedy output matches the monolithic
    reference token for token."""
    from deepspeed_tpu.inference.v2.fleet.two_process import TwoProcessFleet
    cfg, model, params = served
    prompts = _prefix_requests(cfg)
    want = ref6
    tp = TwoProcessFleet(model, params, dataclasses.asdict(cfg),
                         engine_config=ENG, token_budget=48,
                         delta_shipping=True)
    try:
        for uid, p in prompts.items():
            tp.submit(uid, p, max_new_tokens=6, temperature=0.0, seed=3)
        got = {u: np.asarray(v, np.int32)
               for u, v in tp.run_to_completion().items()}
    finally:
        tp.close()
    _assert_parity(got, want)
    st = tp.stats()
    assert st["handoffs"] == len(prompts)
    assert st["pages_delta_skipped"] > 0
    assert st["crc_naks"] == 0 and st["fallbacks"] == 0
    assert st["lost_requests"] == 0
