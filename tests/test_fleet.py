"""Serving fleet: SLO-aware router + prefill/decode disaggregation.

The load-bearing invariant is BIT-EXACTNESS: a request admitted through the
router, prefilled on a prefill-only replica, shipped (KV pages) to a decode
replica and finished there must emit exactly the tokens the monolithic
single-replica path emits — greedy and seeded sampling alike. Around that:
typed admission outcomes under saturation, page conservation across
handoffs, cancellation without KV leaks, and the public load-signal
accessors the router runs on.
"""

import numpy as np
import pytest

import jax

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2.fleet import (
    PrefillDecodeFleet, RequestAdmitted, RequestQueued, RequestRejected,
    SLORouter)
from deepspeed_tpu.inference.v2.replica_group import build_replica
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 3,
    reason="fleet tests need >= 3 devices (2 prefill + 1 decode)")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.close()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


@pytest.fixture(scope="module")
def served():
    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return cfg, model, params


ENG = {"state_manager": {"max_ragged_sequence_count": 9,
                         "max_ragged_batch_size": 64,
                         "max_context": 96,
                         "num_kv_blocks": 96},
       "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}}


def make_fleet(model, params, **kw):
    kw.setdefault("engine_config", ENG)
    kw.setdefault("token_budget", 48)
    return PrefillDecodeFleet(model, params, prefill_replicas=2,
                              decode_replicas=1, **kw)


def single_reference(model, params, requests):
    """Monolithic single-replica run of the same requests:
    {uid: (prompt, kwargs)} -> {uid: tokens}."""
    mesh, sched = build_replica(model, params, [jax.devices()[0]],
                                engine_config=ENG, token_budget=48)
    with mesh:
        for uid, (prompt, kwargs) in requests.items():
            sched.submit(uid, prompt, **kwargs)
        return {u: np.asarray(v, np.int32)
                for u, v in sched.run_to_completion().items()}


def _requests(cfg, n=4, seed=5, max_new=6, sampling=False):
    """Mixed-length prompts, several longer than the prefill chunk so the
    SplitFuse chunking and the handoff both run."""
    rng = np.random.default_rng(seed)
    out = {}
    for uid in range(n):
        plen = int(rng.integers(5, 60))
        kwargs = {"max_new_tokens": max_new}
        if sampling:
            kwargs.update(temperature=0.9, top_k=5,
                          seed=int(rng.integers(0, 2 ** 30)))
        out[uid] = (rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                    kwargs)
    return out


# ---------------------------------------------------------------------------
# bit-exact disaggregation
# ---------------------------------------------------------------------------

def test_fleet_greedy_bit_exact_vs_single(served):
    """Greedy fleet output (prefill -> ship -> decode) must equal the
    monolithic single-replica run token for token."""
    cfg, model, params = served
    requests = _requests(cfg, n=4, seed=5)
    want = single_reference(model, params, requests)

    fleet = make_fleet(model, params)
    for uid, (prompt, kwargs) in requests.items():
        fleet.submit(uid, prompt, **kwargs)
    got = fleet.run_to_completion()
    assert set(got) == set(want)
    for uid in want:
        np.testing.assert_array_equal(np.asarray(got[uid], np.int32),
                                      want[uid], err_msg=f"uid {uid}")
    # every multi-token request crossed the prefill->decode boundary
    assert fleet.transport.handoffs == len(requests)
    assert fleet.transport.pages_shipped == fleet.transport.pages_bound > 0
    # batched: never more device copies than handed-off requests
    assert 0 < fleet.transport.transfers <= fleet.transport.handoffs


def test_fleet_seeded_sampling_bit_exact_vs_single(served):
    """Seeded stochastic sampling is deterministic per (seed, position), so
    the decode side inherits the prefill side's stream mid-request and the
    fleet still matches the monolithic run exactly."""
    cfg, model, params = served
    requests = _requests(cfg, n=4, seed=11, sampling=True)
    want = single_reference(model, params, requests)

    fleet = make_fleet(model, params)
    for uid, (prompt, kwargs) in requests.items():
        fleet.submit(uid, prompt, **kwargs)
    got = fleet.run_to_completion()
    for uid in want:
        np.testing.assert_array_equal(np.asarray(got[uid], np.int32),
                                      want[uid], err_msg=f"uid {uid}")


def test_single_token_request_finishes_at_prefill(served):
    """max_new_tokens=1 never ships: the prefill side is the terminal
    owner and the transport stays untouched."""
    cfg, model, params = served
    fleet = make_fleet(model, params)
    prompt = np.arange(20, dtype=np.int32) % cfg.vocab_size
    fleet.submit(0, prompt, max_new_tokens=1)
    out = fleet.run_to_completion()
    assert len(out[0]) == 1
    assert fleet.transport.handoffs == 0
    assert fleet.transport.transfers == 0


# ---------------------------------------------------------------------------
# router admission under saturation
# ---------------------------------------------------------------------------

def test_router_typed_outcomes_and_shedding(served):
    """Past-SLO requests queue up to the bound, then shed — typed outcomes,
    consistent accounting, and queued requests still run to completion
    (force-admitted once the backend idles)."""
    cfg, model, params = served
    fleet = make_fleet(model, params)
    router = SLORouter(fleet, slo_ttft_s=1e-9, queue_limit=2,
                       prefix_affinity=False)
    rng = np.random.default_rng(2)
    outcomes = [router.submit(uid,
                              rng.integers(0, cfg.vocab_size, 24)
                              .astype(np.int32), max_new_tokens=3)
                for uid in range(5)]
    # an impossible SLO queues everything; the queue bound sheds the rest
    assert [type(o) for o in outcomes] == [RequestQueued, RequestQueued,
                                           RequestRejected, RequestRejected,
                                           RequestRejected]
    assert outcomes[2].reason.startswith("predicted TTFT")
    assert router.report()["queue_depth"] == 2
    assert router.shed_rate == pytest.approx(3 / 5)

    out = router.run_to_completion()
    assert set(out) == {0, 1}  # shed requests never ran
    assert all(len(v) == 3 for v in out.values())
    rep = router.report()
    assert rep["admitted"] + rep["rejected"] == rep["submitted"]
    assert rep["queue_depth"] == 0


def test_router_admits_under_slo_and_rejects_unservable(served):
    cfg, model, params = served
    fleet = make_fleet(model, params)
    router = SLORouter(fleet, slo_ttft_s=60.0, prefix_affinity=False)
    a = router.submit(0, np.arange(16, dtype=np.int32) % cfg.vocab_size,
                      max_new_tokens=2)
    assert isinstance(a, RequestAdmitted)
    assert 0 < a.predicted_ttft_s <= 60.0
    # a prompt that cannot fit any replica's max_context sheds immediately
    # with a typed reason instead of a scheduler ValueError
    r = router.submit(1, np.zeros(200, np.int32), max_new_tokens=2)
    assert isinstance(r, RequestRejected) and "max_context" in r.reason
    assert len(router.run_to_completion()[0]) == 2


def test_router_prefix_affinity_pulls_to_warm_replica(served):
    """A prompt whose prefix is cached on one prefill replica routes there
    (the cached blocks shrink its predicted TTFT) and records the hit."""
    cfg, model, params = served
    eng_cfg = dict(ENG, prefix_caching=True)
    fleet = make_fleet(model, params, engine_config=eng_cfg)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, 33).astype(np.int32)
    # seed replica 1's prefix cache: the export at handoff commits the
    # prefilled blocks before releasing them
    fleet.submit(0, prompt, max_new_tokens=3, replica=1)
    fleet.run_to_completion()
    assert fleet.prefill[1][1].peek_prefix(prompt) > 0

    router = SLORouter(fleet, slo_ttft_s=60.0)
    a = router.submit(1, prompt, max_new_tokens=3)
    assert isinstance(a, RequestAdmitted)
    assert a.replica == 1 and a.affinity_tokens > 0
    assert router.affinity_hits == 1
    router.run_to_completion()


# ---------------------------------------------------------------------------
# page conservation + cancellation
# ---------------------------------------------------------------------------

def _total_free(fleet):
    return {role: [s.engine.free_blocks for _, s in side]
            for role, side in (("prefill", fleet.prefill),
                               ("decode", fleet.decode))}


def test_fleet_drains_all_kv_pages(served):
    """After a full run every pool is back to its initial free-block count:
    export released the prefill side, finish flushed the decode side."""
    cfg, model, params = served
    fleet = make_fleet(model, params)
    before = _total_free(fleet)
    for uid, (prompt, kwargs) in _requests(cfg, n=4, seed=13).items():
        fleet.submit(uid, prompt, **kwargs)
    fleet.run_to_completion()
    assert _total_free(fleet) == before


def test_fleet_cancel_frees_pages_on_either_side(served):
    """Cancel mid-prefill and mid-decode: both free their KV pages and the
    remaining requests still finish bit-exactly."""
    cfg, model, params = served
    requests = _requests(cfg, n=3, seed=17, max_new=8)
    want = single_reference(model, params,
                            {2: requests[2]})  # the survivor
    fleet = make_fleet(model, params)
    before = _total_free(fleet)
    for uid, (prompt, kwargs) in requests.items():
        fleet.submit(uid, prompt, **kwargs)
    assert fleet.cancel(0)          # still queued/prefilling
    while fleet.transport.handoffs == 0 and fleet.has_work:
        fleet.step()
    handed = [uid for uid, r in fleet._route.items() if r[0] == "decode"]
    if 1 in handed:
        assert fleet.cancel(1)      # now lives on the decode side
    out = fleet.run_to_completion()
    np.testing.assert_array_equal(np.asarray(out[2], np.int32), want[2])
    assert _total_free(fleet) == before
    assert fleet.cancel(99) is False  # unknown uid


# ---------------------------------------------------------------------------
# load signals + telemetry
# ---------------------------------------------------------------------------

def test_load_report_and_public_accessors(served):
    cfg, model, params = served
    fleet = make_fleet(model, params)
    rep = fleet.load_report()
    assert [r["replica"] for r in rep["replicas"]] == \
        ["prefill0", "prefill1", "decode0"]
    assert all(r["active"] == 0 and r["kv_occupancy"] == 0.0
               for r in rep["replicas"])
    assert rep["transport"]["pages_shipped"] == 0
    prompt = np.arange(30, dtype=np.int32) % cfg.vocab_size
    replica = fleet.submit(0, prompt, max_new_tokens=4)
    sched = fleet.prefill[replica][1]
    assert sched.active_count() == 1
    stats = sched.kv_stats()
    assert {"occupancy", "free_blocks"} <= set(stats)
    fleet.run_to_completion()
    assert sched.active_count() == 0


def test_fleet_telemetry_stream(served):
    """Router admissions and handoffs land in summary()["fleet"]: typed
    event counts, queue/shed gauges, and handoff page/byte/latency totals
    with pages shipped == pages bound."""
    cfg, model, params = served
    telemetry.configure(enabled=True)
    fleet = make_fleet(model, params)
    router = SLORouter(fleet, slo_ttft_s=60.0, prefix_affinity=False)
    requests = _requests(cfg, n=3, seed=23)
    for uid, (prompt, kwargs) in requests.items():
        assert isinstance(router.submit(uid, prompt, **kwargs),
                          RequestAdmitted)
    router.run_to_completion()

    flt = telemetry.summary()["fleet"]
    assert flt["events"]["admitted"] == 3
    h = flt["handoff"]
    assert h["count"] == 3
    assert h["pages_shipped"] == h["pages_bound"] > 0
    assert h["bytes"] > 0 and h["total_s"] > 0
    hists = telemetry.summary()["serving"]["histograms"]
    assert hists["fleet/predicted_ttft_s"]["count"] == 3
    assert hists["fleet/handoff_s"]["count"] == 3
