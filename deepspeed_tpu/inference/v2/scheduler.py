"""SplitFuse continuous-batching scheduler over ``InferenceEngineV2``.

The reference keeps this role in DeepSpeed-MII (``engine_v2.py`` exposes
``query``/``can_schedule`` for it; the SplitFuse policy is described in the
FastGen blog): every forward carries a near-constant token budget by
splitting long prompts into chunks and fusing them with the single-token
decodes of running sequences — prefill never stalls decode latency and the
MXU always sees a full batch.

Pure host-side policy: composes ragged batches, calls ``engine.put``, samples
greedily, retires finished sequences. The engine's admission control
(``can_schedule``) stays the source of truth; the scheduler only proposes.

Every lifecycle transition feeds the telemetry serving stream when enabled
(submit -> queued -> prefill-chunk -> decode -> finish/evict, plus
preempt/resume): TTFT/TPOT/e2e/queue-wait histograms, per-request
Chrome-trace lanes, and per-step scheduler gauges (token-budget utilization,
running/waiting counts, KV occupancy via ``engine.sample_kv_stats``).
Disabled, every such hook is a single boolean check — zero allocations and
zero syncs per step, and the clock is read twice per REQUEST (at ``submit``
and at its admission), never per round (pinned by
tests/test_serving_observability.py).

Whatever ``enabled`` says, each round opens ``telemetry.span``s
(``serving/round`` > ``serving/compose``, the engine's ``serving/build``,
``dispatch`` (> ``dispatch/h2d``, ``dispatch/forward``, ``dispatch/sample``)
and ``post_forward`` once per dispatch of the round, its one ``fetch``,
``serving/retire``) and marks each request's ``serving/admit`` /
``first_token`` / ``finish``: profiler annotations that cost about a
microsecond with no profiler session and never sync. All carry the engine's
``round``: compose, build, dispatch and post_forward the round they
dispatch, fetch and retire the round they fetch, ``serving/round`` the round
whose result the ``step()`` returns; the engine's per-dispatch spans also its
``dispatch``, one value per forward. docs/OBSERVABILITY.md lists their
attributes.

``step()`` runs ahead by one round where no request could have joined the
next round anyway: it composes and dispatches round n + 1 before it fetches
round n, so the device never waits for the host between them
(``_propose_ahead`` has the rule, docs/SERVING.md the whole of it).
"""

import collections
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import short_row_tokens

# module-level alias so tests can prove the disabled path never reads the
# clock (monkeypatching time.perf_counter itself would break jax internals)
_now = time.perf_counter


def sheddable_classes(targets, burning):
    """Which SLO classes absorb shedding/preemption while ``burning``
    classes exceed burn rate 1: every class whose TTFT target is strictly
    LOOSER than the tightest burning class's. A batch class (30s TTFT)
    sheds for a burning interactive class (4s); the reverse never holds —
    a burning batch class cannot push interactive rows out. ``targets`` is
    the ``telemetry.slo_class_targets()`` shape; classes without a TTFT
    target never shed for anyone (and nothing sheds for them)."""
    if not burning:
        return frozenset()
    tight = min((targets.get(c, {}).get("ttft_target_s") or float("inf"))
                for c in burning)
    out = set()
    for cls, spec in targets.items():
        if cls in burning:
            continue
        t = spec.get("ttft_target_s")
        if t is not None and t > tight:
            out.add(cls)
    return frozenset(out)


@dataclasses.dataclass
class _Request:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_token_id: Optional[int]
    slo_class: Optional[str] = None  # serving SLO class (config slo_classes)
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    prefill_pos: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    # sampling-stream offset for re-admitted requests: the request already
    # emitted ``pos_offset`` tokens on a replica that died, so every sample
    # here draws at position ``len(generated) + pos_offset`` — the exact
    # position the uninterrupted stream would use (bit-exact recovery)
    pos_offset: int = 0
    # what rounds dispatched and not yet retired will have done: prompt
    # tokens run and tokens sampled. ``prefill_pos`` and ``generated`` move
    # at retire, so they show a caller what ``step()`` has returned and
    # nothing of a round in flight; composing counts both
    flying_prefill: int = 0
    flying_new: int = 0
    done: bool = False
    preempted: bool = False  # KV host-swapped out (scheduler preemption)
    # perf_counter timestamps: submit_ts and first_sched_ts always (0.0 =
    # not yet), last_token_ts only with telemetry enabled
    submit_ts: float = 0.0
    first_sched_ts: float = 0.0
    last_token_ts: float = 0.0

    @property
    def prefilling(self):
        return self.prefill_pos < len(self.prompt)

    @property
    def to_prefill(self):
        """Prompt tokens no round has been dispatched for yet."""
        return len(self.prompt) - self.prefill_pos - self.flying_prefill


#: a round dispatched and not yet fetched (``step_begin``'s handle): its rows
#: and chunks, the ids (or logits) on the device, when it was dispatched,
#: which rows were mid-prompt, its tokens, the engine's ``round``, the tokens
#: each row will have sampled (0 or 1), and whether it was dispatched ahead
_Pending = collections.namedtuple(
    "_Pending", "uids chunks ids logits t_fwd was_prefilling sched_tokens "
                "rnd news ahead")


class SplitFuseScheduler:
    """Greedy continuous batching with chunked (split) prefill.

    Args:
        engine: an ``InferenceEngineV2``.
        token_budget: max tokens per forward (defaults to the engine's
            ``max_ragged_batch_size``).
    """

    def __init__(self, engine, token_budget=None, device_sampling=True):
        self._engine = engine
        sm = engine._config.state_manager
        self._budget = min(token_budget or sm.max_ragged_batch_size,
                           sm.max_ragged_batch_size)
        self._max_seqs = sm.max_ragged_sequence_count
        self._requests: Dict[int, _Request] = {}
        self._starved = 0  # consecutive rounds with nothing schedulable
        # prefix-cache awareness: resolved once at construction so the
        # disabled path costs one attribute read per prefill candidate
        self._prefix_caching = bool(getattr(engine, "prefix_caching", False))
        # prompt tokens actually run vs skipped via cached prefixes —
        # plain ints (always on) so bench harnesses can report reductions
        # without telemetry
        self.prefill_tokens_executed = 0
        self.prefill_tokens_saved = 0
        # batch occupancy without a profile, each count a plain attribute
        # too (``sched.rounds``: ``__getattr__``, 0 until something adds to
        # it). The scheduler's own: ``rounds``, the ``dispatches`` they took,
        # and run-ahead's ``rounds_ahead`` (dispatched before the round
        # before them was fetched), ``ahead_rows`` (their rows whose token
        # came from the device) and ``ahead_rows_dropped`` (those of them
        # that had ended, an eos or a cancel, and rode the round for
        # nothing). The rest is the engine's ``last_counts`` summed over
        # rounds, keys this file never names: the sums of the
        # ``serving/build`` spans' ``real_tokens``, ``padded_slots``,
        # ``live_pages`` and of what the model's cache groups and expert
        # layer add (docs/SERVING.md, "What a dispatch reports")
        self.counts = collections.Counter()
        # the round dispatched ahead, ``step_finish``'s to fetch next
        self._flying = None
        # device_sampling=True (default) fuses temperature/top-k/top-p and
        # the categorical draw into the decode step on the accelerator: the
        # host receives one int32 per sequence instead of a [S, vocab] float
        # tensor per forward. False keeps the numpy reference sampler (its
        # draws differ stream-wise from jax.random, but both are
        # deterministic per (seed, position)).
        self._device_sampling = bool(device_sampling)
        # submitted-but-unfinished count, maintained incrementally so
        # per-request placement decisions (fleet router, replica skew)
        # never scan the request table
        self._active = 0
        # draft-then-verify decode (config_v2 SpeculativeConfig): decode
        # rows carry [last_token] + drafted tokens as a SplitFuse chunk
        # through the verify forward; accepted prefixes commit their KV in
        # place, rejected tails roll the paged cursor back. Off: zero extra
        # work per step (every branch below is one bool test).
        spec_cfg = getattr(engine._config, "speculative", None)
        self._spec = bool(spec_cfg is not None and spec_cfg.enabled)
        self._drafter = None
        self._kmax = 0
        if self._spec:
            if not self._device_sampling:
                raise ValueError(
                    "speculative decode requires device_sampling=True "
                    "(the verify sampler is the on-device k-token path)")
            if not engine.verify_supported:
                raise ValueError(
                    "speculative decode requires an engine with a verify "
                    "forward (engine_factory.resolve_verify_fn)")
            from deepspeed_tpu.inference.v2.speculative import NgramDrafter
            self._drafter = NgramDrafter(spec_cfg.ngram_max)
            self._max_drafts = max(1, int(spec_cfg.max_draft_tokens))
            # static verify width: pow2 bucket holding drafts + 1 so one
            # compiled verify program serves every round
            self._kmax = 1
            while self._kmax < self._max_drafts + 1:
                self._kmax *= 2
        # speculation counters — plain ints, always on (bench harnesses and
        # the router's tokens_per_round signal read them without telemetry)
        self.speculated_tokens = 0
        self.accepted_tokens = 0
        self.rejected_tokens = 0
        # EWMA of tokens committed per decode row per round — the fleet
        # router divides its backlog-rounds estimate by this (a speculating
        # replica retires several tokens per round; predicting 1/round
        # systematically over-estimates its TTFT)
        self._tokens_per_round_ewma = 1.0
        # terminal outcomes beyond plain finish (evict/cancel), drained by
        # the fleet router so its predicted-backlog model retires on EVERY
        # terminal event — plain list appends, always on (the router must
        # not leak backlog just because telemetry is off)
        self.terminal_events = []
        # SLO-precedence preemptions taken (burn-rate gauge > 1 steered the
        # victim choice) — always-on int for bench payloads
        self.slo_preemptions = 0
        # prefill/decode disaggregation hook: called as on_finish(sched, req)
        # the moment a request completes, BEFORE the sequence flushes; a
        # truthy return means ownership (KV pages + remaining decode) moved
        # to another scheduler — this one skips flush and terminal telemetry
        self.on_finish = None
        # per-class SLO latency targets (config_v2.slo_classes), installed
        # into telemetry once here so slo_observe knows the targets; requests
        # tag themselves via submit(..., slo_class=...). The install survives
        # telemetry.reset() (configuration, like the sinks).
        self._slo_classes = dict(
            getattr(engine._config, "slo_classes", None) or {})
        if self._slo_classes:
            telemetry.set_slo_classes(self._slo_classes)

    def __getattr__(self, name):
        """A count by its name (``self.counts``): 0 until a round adds to
        it, so for a model none of whose dispatches reports it."""
        if name.startswith("_") or "counts" not in self.__dict__:
            raise AttributeError(name)
        return self.counts[name]

    def submit(self, uid, prompt, max_new_tokens=16, eos_token_id=None,
               temperature=0.0, top_k=0, top_p=1.0, seed=None,
               slo_class=None):
        """Queue a request. ``temperature`` 0.0 = greedy; otherwise
        per-request top-k/top-p sampling. ``seed=None`` draws a fresh random
        stream per request; pass an int for reproducible completions.
        ``slo_class`` tags the request's latency samples against that class's
        targets (config ``slo_classes``; see docs/SERVING.md)."""
        if uid in self._requests:
            raise ValueError(f"uid {uid} already submitted")
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        max_ctx = self._engine._config.state_manager.max_context
        if len(prompt) >= max_ctx:
            raise ValueError(f"prompt of {len(prompt)} tokens cannot fit "
                             f"max_context {max_ctx}")
        if seed is None:
            import secrets
            seed = secrets.randbits(31)
        if slo_class is not None and self._slo_classes \
                and slo_class not in self._slo_classes:
            raise ValueError(f"unknown slo_class {slo_class!r} (configured: "
                             f"{sorted(self._slo_classes)})")
        req = _Request(uid, prompt, int(max_new_tokens), eos_token_id,
                       slo_class=slo_class,
                       temperature=float(temperature),
                       top_k=int(top_k), top_p=float(top_p),
                       seed=int(seed))
        req.submit_ts = _now()
        tm = telemetry.get_telemetry()
        if tm.enabled:
            tm.serving_event("submitted")
            tm.record_request_phase(uid, "submit", req.submit_ts,
                                    prompt_tokens=len(prompt))
            tm.record_request_flow(uid, "submit",
                                   prompt_tokens=len(prompt))
        self._requests[uid] = req
        self._active += 1

    def adopt(self, uid, prompt, generated, max_new_tokens=16,
              eos_token_id=None, temperature=0.0, top_k=0, top_p=1.0,
              seed=0, submit_ts=0.0, last_token_ts=0.0, slo_class=None):
        """Adopt a mid-generation request whose KV pages were just imported
        into this scheduler's engine (prefill/decode disaggregation): the
        prompt is fully prefilled and ``generated`` holds the tokens the
        prefill side already sampled. Decode continues bit-exactly — device
        sampling is deterministic per (seed, position) and positions resume
        from ``len(generated)``. ``submit_ts``/``last_token_ts`` carry the
        originating timestamps through so e2e and TPOT histograms span the
        handoff instead of restarting at it."""
        if uid in self._requests:
            raise ValueError(f"uid {uid} already submitted")
        generated = [int(t) for t in generated]
        if not generated:
            raise ValueError("adopt requires at least one generated token")
        prompt = np.asarray(prompt, np.int32)
        seq = self._engine._state.get_sequence(uid)
        if seq is None or seq.seen_tokens != len(prompt):
            raise ValueError(
                f"uid {uid}: imported KV does not cover the prompt "
                f"(seen={seq.seen_tokens if seq else None}, "
                f"prompt={len(prompt)})")
        req = _Request(uid, prompt, int(max_new_tokens), eos_token_id,
                       slo_class=slo_class,
                       temperature=float(temperature), top_k=int(top_k),
                       top_p=float(top_p), seed=int(seed),
                       prefill_pos=len(prompt), generated=generated)
        req.submit_ts = float(submit_ts)
        req.last_token_ts = float(last_token_ts)
        # admitted where it was prefilled: queue-wait was recorded there
        t = req.first_sched_ts = _now()
        tm = telemetry.get_telemetry()
        if tm.enabled:
            tm.serving_event("adopted")
            tm.record_request_phase(uid, "adopt", t,
                                    seen_tokens=len(prompt),
                                    new_tokens=len(generated))
            tm.record_request_flow(uid, "adopt",
                                   new_tokens=len(generated))
        self._requests[uid] = req
        self._active += 1

    def readmit(self, uid, prompt, generated, max_new_tokens=16,
                eos_token_id=None, temperature=0.0, top_k=0, top_p=1.0,
                seed=0, submit_ts=0.0, last_token_ts=0.0, slo_class=None):
        """Re-admit a request that lost its KV mid-generation (replica loss
        or an exhausted handoff): unlike ``adopt``, NO pages exist here —
        the prompt plus every already-emitted token but the last re-prefill
        as an ordinary SplitFuse prompt (with prefix caching on, only the
        tail past the request's last committed prefix digest actually
        runs), and the deterministic sampling stream resumes at position
        ``len(generated)`` via ``pos_offset``, so the continuation is
        bit-exact with the uninterrupted run. ``max_new_tokens`` is the
        ORIGINAL quota; the emitted count is subtracted here."""
        if uid in self._requests:
            raise ValueError(f"uid {uid} already submitted")
        generated = [int(t) for t in generated]
        if not generated:
            raise ValueError("readmit requires at least one generated "
                             "token; resubmit the prompt instead")
        emitted = len(generated)
        if emitted >= int(max_new_tokens) or \
                (eos_token_id is not None and generated[-1] == eos_token_id):
            raise ValueError(f"uid {uid} is already complete "
                             f"({emitted} tokens)")
        prompt = np.asarray(prompt, np.int32)  # graftlint: allow[GL004] host-committed token list, never a device value
        head = np.asarray(generated[:-1], np.int32)  # graftlint: allow[GL004] host-committed token list, never a device value
        full = np.concatenate([prompt, head]) if emitted > 1 else prompt
        req = _Request(uid, full, int(max_new_tokens) - (emitted - 1),
                       eos_token_id, slo_class=slo_class,
                       temperature=float(temperature), top_k=int(top_k),
                       top_p=float(top_p), seed=int(seed),
                       generated=[generated[-1]], pos_offset=emitted - 1)
        req.submit_ts = float(submit_ts)
        req.last_token_ts = float(last_token_ts)
        tm = telemetry.get_telemetry()
        if tm.enabled:
            t = _now()
            tm.serving_event("readmitted")
            tm.record_request_phase(uid, "readmit", t,
                                    seen_tokens=len(full),
                                    new_tokens=emitted)
            tm.record_request_flow(uid, "readmit", new_tokens=emitted)
        self._requests[uid] = req
        self._active += 1

    def cancel(self, uid):
        """Withdraw a request (router shedding / requeue): frees its KV
        blocks — device-resident or host-swapped — and records the terminal
        ``serving/e2e_s`` + ``req/cancel`` lane, so cancellation never leaks
        blocks or silently drops the worst latencies from replay
        percentiles. Call between steps (the scheduler is synchronous). A
        request riding a round in flight is cancelled like any other: the
        retire of that round drops its id (``ahead_rows_dropped``).
        Returns True iff a live request was cancelled."""
        r = self._requests.get(uid)
        if r is None or r.done:
            return False
        r.done = True
        self._active -= 1
        self.terminal_events.append((uid, "cancelled"))
        self._mark_finish(r, "cancelled", self._engine.round)
        if self._engine._state.get_sequence(uid) is not None:
            self._engine.flush(uid)
        tm = telemetry.get_telemetry()
        if tm.enabled:
            t = _now()
            tm.record_hist("serving/e2e_s", t - (r.submit_ts or t))
            tm.serving_event("cancelled")
            tm.record_request_phase(uid, "cancel", t,
                                    new_tokens=len(r.generated))
            tm.record_request_flow(uid, "cancel", end=True)
        return True

    # -- public load signals (fleet router / ReplicaGroup) -----------------
    def active_count(self):
        """Submitted-but-unfinished request count, O(1)."""
        return self._active

    def drain_terminal(self):
        """Terminal outcomes beyond plain finish since the last call
        (``[(uid, "evicted" | "cancelled"), ...]``) — the router retires
        its predicted-backlog rounds on these; finished uids retire via the
        ``step()`` return instead."""
        events, self.terminal_events = self.terminal_events, []
        return events

    def _burning_classes(self):
        """Classes whose live burn-rate gauge exceeds 1 (either metric).
        Telemetry off or no classes configured -> () — precedence simply
        disengages (two attribute reads, no allocation)."""
        if not self._slo_classes:
            return ()
        tm = telemetry.get_telemetry()
        if not tm.enabled:
            return ()
        out = []
        for cls in self._slo_classes:
            for metric in ("ttft", "tpot"):
                v = tm.gauge_value(f"slo/{cls}/{metric}_burn_rate")
                if v is not None and v > 1.0:
                    out.append(cls)
                    break
        return out

    def tokens_per_round(self):
        """EWMA of tokens committed per decode row per round, >= 1.0 (the
        SLO router's TTFT divisor; exactly 1.0 without speculation)."""
        return self._tokens_per_round_ewma

    def kv_stats(self):
        """This replica's host-side KV pool stats
        (``InferenceEngineV2.kv_stats`` — occupancy, free blocks, swaps)."""
        return self._engine.kv_stats()

    def peek_prefix(self, prompt_tokens):
        """Cached-prefix coverage for a prompt, pure read (router
        prefix-digest affinity)."""
        return self._engine.peek_prefix(prompt_tokens)

    @property
    def budget(self):
        """Per-forward token budget (SplitFuse)."""
        return self._budget

    @property
    def engine(self):
        """The underlying ``InferenceEngineV2`` (page transfer, admission)."""
        return self._engine

    @property
    def max_context(self):
        return self._engine._config.state_manager.max_context

    @property
    def has_work(self):
        """A request unfinished, or a round in flight still to be fetched."""
        return self._flying is not None or \
            any(not r.done for r in self._requests.values())

    @staticmethod
    def _mark_finish(r, reason, rnd):
        """The request's terminal mark in the profiler's trace; ``rnd`` is
        the round being retired, or the one composed next."""
        telemetry.span("serving/finish", uid=r.uid, round=rnd,
                       new_tokens=len(r.generated), reason=reason).end()

    def _compose(self, ahead=False):
        """Pick (uids, token-chunks) for one forward under the budget.

        Decodes (1 token) first — they bound tail latency; leftover budget
        is split across pending prefills (the SplitFuse chunking).

        ``ahead``: the round before this one is still in flight, and the
        rows count what it will have done (``flying_prefill``,
        ``flying_new``). A decode row that rode it has its token on the
        device: its row is in the third value returned (empty otherwise). A
        row that ends in flight by count, or at the context roof, is left
        out, and nothing is evicted. The fourth value is then whether this
        round is CLOSED: a request submitted from now on could take nothing
        of it, as this method would compose it after the retire (None when
        not ``ahead``). Closed is: every sequence slot taken, or the token
        budget spent, or no page or slot of state for a sequence the engine
        does not track yet; and never where this is not what ``_propose``
        would compose after the retire (a sequence on the host to resume, a
        row at the roof to evict, pages a row ending in flight gives back
        that a chunk here was cut for)."""
        max_ctx = self._engine._config.state_manager.max_context
        tm = telemetry.get_telemetry()
        uids, chunks, budget = [], [], self._budget
        device_rows = set()
        # ahead: a row in flight ends (its pages and its slot are free once
        # it is retired); ``_propose`` would compose another round
        ending = differs = False
        for r in list(self._requests.values()):
            if r.done:
                continue
            if r.preempted:
                differs = True
                continue
            if r.to_prefill > 0 or len(uids) >= self._max_seqs:
                continue
            n_new = len(r.generated) + r.flying_new
            pos = len(r.prompt) + n_new
            if r.flying_new and (n_new >= r.max_new_tokens or pos >= max_ctx):
                ending = True
                continue
            if pos >= max_ctx:
                if ahead:
                    differs = True
                    continue
                # context capacity reached: retire with what it has — the
                # request can never schedule again and must not wedge others.
                # This IS the request's terminal event: record e2e latency
                # and the evict lane here or replay percentiles silently drop
                # exactly the worst-latency requests.
                r.done = True
                self._active -= 1
                self.terminal_events.append((r.uid, "evicted"))
                self._mark_finish(r, "evicted", self._engine.round)
                self._engine.flush(r.uid)
                if tm.enabled:
                    t_evict = _now()
                    tm.record_hist("serving/e2e_s",
                                   t_evict - (r.submit_ts or t_evict))
                    tm.serving_event("evicted")
                    tm.record_request_phase(r.uid, "evict", t_evict,
                                            seen_tokens=pos)
                    tm.record_request_flow(r.uid, "evict", end=True)
                continue
            if budget < 1:
                break
            if r.flying_new:
                device_rows.add(len(uids))
                chunk = [0]          # not read: the token is on the device
            else:
                chunk = [r.generated[-1]]
            if self._spec:
                # drafts bounded by the verify width, the row's remaining
                # token quota (emitting past max_new is wasted work), the
                # context roof (the chunk's KV must fit: seen is pos-1, so
                # at most max_ctx - pos drafts ride along), and the round's
                # token budget
                d_cap = min(self._max_drafts,
                            r.max_new_tokens - len(r.generated) - 1,
                            max_ctx - pos, budget - 1)
                if d_cap > 0:
                    chunk += self._drafter.draft(
                        list(r.prompt) + r.generated, d_cap)[:d_cap]
            uids.append(r.uid)
            chunks.append(np.asarray(chunk, np.int32))
            budget -= len(chunk)
        for r in self._requests.values():
            if r.done or r.to_prefill < 1 or r.preempted or r.uid in uids:
                continue
            if len(uids) >= self._max_seqs or budget < 1:
                break
            room, _ = self._engine.query(r.uid, budget,
                                         self._engine.free_blocks)
            take = min(budget, room, r.to_prefill)
            if ending and take < min(budget, r.to_prefill):
                differs = True       # cut for pages the retire gives back
            if take < 1:
                continue
            if self._prefix_caching and r.prefill_pos == 0 and \
                    (not r.generated or r.pos_offset):
                # pos_offset marks a re-admitted request: its "prompt" is
                # prompt + prior tokens, so the match below IS the
                # re-admission-from-last-prefix-digest contract — only the
                # tail past the cached chain re-runs
                # longest-cached-prefix match, deferred to the moment the
                # first chunk actually schedules — by then earlier requests
                # have committed their blocks, so queued bursts sharing a
                # prefix hit even when submitted before it was cached
                matched = self._engine.match_prefix(r.uid, r.prompt)
                if tm.enabled:
                    tm.serving_event("prefix_hit" if matched
                                     else "prefix_miss")
                    if matched:
                        tm.serving_event("prefill_tokens_saved", n=matched)
                if matched:
                    r.prefill_pos = matched
                    self.prefill_tokens_saved += matched
                    take = min(budget, room, r.to_prefill)
            at = r.prefill_pos + r.flying_prefill
            uids.append(r.uid)
            chunks.append(r.prompt[at:at + take])
            budget -= take
        if not ahead:
            return uids, chunks, device_rows, None
        closed = not differs and (
            len(uids) >= self._max_seqs or budget < 1
            or not (ending or self._engine.can_admit()))
        return uids, chunks, device_rows, closed

    def _try_resume(self):
        """Swap preempted sequences back in (oldest first) while device
        blocks allow — preempted work outranks new admissions. A sequence
        only resumes when it can ALSO schedule its next chunk afterwards:
        resuming into exactly-fitting blocks would re-preempt immediately and
        thrash the pool while others starve."""
        state = self._engine._state
        for r in list(self._requests.values()):
            if r.done or not r.preempted:
                continue
            need = self._engine.blocks_to_resume(r.uid)
            seq = state.get_sequence(r.uid)
            if seq is None:
                r.preempted = False
                continue
            grow = state.blocks_needed_for(seq.seen_tokens, need, 1,
                                           state.kv_block_size)
            if need and self._engine.free_blocks >= need + grow and \
                    self._engine.further_groups_fit_resume(r.uid):
                self._engine.resume(r.uid)
                r.preempted = False
                tm = telemetry.get_telemetry()
                if tm.enabled:
                    tm.serving_event("resumed")
                    tm.record_request_phase(r.uid, "resume", _now(),
                                            blocks=need)

    def _preempt_for_progress(self):
        """KV pressure relief (the ZeRO-Inference KV-offload path): push the
        request holding the most blocks out to the host tier so someone else
        can run; its cache is restored later, not recomputed. Half-prefilled
        sequences are valid victims — two of them deadlocking the pool
        (neither can grow) is the classic starvation case. Returns True if a
        sequence was preempted.

        This is the LAST pressure tier. Before any live sequence swaps,
        ``BlockedAllocator.allocate`` has already asked the prefix cache to
        reclaim parked blocks — spilling them to the host-DRAM KV tier while
        it has room (contents stay matchable; the double-buffered swapper
        defers the device->host landing so the transfer overlaps the next
        rounds' decode dispatches), then evicting outright. Pressure order:
        spill-to-host, evict-to-free, preempt-live."""
        def blocks_of(r):
            seq = self._engine._state.get_sequence(r.uid)
            return len(seq.kv_blocks) if seq is not None else 0

        candidates = [r for r in self._requests.values()
                      if not r.done and not r.preempted and blocks_of(r) > 0]
        active = sum(1 for r in self._requests.values()
                     if not r.done and not r.preempted)
        if len(candidates) < 1 or active < 2:
            return False  # alone: preempting would free blocks we then re-need
        # SLO precedence (PR 17's gauges as an INPUT): while any class's
        # burn rate exceeds 1, rows of strictly looser classes are
        # preempted first — batch absorbs the KV pressure so interactive
        # attainment holds. Falls through to pure blocks_of when no class
        # burns, nothing is tagged, or only protected rows hold blocks.
        slo_pick = False
        burning = self._burning_classes()
        if burning:
            shed = sheddable_classes(telemetry.slo_class_targets(), burning)
            preferred = [r for r in candidates
                         if r.slo_class is None or r.slo_class in shed]
            if preferred and len(preferred) < len(candidates):
                candidates = preferred
                slo_pick = True
        victim = max(candidates, key=blocks_of)
        if slo_pick:
            self.slo_preemptions += 1
        n_blocks = blocks_of(victim)
        self._engine.preempt(victim.uid)
        victim.preempted = True
        tm = telemetry.get_telemetry()
        if tm.enabled:
            if slo_pick:
                tm.serving_event("slo_preempted")
            tm.serving_event("preempted")
            tm.record_request_phase(victim.uid, "preempt", _now(),
                                    blocks=n_blocks)
        return True

    def _propose(self):
        """The round's (uids, chunks) as the engine admits them, both empty
        when nothing can run: resume what fits, compose under the budget,
        shrink until ``can_schedule`` agrees, preempt when starved. Also
        returns the rows the shrink loop dropped and the sequences
        preempted (0 or 1), for the ``serving/compose`` span."""
        self._try_resume()
        uids, chunks, _, _ = self._compose()
        if not uids:
            # nothing composable but preempted work pending and unresumable:
            # that's starvation too (e.g. a request whose resume needs more
            # blocks than the whole pool) — keep the counter honest so the
            # diagnostic error fires instead of a silent spin
            if any(not r.done and r.preempted for r in self._requests.values()):
                self._starved += 1
                if self._starved > 3:
                    raise RuntimeError(
                        f"no schedulable work for {self._starved} rounds: "
                        f"preempted sequence(s) cannot be resumed (KV cache "
                        f"too small for the request?)")
            return [], [], 0, 0
        # shrink the proposal until the engine admits it (KV pressure):
        # drafts shed first — a speculative decode row trims back to its
        # plain 1-token chunk (the draft tail is opportunistic; the row
        # still progresses), because ``_try_resume`` gates resume on
        # 1-token growth and popping the row instead would re-preempt it
        # and thrash the pool resume/preempt forever — then whole chunks
        # drop largest-first and RE-validate; put() would raise on an
        # oversubscribed batch
        shrunk = 0
        while uids:
            verdict = self._engine.can_schedule(uids, [len(c) for c in chunks])
            if verdict.success:
                break
            if self._spec:
                spec_rows = [i for i, u in enumerate(uids)
                             if not self._requests[u].prefilling
                             and len(chunks[i]) > 1]
                if spec_rows:
                    trim = max(spec_rows, key=lambda i: len(chunks[i]))
                    chunks[trim] = chunks[trim][:1]
                    continue
            biggest = int(np.argmax([len(c) for c in chunks]))
            uids.pop(biggest)
            chunks.pop(biggest)
            shrunk += 1
        if not uids:
            self._starved += 1
            # host-swap a blocked decode's KV before declaring starvation
            if self._preempt_for_progress():
                self._starved = 0
                return [], [], shrunk, 1
            if self._starved > 3:
                raise RuntimeError(
                    f"no schedulable work for {self._starved} rounds: "
                    f"{verdict.reason} (KV cache too small for any request?)")
            return [], [], shrunk, 0
        self._starved = 0
        return uids, chunks, shrunk, 0

    def _propose_ahead(self):
        """Round n + 1's (uids, chunks, rows whose token is on the device)
        while round n is in flight, or None: the round is then composed as
        ever, after n has been retired and the caller has had its turn to
        submit. Not None only where the composition is CLOSED (``_compose``)
        and is what ``_propose`` would return after the retire with nothing
        more submitted: no sequence to resume, nothing to shrink, preempt or
        shed. So no request is admitted a round later for it. Changes
        nothing."""
        uids, chunks, device_rows, closed = self._compose(ahead=True)
        if not (uids and closed and self._engine.can_schedule(
                uids, [len(c) for c in chunks]).success):
            return None
        return uids, chunks, device_rows

    def step(self):
        """One scheduling round + forward. Returns uids finished this round.

        Where it can, it first composes and dispatches the NEXT round, then
        fetches and retires this one: that round stays in flight and is the
        next call's to return (at most one beyond the one being fetched).
        Whether it can is decided round by round (``_propose_ahead``), and
        never for a scheduler that speculates (the accept walk decides the
        next chunk), samples on the host, hands sequences off at retire
        (``on_finish``) or caches prefixes (a block's digest needs the ids).
        A caller sees the round returned and nothing of the one in flight."""
        pending, self._flying = self._flying, None
        with telemetry.span("serving/round", round=pending.rnd if pending
                            else self._engine.round):
            if pending is None:
                pending = self._begin()
            if pending is None:
                return []
            if self._device_sampling and not self._spec and \
                    not self._prefix_caching and self.on_finish is None:
                self._flying = self._begin(ahead=True)
            return self.step_finish(pending)

    def step_begin(self):
        """Compose + dispatch one round WITHOUT fetching the result.

        Returns an opaque pending handle for ``step_finish`` (None when
        nothing was schedulable). The forward and on-device sampling stay
        asynchronously dispatched in between — a fleet stepping N replicas
        begins them all, then finishes them all, so the forwards run
        concurrently across submeshes instead of serializing on each
        replica's host fetch. ``step()`` is the fused single-replica form,
        and the only one that runs ahead; a round it left in flight is the
        round begun here."""
        pending, self._flying = self._flying, None
        return pending or self._begin()

    def _begin(self, ahead=False):
        """``step_begin``'s work; ``ahead``: while the round before is in
        flight, and only if ``_propose_ahead`` has a round for it."""
        tm = telemetry.get_telemetry()
        enabled = tm.enabled
        rnd = self._engine.round
        t_fwd = 0.0
        sched_tokens = prefill_tokens = 0
        was_prefilling = []
        with tm.span("serving/compose", round=rnd) as sp:
            shrunk = preempted = 0
            if ahead:
                uids, chunks, device_rows = \
                    self._propose_ahead() or ([], [], ())
            else:
                uids, chunks, shrunk, preempted = self._propose()
                device_rows = ()
            if enabled:
                t_fwd = _now()
            for row, uid in enumerate(uids):
                r = self._requests[uid]
                n = len(chunks[row])
                sched_tokens += n
                was_prefilling.append(r.to_prefill > 0)
                if was_prefilling[row]:
                    prefill_tokens += n
                if r.first_sched_ts == 0.0:
                    r.first_sched_ts = _now()
                    # a re-admitted request may come without its submit time
                    waited = r.first_sched_ts - r.submit_ts \
                        if r.submit_ts else 0.0
                    admit = dict(uid=uid, round=rnd,
                                 waited_us=int(waited * 1e6),
                                 prompt_tokens=len(r.prompt))
                    slot = self._engine.state_slot(uid)
                    if slot is not None:
                        admit["slot"] = slot
                    tm.span("serving/admit", **admit).end()
                    if enabled:
                        if r.submit_ts:
                            tm.record_hist("serving/queue_wait_s", waited)
                            tm.record_request_phase(uid, "queued",
                                                    r.submit_ts, waited)
                        tm.record_request_flow(uid, "prefill", tokens=n)
            short = short_row_tokens(self._kmax)
            sp.set(seqs=len(uids), prefill_tokens=prefill_tokens,
                   decode_rows=len(uids) - sum(was_prefilling),
                   long_rows=sum(len(c) > short for c in chunks),
                   shrunk=shrunk, preempted=preempted,
                   ahead=int(ahead and bool(uids)),
                   ahead_rows=len(device_rows))
        if not uids:
            return None
        reqs = [self._requests[u] for u in uids]
        if self._spec:
            # each row's LAST verify column samples at: the next stream
            # position after the chunk for decode rows (len(generated)
            # counts chunk[0], drafts follow), the first generated position
            # for prefill rows (mid-prompt rows discard their ids anyway)
            positions = [len(r.generated) + r.pos_offset if prefilling
                         else len(r.generated) + len(c) - 1 + r.pos_offset
                         for r, c, prefilling in
                         zip(reqs, chunks, was_prefilling)]
            # rows that can roll back must not commit prefix-cache blocks
            # until the accept walk ran (a rejected draft in the chain
            # cache would poison every future match)
            defer = {u for u, c in zip(uids, chunks) if len(c) > 1}
            ids = self._engine.put_verify_device(
                uids, chunks,
                temperatures=[r.temperature for r in reqs],
                top_ks=[r.top_k for r in reqs],
                top_ps=[r.top_p for r in reqs],
                seeds=[r.seed for r in reqs],
                positions=positions, k_max=self._kmax, defer_commit=defer)
            logits = None
        elif self._device_sampling:
            ids = self._engine.put_sampled_device(
                uids, chunks,
                temperatures=[r.temperature for r in reqs],
                top_ks=[r.top_k for r in reqs],
                top_ps=[r.top_p for r in reqs],
                seeds=[r.seed for r in reqs],
                positions=[len(r.generated) + r.flying_new + r.pos_offset
                           for r in reqs],
                device_rows=device_rows)
            logits = None
        else:
            logits = self._engine.put(uids, chunks)
            ids = None
        self.counts.update(
            self._engine.last_counts, rounds=1, rounds_ahead=int(ahead),
            dispatches=len(self._engine.last_batch_shapes),
            ahead_rows=len(device_rows))
        # what this round will have done once retired: a prompt's tokens,
        # and a token sampled by every row but one mid-prompt and a
        # re-admitted row's last chunk (its sample is discarded)
        news = []
        for r, c, prefilling in zip(reqs, chunks, was_prefilling):
            if prefilling:
                r.flying_prefill += len(c)
            news.append(int(not (r.to_prefill or r.generated))
                        if prefilling else 1)
            r.flying_new += news[-1]
        return _Pending(uids, chunks, ids, logits, t_fwd, was_prefilling,
                        sched_tokens, rnd, news, ahead)

    def step_finish(self, pending):
        """Fetch a dispatched round's sampled ids and retire tokens /
        finished requests. Returns uids finished this round."""
        (uids, chunks, ids, logits, t_fwd, was_prefilling, sched_tokens,
         rnd, news, ahead) = pending
        tm = telemetry.get_telemetry()
        # t_fwd == 0.0 means telemetry was off at dispatch; recording phases
        # against a zero anchor would be garbage, so the round stays dark
        enabled = tm.enabled and t_fwd > 0.0
        if ids is not None:
            # the only device sync of the round, accounted so
            # engine.host_sync_count audits the one-fetch-per-round budget
            ids = self._engine.host_fetch(ids, "scheduler/sampled_ids")
        sp = tm.span_begin("serving/retire", round=rnd)
        spec = self._spec
        if enabled:
            t_done = _now()
            fwd_dur = t_done - t_fwd
            for row, uid in enumerate(uids):
                phase = "prefill" if was_prefilling[row] else \
                    ("speculate" if spec and len(chunks[row]) > 1 else "decode")
                tm.record_request_phase(uid, phase, t_fwd, fwd_dur,
                                        tokens=len(chunks[row]))
        finished = []
        new_tokens = 0
        # per-round speculation tallies (gauges + the router EWMA)
        n_decode_rows = decode_committed = drafted = accepted = occ_cols = 0
        for row, uid in enumerate(uids):
            r = self._requests[uid]
            r.flying_new -= news[row]
            if was_prefilling[row]:
                r.flying_prefill -= len(chunks[row])
            if r.done:
                # ended while this round was in flight (an eos in the round
                # before, a cancel): it rode the round for nothing. Its id
                # is dropped; its pages went at its end, which the device's
                # in-order queue makes safe (docs/SERVING.md)
                self.counts["ahead_rows_dropped"] += ahead
                continue
            if was_prefilling[row]:
                self.prefill_tokens_executed += len(chunks[row])
                r.prefill_pos += len(chunks[row])
                if r.prefilling:
                    continue  # mid-prompt ids/logits are not a next token
                if r.generated:
                    # re-admitted row finishing its re-prefill: the stream's
                    # last committed token is already in ``generated`` (its
                    # context ends the rebuilt prompt), so the final chunk's
                    # sample would duplicate it — discard; decode resumes by
                    # feeding that token as an ordinary chunk next round
                    emitted = []
                else:
                    # final prefill chunk: the last verify column is the
                    # row's ordinary last-token sample
                    emitted = [int(ids[row, -1])] if spec else \
                        [int(ids[row]) if logits is None
                         else self._sample(r, logits[row])]
            elif spec:
                # accept walk: target column c is the token plain decode
                # would emit after chunk position c; drafts match targets
                # one position earlier, so j accepted drafts let the row
                # emit j+1 plain-stream tokens from one forward
                chunk = chunks[row]
                n_drafts = len(chunk) - 1
                n_decode_rows += 1
                occ_cols += len(chunk)
                targets = [int(t) for t in
                           ids[row, self._kmax - len(chunk):]]
                j = 0
                while j < n_drafts and int(chunk[1 + j]) == targets[j]:
                    j += 1
                drafted += n_drafts
                accepted += j
                self.speculated_tokens += n_drafts
                self.accepted_tokens += j
                self.rejected_tokens += n_drafts - j
                emitted = targets[:j + 1]
                # truncate at the row's quota and at eos — tokens past
                # either would never exist in the plain stream
                emitted = emitted[:r.max_new_tokens - len(r.generated)]
                if r.eos_token_id is not None and r.eos_token_id in emitted:
                    emitted = emitted[:emitted.index(r.eos_token_id) + 1]
                # rejected/unused tail leaves the paged cursor: the chunk
                # wrote len(chunk) KV tokens, the plain stream keeps
                # len(emitted) of them (chunk[0] + the accepted drafts;
                # emitted[-1] is next round's chunk[0], not yet in KV)
                rollback = len(chunk) - len(emitted)
                if rollback:
                    self._engine.rollback(uid, rollback)
                if n_drafts and self._prefix_caching:
                    self._engine.commit_prefix(uid)  # deferred past rollback
                decode_committed += len(emitted)
            else:
                emitted = [int(ids[row]) if logits is None
                           else self._sample(r, logits[row])]
            first = not r.generated
            r.generated.extend(emitted)
            new_tokens += len(emitted)
            if first and emitted:
                tm.span("serving/first_token", uid=uid, round=rnd).end()
            if enabled:
                if first:
                    # TTFT spans submit->first generated token; a request
                    # submitted before telemetry came on anchors at t_fwd
                    ttft = t_done - (r.submit_ts or t_fwd)
                    tm.record_hist("serving/ttft_s", ttft)
                    if r.slo_class:
                        tm.slo_observe(r.slo_class, "ttft", ttft)
                elif r.last_token_ts and emitted:
                    # the round's gap amortized over every emitted token,
                    # one hist entry per token — counts stay token-aligned
                    # and the mean reflects the speculative speedup
                    gap = (t_done - r.last_token_ts) / len(emitted)
                    for _ in emitted:
                        tm.record_hist("serving/tpot_s", gap)
                    if r.slo_class:
                        tm.slo_observe(r.slo_class, "tpot", gap,
                                       n=len(emitted))
                r.last_token_ts = t_done
            if (r.eos_token_id is not None and
                    r.eos_token_id == r.generated[-1]) or \
                    len(r.generated) >= r.max_new_tokens:
                r.done = True
                self._active -= 1
                # disaggregation hook: truthy return = ownership of the KV
                # pages and the remaining decode moved to another scheduler;
                # skip flush and terminal telemetry — the adopting side
                # records the true finish
                if self.on_finish is not None and self.on_finish(self, r):
                    continue
                self._engine.flush(uid)
                finished.append(uid)
                self._mark_finish(r, "done", rnd)
                if enabled:
                    tm.record_hist("serving/e2e_s",
                                   t_done - (r.submit_ts or t_fwd))
                    tm.serving_event("finished")
                    tm.record_request_phase(uid, "finish", t_done,
                                            new_tokens=len(r.generated))
                    tm.record_request_flow(uid, "finish", end=True)
        if spec and n_decode_rows:
            # live accept-rate EWMA feeding SLORouter.predicted_ttft: tokens
            # committed per decode row per round (>= 1 by construction)
            self._tokens_per_round_ewma = max(1.0, (
                0.9 * self._tokens_per_round_ewma
                + 0.1 * (decode_committed / n_decode_rows)))
            if enabled:
                tm.serving_gauge("serving/verify_batch_occupancy",
                                 occ_cols / (n_decode_rows * self._kmax))
                if drafted:
                    tm.serving_gauge("serving/accept_rate",
                                     accepted / drafted)
                    tm.serving_event("speculated_tokens", n=drafted)
                    if drafted - accepted:
                        tm.serving_event("rejected_tokens",
                                         n=drafted - accepted)
        if enabled:
            running = waiting = preempted = 0
            uid_set = set(uids)
            for r in self._requests.values():
                if r.done:
                    continue
                if r.preempted:
                    preempted += 1
                elif r.uid in uid_set:
                    running += 1
                else:
                    waiting += 1
            tm.serving_gauge("serving/token_budget_util",
                             sched_tokens / self._budget)
            tm.serving_gauge("serving/running", running)
            tm.serving_gauge("serving/waiting", waiting)
            tm.serving_gauge("serving/preempted", preempted)
            self._engine.sample_kv_stats()
        sp.set(new_tokens=new_tokens, finished=len(finished))
        sp.end()
        return finished

    def _sample(self, r, row_logits):
        """Per-request sampling, host-side: logits already live on the host
        (engine.put returns numpy), so numpy sampling avoids per-token eager
        device dispatches. Deterministic per (seed, position)."""
        if r.temperature == 0.0:
            return int(np.argmax(row_logits))
        logits = np.asarray(row_logits, np.float64) / r.temperature
        if r.top_k and r.top_k > 0:
            kth = np.sort(logits)[-r.top_k]
            logits = np.where(logits < kth, -1e9, logits)
        if r.top_p < 1.0:
            order = np.argsort(logits)[::-1]
            probs = np.exp(logits[order] - logits[order][0])
            probs /= probs.sum()
            cum = np.cumsum(probs)
            cutoff_idx = int(np.sum(cum < r.top_p))  # always keep the top token
            cutoff = logits[order][cutoff_idx]
            logits = np.where(logits < cutoff, -1e9, logits)
        p = np.exp(logits - logits.max())
        p /= p.sum()
        rng = np.random.default_rng(
            (r.seed << 20) + len(r.generated) + r.pos_offset)
        return int(rng.choice(len(p), p=p))

    def results(self):
        """Generated tokens so far, {uid: int32 array} — includes finished,
        cancelled, and (on a prefill replica) handed-off requests."""
        return {uid: np.asarray(r.generated, np.int32)
                for uid, r in self._requests.items()}

    def run_to_completion(self, max_rounds=10000):
        for _ in range(max_rounds):
            if not self.has_work:
                break
            self.step()
        else:
            raise RuntimeError("scheduler did not converge")
        return self.results()
