"""FastGen-style serving engine (mirrors reference
``deepspeed/inference/v2/engine_v2.py:30``).

``put(uids, tokens)`` schedules a mixed prefill/decode ragged batch and returns
next-token logits per sequence; ``query``/``can_schedule`` expose admission
control for an external scheduler (DeepSpeed-MII's SplitFuse role);
``flush`` retires a sequence and frees its KV blocks.
"""

import collections
import dataclasses
import functools
from typing import Iterable, List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
    RaggedBatchWrapper, dispatch_rows, pack, short_row_tokens, unpack)
from deepspeed_tpu.utils.logging import logger


@dataclasses.dataclass
class SchedulingResult:
    """Admission verdict (reference ``scheduling_utils.py``)."""
    success: bool
    reason: str = "ok"


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 7), donate_argnums=(4,))
def packed_forward(forward_fn, cfg, layout, params, cache, packed, kept,
                   verify_k):
    """The program of a dispatch, for every family: a dispatch's host arrays
    arrive as the ONE int32 buffer ``ragged_wrapper.pack`` made, are sliced
    back out by its ``layout`` (tokens, lengths, positions, the tokens'
    sources, then the cache groups' tables) and go to the family's
    ``forward_fn(cfg, params, cache, tokens, q_len, seen, tables)``, its
    verify forward where ``verify_k`` is set. A row whose source is not -1
    takes its one new token from ``kept`` at that place: the id the round
    before sampled, which the host may not have fetched yet
    (``sampling.sample_rows_packed`` left it there). ``cache`` is donated;
    the family's own jit is inlined."""
    tables = unpack(layout, packed)
    tokens, q_len, seen, src = (tables.pop(n) for n in
                                ("tokens", "q_len", "seen", "src"))
    tokens = tokens.at[:, 0].set(
        jnp.where(src < 0, tokens[:, 0], kept[jnp.maximum(src, 0)]))
    extra = () if verify_k is None else (verify_k,)
    return forward_fn(cfg, params, cache, tokens, q_len, seen, tables, *extra)


class DispatchedRound(list):
    """What one round left on the device: [(rows, out)] per dispatch, ``rows``
    indexing the round's uids and ``out`` that dispatch's padded
    [S-bucket, ...] device array. ``InferenceEngineV2.host_fetch`` lands it
    as ONE array with the rows in the order the round listed them.
    ``round`` is the engine's count of the round that made it."""

    def __init__(self, round):
        super().__init__()
        self.round = round


class InferenceEngineV2:
    """Serve a model through its ragged forward over the per-sequence state
    its cache groups declare: the llama family (llama, mistral, qwen2,
    internlm trees), mixtral, the parallel block (falcon, phi-1/2) and opt
    over one paged KV cache; phi4flash over full-attention pages, window
    pages that are freed behind the window, and slots of recurrent state
    (no prefix cache, speculation or page export for it yet).

    Args:
        model: the in-tree model — provides ``config`` (and, where the
            stack is not homogeneous, ``cache_groups``).
        params: trained parameter pytree, as the model trains it: the
            engine applies the family's ``prepare_params`` to it, here and
            nowhere else (the caller's tree is left as it is).
        config: ``RaggedInferenceEngineConfig`` or dict.
        forward_fn, verify_fn, cache_groups, report_fn, prepare_fn: what
            ``engine_factory`` resolved for the family; resolved here when
            left out.
    """

    def __init__(self, model, params, config=None, forward_fn=None,
                 verify_fn=None, cache_groups=None, report_fn=None,
                 prepare_fn=None):
        if not isinstance(config, RaggedInferenceEngineConfig):
            config = RaggedInferenceEngineConfig(config or {})
        self._config = config
        self._model_config = model.config
        cfg = self._model_config
        if forward_fn is None:
            # standalone construction: infer via the factory's policy map
            from deepspeed_tpu.inference.v2.engine_factory import resolve_forward_fn
            forward_fn = resolve_forward_fn(model)
        if verify_fn is None:
            from deepspeed_tpu.inference.v2.engine_factory import resolve_verify_fn
            verify_fn = resolve_verify_fn(model)
        if cache_groups is None:
            from deepspeed_tpu.inference.v2.engine_factory import resolve_cache_groups
            cache_groups = resolve_cache_groups(model)
        if report_fn is None:
            from deepspeed_tpu.inference.v2.engine_factory import resolve_report_fn
            report_fn = resolve_report_fn(model)
        if prepare_fn is None:
            from deepspeed_tpu.inference.v2.engine_factory import resolve_prepare_fn
            prepare_fn = resolve_prepare_fn(model)
        self._params = self._prepare(prepare_fn, params, family=getattr(
            forward_fn, "__module__", "").rpartition(".")[2])
        self._ragged_forward = forward_fn
        self._verify_forward = verify_fn
        # what a dispatch reports beyond the engine's own counts is said by
        # the state manager, for the cache groups, and by the family's
        # ``dispatch_report`` where its module exports one (docs/SERVING.md)
        self._dispatch_report = report_fn
        if config.speculative.enabled and verify_fn is None:
            raise ValueError(
                "speculative.enabled requires a verify forward; "
                f"{type(cfg).__name__} has none (resolve_verify_fn)")
        self._state = DSStateManager(config, cache_groups)
        # KV host-spill transfers (prefix blocks demoted to the DRAM tier)
        # land through the SAME accounted fetch as logits/sampled ids, so
        # host_sync_count + graftlint audit them like every other boundary
        self._state.kv_cache.set_host_fetch(self.host_fetch)
        for _, cache in self._state.paged_groups.values():
            cache.set_host_fetch(self.host_fetch)
        sm = config.state_manager
        bs = self._state.kv_block_size
        self._max_blocks_per_seq = -(-sm.max_context // bs)
        self._host_sync_count = 0
        # rounds dispatched so far (one per ``put*`` call, whatever the
        # number of forwards it took): the ``round`` every span of a serving
        # round carries (the scheduler reads it before composing)
        self.round = 0
        # dispatches so far (one per forward, never reset): the ``dispatch``
        # that ``serving/build``, ``serving/dispatch`` and the spans inside
        # it carry, which ties the host's work for one dispatch to the
        # device runs it caused
        self.dispatch = 0
        # the (sequence bucket, chunk bucket, verify_k) dispatched so far:
        # the dispatch that is first of its shape (``first_seen`` on its
        # span) is this engine's guess at the one that built a program;
        # ``built`` beside it is what jax did build (telemetry/buildlog.py)
        self._shapes_seen = set()
        # the ids the last round's samplers drew, left on the device for the
        # next round's forward (``packed_forward``'s ``kept``): one place a
        # row of a round, so its length is the engine's limit whatever the
        # round's buckets; and where each uid's id lies in it
        self._kept_ids = jnp.zeros((sm.max_ragged_sequence_count,), jnp.int32)
        self._kept_at = {}
        # [sequence bucket, chunk bucket] of each dispatch of the last round
        self.last_batch_shapes = []
        # of the last round's dispatches, summed: ``real_tokens``,
        # ``padded_slots``, ``live_pages`` (pages of the "kv" group the rows'
        # contexts reach: what the paged kernel walks), ``dispatches_sorted``
        # (those with a row whose temperature is above 0: their sampler
        # sorted every row's vocabulary, ``sampling.py``) and whatever the
        # reporters said a dispatch adds
        self.last_counts = collections.Counter()
        # postmortem-bundle collector (telemetry/flightrec.py): the newest
        # engine's host-side KV pool stats ride every bundle — pure host
        # reads, so collection is safe even from an abnormal path
        from deepspeed_tpu.telemetry import flightrec
        flightrec.register_collector("engine_v2/kv_stats", self.kv_stats)
        logger.info(f"InferenceEngineV2: S<={sm.max_ragged_sequence_count} "
                    f"tokens<={sm.max_ragged_batch_size} context<={sm.max_context}")

    def _prepare(self, prepare_fn, params, family):
        """The tree as the family's forward reads it
        (``engine_factory.resolve_prepare_fn``), under the span
        ``serving/prepare_params``: ``family`` is the forward's module,
        ``leaves`` and ``bytes`` count the leaves of the prepared tree that
        the caller's tree does not hold (0 for a family without the hook)."""
        with telemetry.get_telemetry().span(
                "serving/prepare_params", family=family) as span:
            made = []
            if prepare_fn is not None:
                given = {id(leaf) for leaf in jax.tree.leaves(params)}
                params = prepare_fn(self._model_config, params)
                made = [leaf for leaf in jax.tree.leaves(params)
                        if id(leaf) not in given]
            span.set(leaves=len(made), bytes=sum(
                leaf.size * leaf.dtype.itemsize for leaf in made))
        return params

    # -- accounted host fetch (mirrors DeepSpeedEngine._host_fetch) --------
    @property
    def host_sync_count(self) -> int:
        """Device->host syncs this engine has performed. One decode round
        through the scheduler costs exactly one (the sampled-ids fetch);
        anything faster-growing is a stray sync on the hot path."""
        return self._host_sync_count

    def host_fetch(self, value, what: str):
        """THE accounted device->host boundary for serving, counted and
        attributed exactly like the training engine's ``_host_fetch``
        (``runtime/engine.py``). Every hot-path transfer funnels through
        here so ``host_sync_count`` + the ``host_sync`` telemetry counter
        audit the per-round sync budget; graftlint (GL003/GL004) flags any
        fetch that bypasses it."""
        self._host_sync_count += 1
        tm = telemetry.get_telemetry()
        if tm.enabled:
            tm.count("host_sync", what=what)
        a_round = isinstance(value, DispatchedRound)
        with tm.span("serving/fetch", what=what,
                     round=value.round if a_round else self.round - 1):
            if not a_round:
                return jax.device_get(value)
            # the dispatches' padded arrays land as they are, in one
            # transfer: slicing or concatenating them on the device would
            # compile a program per row count
            fetched = jax.device_get([out for _, out in value])
            n = sum(len(rows) for rows, _ in value)
            merged = np.empty((n,) + fetched[0].shape[1:], fetched[0].dtype)
            for (rows, _), out in zip(value, fetched):
                merged[rows] = out[:len(rows)]
            return merged

    # -- admission control (reference engine_v2.py:158-241) ----------------
    @property
    def free_blocks(self):
        return self._state.free_blocks

    # -- prefix caching (ragged/prefix_cache.py) ---------------------------
    @property
    def prefix_caching(self) -> bool:
        return self._state.prefix_cache is not None

    def match_prefix(self, uid: int, prompt_tokens) -> int:
        """Longest-cached-prefix match at sequence creation: creates the
        sequence holding the shared blocks and returns the matched token
        count (0 = miss or caching disabled). Schedulers advance their
        prefill cursor past the return value."""
        return self._state.match_prefix(uid, prompt_tokens)

    def peek_prefix(self, prompt_tokens) -> int:
        """How many prompt tokens a cached prefix would cover, WITHOUT
        creating a sequence or taking references (pure read). The fleet
        router's prefix-affinity signal: route a request to the replica
        whose cache already holds its longest chain."""
        cache = self._state.prefix_cache
        if cache is None:
            return 0
        blocks, _ = cache.lookup_chain(prompt_tokens)
        return len(blocks) * cache.block_size

    def query(self, uid: int, max_request_tokens: int,
              max_request_blocks: int) -> Tuple[int, int]:
        """How many tokens/blocks this sequence could schedule right now."""
        seq = self._state.get_sequence(uid)
        seen = seq.seen_tokens if seq else 0
        have_blocks = seq.cur_allocated_blocks if seq else 0
        bs = self._state.kv_block_size
        token_room = self._config.state_manager.max_context - seen
        block_room = have_blocks * bs - seen + min(max_request_blocks,
                                                   self.free_blocks) * bs
        return min(max_request_tokens, token_room, block_room), \
            min(max_request_blocks, self.free_blocks)

    def can_schedule(self, uids: Iterable[int],
                     lengths: Iterable[int]) -> SchedulingResult:
        uids, lengths = list(uids), list(lengths)
        sm = self._config.state_manager
        if len(set(uids)) != len(uids):
            return SchedulingResult(False, "duplicate uids in batch")
        if len(uids) > sm.max_ragged_sequence_count:
            return SchedulingResult(False, "too many sequences")
        if sum(lengths) > sm.max_ragged_batch_size:
            return SchedulingResult(False, "too many tokens")
        need, new_seqs = 0, 0
        further, new_slots = {}, 0
        has_further = self._state.has_further_groups
        for uid, n in zip(uids, lengths):
            seq = self._state.get_sequence(uid)
            seen = seq.seen_tokens if seq else 0
            if seq is not None and seq.is_swapped:
                # its KV lives in the host tier: attending would silently read
                # zeroed blocks — the caller must resume() first
                return SchedulingResult(False, f"uid {uid} is swapped out")
            if seq is None:
                new_seqs += 1
            if seen + n > sm.max_context:
                return SchedulingResult(False, f"uid {uid} exceeds max_context")
            have = seq.cur_allocated_blocks if seq else 0
            need += self._state.blocks_needed_for(seen, have, n,
                                                  self._state.kv_block_size)
            if has_further:
                for name, k in self._state.further_blocks_needed(
                        seq, seen, n).items():
                    further[name] = further.get(name, 0) + k
                new_slots += seq is None or seq.slot is None
        if self._state.n_tracked_sequences + new_seqs > sm.max_tracked_sequences:
            return SchedulingResult(False, "too many tracked sequences")
        if need > self.free_blocks:
            return SchedulingResult(False, "not enough KV blocks")
        for name, k in further.items():
            if k > self._state.paged_groups[name][1].free_blocks:
                return SchedulingResult(False, f"not enough {name} blocks")
        if self._state.slot_group is not None and \
                new_slots > self._state.free_slots:
            return SchedulingResult(False, "no free state slot")
        return SchedulingResult(True)

    def can_admit(self) -> bool:
        """Whether a sequence this engine does not track yet could take its
        first token now: a page of every paged group, a slot of state, a
        place among the tracked sequences."""
        return self.can_schedule([None], [1]).success

    def get_remaining_block_capacity(self, uid: int) -> int:
        seq = self._state.get_sequence(uid)
        if seq is None:
            return 0
        return seq.cur_allocated_blocks * self._state.kv_block_size - seq.seen_tokens

    # -- serving (reference engine_v2.py:107) ------------------------------
    def _forward_device(self, batch_uids: List[int],
                        batch_tokens: List[np.ndarray],
                        verify_k: int = None, defer_commit=(), sample=None,
                        device_rows=()):
        """Run one round's rows through the ragged forward, dispatched by
        chunk-length class (``dispatch_rows``): the rows of one new token
        together as [D, 1] (a verify round's short rows as [D, max(8, k)]),
        every other row alone as [1, C], back to back with the donated pools
        threaded from one to the next and no fetch in between. Returns
        a ``DispatchedRound`` of each dispatch's FULL padded [S-bucket, vocab]
        logits as a device array (no host transfer); ``host_fetch`` lands it
        as [len(uids), vocab].

        ``verify_k``: when set, dispatch the k-token verify forward instead
        (same trunk, JX005-pinned) and return [S-bucket, verify_k, vocab]
        logits covering the last ``verify_k`` chunk positions per row.
        ``defer_commit``: uids whose prefix-cache block commit is postponed
        (speculating rows — rejected chunk tails must be rolled back before
        any block digest is registered, or a wrong draft would poison the
        shared chain cache; the scheduler calls ``commit_prefix`` after
        accept/rollback). ``sample``: a callable dispatched on each
        dispatch's logits and rows behind its forward (the on-device
        sampler); it returns what goes in the logits' place, and how many of
        the rows sample (``sampled_rows`` on the dispatch's span: 0 where
        the sampler took the argmax and sorted nothing). ``device_rows``:
        the rows whose one new token is the id this engine's last round
        sampled for the same uid and left on the device; what
        ``batch_tokens`` holds for them is not read."""
        lengths = [len(t) for t in batch_tokens]
        verdict = self.can_schedule(batch_uids, lengths)
        if not verdict.success:
            raise RuntimeError(f"cannot schedule batch: {verdict.reason}")
        if verify_k is not None and self._verify_forward is None:
            raise RuntimeError("no verify forward for this model family")

        tm = telemetry.get_telemetry()
        rnd = self.round
        sm = self._config.state_manager
        kv = self._state.kv_cache
        caching = self._state.prefix_cache is not None
        if device_rows and caching:
            raise RuntimeError("the prefix cache digests a block's token ids: "
                               "every row's tokens have to be the host's")
        parts, self.last_batch_shapes = DispatchedRound(rnd), []
        further = self._state.has_further_groups
        counts = self.last_counts = collections.Counter()
        for rows, min_seqs, min_tokens in dispatch_rows(
                lengths, short_row_tokens(verify_k)):
            # explicit begin/end everywhere below: tracing a new batch shape
            # inside ``with`` blocks cost set-up 0.07 s a shape more on the
            # chip (PERF.md, PR 27)
            n = self.dispatch
            sp = tm.span_begin("serving/build", round=rnd, dispatch=n,
                               seqs=len(rows))
            wrapper = RaggedBatchWrapper(sm.max_ragged_sequence_count,
                                         sm.max_ragged_batch_size,
                                         self._max_blocks_per_seq,
                                         kv.trash_block)
            real_tokens = context_tokens = live_pages = 0
            seqs = []
            for i in rows:
                uid, toks = batch_uids[i], batch_tokens[i]
                seq = self._state.get_or_create_sequence(uid)
                self._state.ensure_capacity(seq, len(toks))
                seq.in_flight_tokens = len(toks)
                if caching:
                    seq.tokens.extend(int(t) for t in toks)
                real_tokens += len(toks)
                if len(toks) == 1:
                    context_tokens += seq.seen_tokens
                live_pages += -(-(seq.seen_tokens + len(toks))
                                // self._state.kv_block_size)
                wrapper.insert_sequence(
                    uid, np.asarray(toks, np.int32), seq.seen_tokens,
                    seq.kv_blocks,
                    self._kept_at[uid] if i in device_rows else -1)
                seqs.append(seq)
            arrays = wrapper.build(min_seqs, min_tokens)
            seq_bucket, chunk_bucket = arrays["tokens"].shape
            tables = {"kv": arrays.pop("block_tables")}
            if further:
                tables.update(self._state.group_tables(seqs, seq_bucket))
            self.last_batch_shapes.append((seq_bucket, chunk_bucket))
            # what the dispatch adds to the round's counts, and what rides
            # on its span alone: a reporter answers with both, and neither
            # is this method's to read
            adds = {"real_tokens": real_tokens,
                    "padded_slots": seq_bucket * chunk_bucket,
                    "live_pages": live_pages}
            rides = {"seq_bucket": seq_bucket, "chunk_bucket": chunk_bucket,
                     "context_tokens": context_tokens}
            reports = [self._state.dispatch_report(
                seqs, arrays["seen"], arrays["q_len"], chunk_bucket)]
            if self._dispatch_report is not None:
                reports.append(self._dispatch_report(
                    self._model_config, real_tokens, chunk_bucket))
            for more_adds, more_rides in reports:
                adds.update(more_adds)
                rides.update(more_rides)
            counts.update(adds)
            sp.set(**adds, **rides)
            sp.end()

            sp = tm.span_begin("serving/dispatch", round=rnd, dispatch=n)
            built = telemetry.build_count()
            part = tm.span_begin("serving/dispatch/h2d", round=rnd, dispatch=n)
            # ONE transfer a dispatch: a transfer costs the host the same
            # whatever it carries, so the arrays cross as one buffer that
            # the program slices by ``layout``. It goes to the jitted call as
            # the numpy array it is: the call's fast path moves it for a
            # third of what ``jnp.asarray`` takes first (PERF.md, PR 40)
            fields = {**arrays, **tables}   # tokens, q_len, seen, src, tables
            if len(fields) != len(arrays) + len(tables):
                raise ValueError(f"a cache group's table is named as one of "
                                 f"the batch's own arrays: {list(tables)}")
            layout, packed = pack(fields)
            part.set(arrays=1, bytes=packed.nbytes)
            part.end()
            # fwd_k/fwd_v are (int8, scale) pairs when kv_dtype="int8" — they
            # flow through the jitted forwards as pytree leaves. The cache
            # (named groups of pools, donated) is threaded from one dispatch
            # of the round to the next
            # the shape that names the program rides on the two spans a
            # program is built under: the build ledger (telemetry/buildlog.py)
            # copies the innermost open span's attributes into its record
            buckets = {"seq_bucket": seq_bucket, "chunk_bucket": chunk_bucket,
                       "verify_k": verify_k or 0}
            part = tm.span_begin("serving/dispatch/forward", round=rnd,
                                 dispatch=n, **buckets)
            out, cache = packed_forward(
                self._ragged_forward if verify_k is None
                else self._verify_forward, self._model_config, layout,
                self._params, self._state.cache_view(), packed,
                self._kept_ids, verify_k)
            self._state.cache_update(cache)
            part.end()
            programs = 1
            if sample is not None:
                part = tm.span_begin("serving/dispatch/sample", round=rnd,
                                     dispatch=n, **buckets)
                out, sampled_rows = sample(out, rows)
                part.end()
                programs = 2
                counts["dispatches_sorted"] += sampled_rows > 0
                sp.set(sampled_rows=sampled_rows)
            shape = (seq_bucket, chunk_bucket, verify_k)
            first_seen = int(shape not in self._shapes_seen)
            if first_seen:
                self._shapes_seen.add(shape)
            # ``built`` is the measurement (programs jax traced, lowered,
            # compiled or loaded during this dispatch, whatever the cause),
            # ``first_seen`` this engine's guess from its own shapes
            built = telemetry.build_count() - built
            sp.set(programs=programs, first_seen=first_seen, built=built,
                   build_ms=telemetry.build_ms(built))
            sp.end()
            self.dispatch = n + 1
            parts.append((rows, out))

            sp = tm.span_begin("serving/post_forward", round=rnd, dispatch=n)
            for i in rows:
                seq = self._state.get_sequence(batch_uids[i])
                seq.post_forward()
                if further:
                    self._state.retire_window(seq)
                if caching and batch_uids[i] not in defer_commit:
                    # register blocks as they FILL (not at flush) so
                    # concurrent requests sharing a prefix hit as early as
                    # possible
                    self._state.commit_cached_blocks(seq)
            sp.end()
        self.round = rnd + 1
        # the samplers wrote row i's id at place i (``_packed_sampler``)
        self._kept_at = {uid: i for i, uid in enumerate(batch_uids)} \
            if sample is not None and verify_k is None else {}
        return parts

    def state_slot(self, uid: int):
        """The slot of recurrent state ``uid`` holds, taken now if it has
        none (admission: the round that first schedules it has passed
        ``can_schedule``); None for a model without a slot group."""
        if self._state.slot_group is None:
            return None
        return self._state.take_slot(self._state.get_or_create_sequence(uid))

    def _packed_sampler(self, sampler, temperatures, top_ks, top_ps, seeds,
                        positions, keep=False):
        """``sample(logits, rows)``: ``sampler(logits, fparams, iparams)``
        with the five per-row parameter vectors of a dispatch's ``rows``
        packed into two host arrays of the logits' padded row count, so that
        the jit fast path moves them — per-dispatch host time, not device
        math, bounds a fleet stepping several schedulers per round — beside
        the number of ``rows`` whose temperature is above 0 (any, and the
        sampler sorts every row's vocabulary; none, and it takes the
        argmax: ``sampling.py``). ``keep``: the sampler also writes row i of
        the round's id at place i of the ids kept on the device (a fourth
        line of ``iparams``; a padded row's place is past the end)."""
        # arbitrary Python-int seeds (the host sampler accepted any) fold
        # deterministically into the int31 space PRNGKey wants
        seeds = [int(s) & 0x7FFFFFFF for s in seeds]

        def sample(logits, rows):
            s_max, n = logits.shape[0], len(rows)
            fparams = np.zeros((2, s_max), np.float32)
            fparams[0, :n] = [temperatures[i] for i in rows]
            fparams[1, :n] = [top_ps[i] for i in rows]
            iparams = np.zeros((3 + keep, s_max), np.int32)
            iparams[0, :n] = [top_ks[i] for i in rows]
            iparams[1, :n] = [seeds[i] for i in rows]
            iparams[2, :n] = [positions[i] for i in rows]
            if keep:
                iparams[3, :n] = rows
                iparams[3, n:] = len(self._kept_ids)
                ids, self._kept_ids = sampler(logits, fparams, iparams,
                                              self._kept_ids)
            else:
                ids = sampler(logits, fparams, iparams)
            return ids, int(np.count_nonzero(fparams[0] > 0.0))
        return sample

    def put(self, batch_uids: List[int],
            batch_tokens: List[np.ndarray]) -> np.ndarray:
        """Run one round; returns [len(uids), vocab] next-token logits."""
        return self.host_fetch(self._forward_device(batch_uids, batch_tokens),
                               "serving/logits")

    def put_sampled_device(self, batch_uids: List[int],
                           batch_tokens: List[np.ndarray],
                           temperatures, top_ks, top_ps, seeds,
                           positions, device_rows=()):
        """``put_sampled`` without the final host fetch: returns the
        round's dispatches as a ``DispatchedRound`` of [S-bucket] int32 ids
        on the DEVICE, leaving the forwards + samplers dispatched
        asynchronously; ``host_fetch`` lands it as [len(uids)] ids. The
        two-phase scheduler step (``step_begin``/``step_finish``) uses this
        to keep several replicas' forwards in flight at once — the fleet's
        cross-replica overlap — fetching each result only when retiring
        tokens. ``device_rows`` are the rows whose token is the id the last
        round sampled, still on the device (``_forward_device``): a
        scheduler that runs ahead dispatches a round before it has fetched
        the one before."""
        from deepspeed_tpu.inference.v2.sampling import sample_rows_packed
        # the PADDED [S-bucket] ids come back: a device-side ids[:n] would
        # compile one slice program per distinct live count (n is not
        # bucketed), a cold ~10ms stall every time a request finishes.
        # ``host_fetch`` reads rows < n on the host.
        return self._forward_device(
            batch_uids, batch_tokens, device_rows=device_rows,
            sample=self._packed_sampler(
                sample_rows_packed, temperatures, top_ks, top_ps, seeds,
                positions, keep=True))

    def put_sampled(self, batch_uids: List[int],
                    batch_tokens: List[np.ndarray],
                    temperatures, top_ks, top_ps, seeds,
                    positions) -> np.ndarray:
        """One ragged forward + ON-DEVICE sampling fused behind the same
        dispatch; returns [len(uids)] int32 token ids.

        The host never sees the logits — only 4 bytes per sequence cross the
        host boundary per decode step (vs 4*vocab for ``put``). Rows
        mid-prefill sample garbage by construction (their last-token logits
        are mid-prompt); callers discard those ids, exactly as they discarded
        the logits before. Per-row sampling params are traced, so one
        compiled program covers any greedy/sampled mix.
        """
        return self.host_fetch(self.put_sampled_device(
            batch_uids, batch_tokens, temperatures, top_ks, top_ps, seeds,
            positions), "serving/sampled_ids")

    # -- speculative decode (draft-then-verify) ----------------------------
    @property
    def verify_supported(self) -> bool:
        """Whether this engine's model family has a k-token verify forward
        (speculative decode requires it; see ``resolve_verify_fn``)."""
        return self._verify_forward is not None

    def put_verify_device(self, batch_uids: List[int],
                          batch_tokens: List[np.ndarray],
                          temperatures, top_ks, top_ps, seeds,
                          positions, k_max: int, defer_commit=()):
        """``put_sampled_device`` for a verify round: the same dispatches
        through the SAME ragged prefill kernel, but the sampler draws target
        tokens at the last ``k_max`` chunk positions per row (LAST-aligned: column
        ``k_max-1`` is each row's ordinary last-token draw). ``positions``
        gives each row's stream position for that FINAL column — column
        ``c`` is then the token plain decode would emit at stream position
        ``positions[s] - (k_max-1) + c``. Returns a ``DispatchedRound`` of
        PADDED device [S-bucket, k_max] int32 ids; the scheduler fetches once
        per round (``host_fetch``: [len(uids), k_max]) and walks each row's
        accept prefix on the host.

        ``k_max`` is static (a per-engine pow2 bucket), so one compiled
        verify program serves every round regardless of how many drafts
        each drafter actually produced. ``defer_commit`` is forwarded to
        ``_forward_device`` (see there).
        """
        from deepspeed_tpu.inference.v2.sampling import verify_rows_packed
        return self._forward_device(
            batch_uids, batch_tokens, verify_k=int(k_max),
            defer_commit=defer_commit, sample=self._packed_sampler(
                verify_rows_packed, temperatures, top_ks, top_ps, seeds,
                positions))

    def rollback(self, uid: int, n_tokens: int) -> None:
        """Roll ``uid``'s paged cursor back ``n_tokens`` (the rejected tail
        of a verify chunk): tail blocks that fall wholly past the new
        cursor are dereferenced — shared prefix blocks survive (COW
        boundary), this-round private allocations return to the pool."""
        self._state.rollback_sequence(uid, n_tokens)

    def commit_prefix(self, uid: int) -> None:
        """Run the deferred prefix-cache block commit for a speculating row
        (after accept/rollback, so only verified tokens can enter the
        chain-digest cache). No-op when caching is off."""
        if self._state.prefix_cache is not None:
            seq = self._state.get_sequence(uid)
            if seq is not None:
                self._state.commit_cached_blocks(seq)

    def flush(self, uid: int) -> None:
        """Retire a sequence, freeing its KV blocks (reference :242)."""
        self._state.flush_sequence(uid)

    # -- page transfer (prefill/decode disaggregation) ---------------------
    def export_pages(self, uid: int):
        """Detach ``uid``'s KV pages as device arrays for shipping to a
        decode replica (``KVPageTransport``); releases the local sequence."""
        return self._state.export_sequence_pages(uid)

    def import_pages(self, uid: int, handle) -> int:
        """Bind shipped KV pages into this engine's pool under fresh
        refcount-1 block ids; creates the sequence mid-stream."""
        return self._state.import_sequence_pages(uid, handle)

    def export_pages_many(self, uids, skip=None):
        """Batched ``export_pages``: one device gather covers every listed
        finished sequence (the fleet ships a whole round's handoffs as one
        transfer). ``skip`` maps uid -> leading full blocks to delta-ship
        (digest references instead of page bytes — the destination already
        holds them in its prefix cache)."""
        return self._state.export_sequences_pages(list(uids), skip=skip)

    def import_pages_many(self, handle) -> int:
        """Batched ``import_pages``; returns total pages bound."""
        return self._state.import_sequences_pages(handle)

    def sequence_block_digests(self, uids):
        """Per-uid full-block chain digests — the source half of the
        delta-shipping digest exchange (``{}`` without prefix caching)."""
        return self._state.sequence_block_digests(list(uids))

    def held_prefix_lens(self, chains):
        """Per-uid count of leading chain links this engine's prefix cache
        already holds — the destination half of the digest exchange."""
        return self._state.held_prefix_lens(chains)

    def kv_stats(self):
        """Pure host-side KV pool stats (occupancy, free blocks,
        fragmentation, swap counters) — the router's load signal. Never
        touches the device."""
        return self._state.kv_stats()

    @property
    def kv_block_size(self) -> int:
        return self._state.kv_block_size

    @property
    def kv_page_sharding(self):
        """Current placement of the KV pools — the ``device_put`` target
        ``KVPageTransport`` ships pages onto."""
        return self._state.kv_cache.k_pool.sharding

    def place_kv(self, sharding):
        """Commit the KV pools onto an explicit device/sharding
        (``BlockedKVCache.place``). Replica builders call this so pages can
        ship INTO a replica before its first forward has pinned the pools."""
        self._state.kv_cache.place(sharding)

    def warm_page_transfer(self, dst_engine, max_pages):
        """Compile the page-transfer path toward ``dst_engine`` for every
        padded bucket up to ``max_pages``. Ships trash-block rows only — no
        live KV is read and no allocator ids are held afterwards — so a
        fleet can pay the gather/device_put/scatter compiles before the
        serving clock starts."""
        import jax
        src = self._state.kv_cache
        dst = dst_engine._state.kv_cache
        b = 1
        while True:
            if b > dst.free_blocks:
                break  # a bucket the destination pool can never bind
            k, v = src.export_blocks([src.trash_block] * b)
            k = jax.device_put(k, dst_engine.kv_page_sharding)
            v = jax.device_put(v, dst_engine.kv_page_sharding)
            dst.free(dst.import_blocks(k, v, b))
            if b >= max_pages:
                break
            b *= 2

    # -- KV host swap (ZeRO-Inference KV offload; scheduler preemption) ----
    def preempt(self, uid: int) -> None:
        """Move ``uid``'s KV cache to host memory, freeing its device blocks
        for other sequences; generation state is preserved."""
        self._state.swap_out_sequence(uid)

    def resume(self, uid: int) -> None:
        """Restore a preempted sequence's KV into fresh device blocks."""
        self._state.swap_in_sequence(uid)

    def blocks_to_resume(self, uid: int) -> int:
        return self._state.blocks_to_resume(uid)

    def further_groups_fit_resume(self, uid: int) -> bool:
        """``blocks_to_resume`` counts the "kv" group; this answers for the
        window pages and the slot a preempted sequence took to the host."""
        return self._state.further_groups_fit_resume(uid)

    def device_counters(self):
        """{field: count} of the family's counter group
        (``ragged/cache_groups.py``), {} where it declares none: one fetch
        that waits for the dispatches in flight, for a caller OUTSIDE a round
        (the benchmark when its window closes); no round fetches it. Counted
        as a host sync where there is a group to fetch."""
        if self._state.counter_group is not None:
            self._host_sync_count += 1
        return self._state.device_counters()

    @property
    def swap_stats(self):
        return {"swap_outs": self._state.swap_outs,
                "swap_ins": self._state.swap_ins}

    def sample_kv_stats(self, point="step"):
        """Host-side KV pool stats (occupancy, free-list depth,
        fragmentation). Always returns the dict; records serving gauges
        when telemetry is enabled. Sync-free — block bookkeeping lives on
        the host (the ``sample_memory`` pattern)."""
        return self._state.sample_kv_stats(point=point)
