"""Driver for serving cells: ``InferenceEngineV2`` behind
``SplitFuseScheduler``, driven by one thread that submits what is due and
calls ``step()``.

Open loop: requests arrive on the mix's schedule whatever the system does, and
each is timed from when it was DUE. Closed loop: each client sends its next
request when its last one completes; set-up starts the clients at staggered
phases of their first answer so that the window opens on steady state.
Token times are read after every round from the requests' own token lists.
"""

import gc
import json
import os
import time

import numpy as np

from benchmark import harness, tails, traffic, weights


def buckets(lo, hi):
    out, x = [], lo
    while x < hi:
        out.append(x)
        x *= 2
    return out + [hi]


class Driver:
    gaps = ()                             # set-up's rounds count none

    def __init__(self, cell, seed, rec, devices=None, seconds=0.0):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
        from deepspeed_tpu.models.mistral import MistralForCausalLM, mistral_config

        self.cell, self.seed, self.rec = cell, seed, rec
        self.devices = devices or jax.devices()[:1]
        cfg, mix = cell.config, cell.traffic
        self.reference = harness.load("references", cfg["reference"])
        with rec.span("setup/weights"):
            params = weights.make_params(seed, self.reference.param_spec(cfg))
            jax.block_until_ready(params)
        with rec.span("setup/engine"):
            model = MistralForCausalLM(mistral_config(
                dtype=jnp.bfloat16, **{k: cfg[k] for k in (
                    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
                    "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
                    "sliding_window", "rms_norm_eps", "rope_theta")}))
            self.engine = InferenceEngineV2(model, params, config=cfg["engine"])
            self.sched = SplitFuseScheduler(self.engine)
            del params
        with rec.span("setup/warm_shapes"):
            self.programs_warmed = self._warm_shapes()
        with rec.span("setup/traffic"):
            self.load = traffic.requests(mix, seed, seconds, cfg["vocab_size"])
        self.active, self.next_uid = {}, 0
        self.finished, self.failed = [], 0
        self.measuring = False            # True inside the window
        if self.load["loop"] == "closed":
            with rec.span("setup/stagger"):
                self._stagger()

    # -- set-up -------------------------------------------------------------

    def _warm_shapes(self):
        """Run every [sequence bucket, chunk bucket] batch shape the scheduler
        can compose under the engine's limits, through the scheduler's own
        device path (``put_sampled``); throwaway sequences, flushed after."""
        eng = self.engine
        sm = eng._config.state_manager
        mix = self.cell.traffic
        s_all = buckets(4, sm.max_ragged_sequence_count)
        q_all = buckets(8, sm.max_ragged_batch_size)
        n_programs = 0
        for s in mix.get("warm_seq_buckets", s_all):
            for q in mix.get("warm_chunk_buckets", q_all):
                longest = min(q, sm.max_ragged_batch_size - (s - 1))
                if longest <= q // 2 and q > 8:
                    continue          # the scheduler cannot compose this shape
                uids = list(range(900_000, 900_000 + s))
                toks = [np.zeros(longest, np.int32)] + [np.zeros(1, np.int32)] * (s - 1)
                eng.put_sampled(uids, toks, temperatures=[0.0] * s, top_ks=[0] * s,
                                top_ps=[1.0] * s, seeds=[0] * s, positions=[0] * s)
                for u in uids:
                    eng.flush(u)
                n_programs += 1
        return n_programs

    def _submit(self, prompt, max_new, due, client=None):
        uid, self.next_uid = self.next_uid, self.next_uid + 1
        req = {"uid": uid, "prompt": prompt, "max_new": max_new, "due": due,
               "client": client, "n": 0, "t_last": None, "in_window": self.measuring}
        try:
            self.sched.submit(uid, prompt, max_new_tokens=max_new)
        except ValueError:
            self.failed += 1
            return
        self.active[uid] = req

    def _stagger(self):
        """Start every client part-way through its first answer (the part
        still to come is ``phase`` of it) and run rounds until every client
        is decoding. Clients that finish meanwhile go on as in the window."""
        self.cursor = [0] * len(self.load["clients"])
        for c, (queue, phase) in enumerate(zip(self.load["clients"], self.load["phase"])):
            prompt, max_new = queue[0]
            self.cursor[c] = 1
            self._submit(prompt, max(2, int(max_new * phase)), time.perf_counter(), client=c)
        while any(r["n"] == 0 for r in self.active.values()):
            self._round()

    # -- one round ------------------------------------------------------------

    def _round(self):
        reqs = self.sched._requests
        before = {u: (reqs[u].prefill_pos, len(reqs[u].generated)) for u in self.active}
        with self.rec.span("round") as attrs:
            done = self.sched.step()
        t = time.perf_counter()
        prefill = decode_rows = context = 0
        for uid, (pos0, n0) in before.items():
            r, req = reqs[uid], self.active[uid]
            prefill += r.prefill_pos - pos0
            new = len(r.generated) - n0
            if new and pos0 == len(r.prompt):
                decode_rows += 1
                context += pos0 + n0
            for _ in range(new):
                if req["t_last"] is None:
                    if req["in_window"]:
                        self.ttft.append(t - req["due"])
                elif self.measuring:
                    self.gaps.append(t - req["t_last"])
                req["t_last"] = t
                if self.measuring:
                    self.tokens_out += 1
            req["n"] += new
        attrs.update(prefill_tokens=prefill, decode_rows=decode_rows, context_tokens=context,
                     seqs=len(before), gaps=len(self.gaps))
        for uid in done:
            req = self.active.pop(uid)
            if self.measuring and req["in_window"] is not None:
                self.finished.append((req["prompt"], np.asarray(reqs[uid].generated, np.int32)))
            if req["client"] is not None:
                self._next_of(req["client"], t)
        for uid, what in self.sched.drain_terminal():
            if what == "evicted" and uid in self.active:
                self.failed += 1
                req = self.active.pop(uid)
                if req["client"] is not None:
                    self._next_of(req["client"], t)
        return t

    def _next_of(self, client, t):
        queue, i = self.load["clients"][client], self.cursor[client]
        self.cursor[client] = i + 1
        prompt, max_new = queue[i % len(queue)]
        self._submit(prompt, max_new, t, client=client)

    # -- the window -------------------------------------------------------------

    def window(self, seconds, out_dir):
        rec = self.rec
        self.ttft, self.gaps, self.tokens_out = [], [], 0
        self.measuring = True
        for req in self.active.values():      # staggered sessions already running
            req["in_window"] = False          # their first token came in set-up
        n_started = len(self.active)
        sent_before = sum(getattr(self, "cursor", []))
        n_spans = len(rec.spans)
        no_first_token_at_close = None
        t0 = time.perf_counter()
        t = t0
        if self.load["loop"] == "open":
            pending = list(self.load["requests"])
            late = []
            mix = self.cell.traffic
            while True:
                now = time.perf_counter() - t0
                while pending and pending[0][0] <= now:
                    due, prompt, max_new = pending.pop(0)
                    late.append(now - due)
                    self._submit(prompt, max_new, t0 + due)
                if now >= seconds and not pending:
                    self.measuring = False    # gaps and tokens count inside the window only
                    waiting = any(r["n"] == 0 for r in self.active.values())
                    if no_first_token_at_close is None:
                        no_first_token_at_close = sum(r["n"] == 0 for r in self.active.values())
                    if not waiting or now >= seconds + mix["drain_first_tokens_s"]:
                        break
                if self.sched.has_work:
                    t = self._round()
                else:
                    with rec.span("idle_wait"):
                        time.sleep(min(max(pending[0][0] - now, 0.0), 0.02) if pending else 0.002)
            t_end = t0 + seconds
            attempted = len(self.load["requests"])
            # a request still without its first token has waited at least this long
            self.ttft += [time.perf_counter() - r["due"] for r in self.active.values()
                          if r["n"] == 0]
            generator_late_ms = 1e3 * max(late) if late else 0.0
        else:
            while t - t0 < seconds:
                t = self._round()
            t_end = t
            self.measuring = False
            attempted = n_started + sum(self.cursor) - sent_before
            generator_late_ms = 0.0
        elapsed = t_end - t0
        # what the tail stands on, from the window's own gaps and rounds
        # (benchmark/tails.py); computed here, once the window has closed
        rounds, gap_round, n_gaps = [], [], 0
        for name, a, b, at in rec.spans[n_spans:]:
            if name == "round" and a < t0 + seconds:
                gap_round += [len(rounds)] * (at["gaps"] - n_gaps)
                n_gaps = at["gaps"]
                rounds.append((round(b - t0, 5), round(1e3 * (b - a), 3),
                               at["prefill_tokens"], at["decode_rows"]))
        gaps_ms = [round(1e3 * g, 3) for g in self.gaps]
        # what the result's line says of the window beside its metrics
        tail = {"gaps": len(gaps_ms), **tails.describe(gaps_ms, gap_round, rounds),
                "rounds": len(rounds),
                "in_rounds_share": sum(ms for _, ms, _, _ in rounds) / 1e3 / elapsed,
                "no_first_token_at_close": no_first_token_at_close,
                "generator_late_ms": generator_late_ms}
        with open(os.path.join(out_dir, "window.json"), "w") as f:
            json.dump({"ttft_s": self.ttft, "gaps_ms_p50": 1e3 * (harness.percentile(self.gaps, 50) or 0),
                       "finished": len(self.finished), **tail,
                       "gaps_ms": gaps_ms, "gap_round": gap_round,
                       "round_fields": ["end_s", "ms", "prefill_tokens", "decode_rows"],
                       "rounds": rounds}, f)
        p = harness.percentile
        return {"ttft_p90_s": p(self.ttft, 90),
                "token_gap_p99_ms": 1e3 * p(self.gaps, 99) if self.gaps else None,
                "serve_tokens_per_s": self.tokens_out / elapsed,
                "attempted": attempted, "failed": self.failed,
                "window_s": elapsed, "finished": len(self.finished),
                "tokens_out": self.tokens_out, "gaps": len(self.gaps),
                "token_gap_p50_ms": 1e3 * p(self.gaps, 50) if self.gaps else None,
                "generator_late_ms": generator_late_ms,
                "programs_warmed": self.programs_warmed, "tail": tail}

    def release(self):
        import jax
        self.engine = self.sched = None
        self.active = {}
        gc.collect()
        jax.clear_caches()
        gc.collect()

    # -- correct ------------------------------------------------------------------

    def _sample(self):
        """The finished requests compared: the longest, and others drawn from
        the seed, ``check_requests`` in all (repeated if fewer finished)."""
        mix = self.cell.traffic
        if not self.finished:
            return None
        order = sorted(range(len(self.finished)),
                       key=lambda i: -(len(self.finished[i][0]) + len(self.finished[i][1])))
        rng = np.random.default_rng([int(self.seed), 0x636865636B])
        rest = list(rng.permutation(order[1:]))
        n = mix["check_requests"]          # a fixed count, so one compiled reference
        picks = (([order[0]] + rest) * n)[:n]
        return [self.finished[i] for i in picks]

    def _gaps(self, low_precision=None):
        mix = self.cell.traffic
        sample = self._sample()
        if sample is None:
            return []
        gaps = self.reference.served_token_gaps(
            self.cell.config, self.seed, [p for p, _ in sample], [o for _, o in sample],
            mix["check_pad_to"], mix["check_max_new"], low_precision)
        print(f"compared {len(gaps)} served tokens of {len(sample)} requests "
              f"(longest {max(len(p) + len(o) for p, o in sample)} tokens)", flush=True)
        return [("served_gap.max", float(np.max(gaps)), self.cell.limit("served_gap_max")),
                ("served_gap.mean", float(np.mean(gaps)), self.cell.limit("served_gap_mean"))]

    def compare(self):
        return self._gaps()

    def control(self):
        return self._gaps("int8")
