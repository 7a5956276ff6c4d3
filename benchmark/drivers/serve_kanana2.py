"""Driver for the Kanana-2 serving cells.

It builds ``Kanana2ForCausalLM`` from the configuration file's own keys (the
published ``config.json``'s, with the share of the routed experts this chip
holds: ``n_routed_experts`` counts the experts HELD, ``experts_held`` names
their range, ``n_routed_experts_published`` is the router's width) and takes
everything that is timed or compared from ``serve_mellum2.Driver``, which takes
it from ``serve.Driver``: ``_round`` with ``attn_rows`` on its span (read here by
``mla_attn_roofline.serve``), ``window``, ``release``, ``_sample``, and
``compare`` / ``control`` with the served gap's mean as a share of the int8
control's (the traffic file's ``control_without`` names the term the second
control drops: the ``k_pe`` term of the score).

``_stagger`` opens the window on the state ``serve.Driver._stagger`` ends in,
and BUILDS that state by prefill where the base driver serves its way there.
The base submits every client's first request part-way through its answer and
runs rounds until no active request is still in prefill. Under this traffic
nearly every round carries a chunk of some prompt (by its token budget), so
that moment comes after 3,461 rounds, 368 s on the chip, more than a run may
take. What it ends in is fixed by the lengths alone (``shape_seed`` 0,
``order: fixed``): ``_served_start`` counts the base driver's rounds by the
scheduler's rule and gives, a client, the request it is at and the tokens it
has emitted. Each client then submits that request with the emitted part
already in its context (random ids from the seed, as
``serve_phi4flash.Driver._stagger`` does), in ~1,000 rounds of prefill.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, traffic, weights
from benchmark.drivers import serve_mellum2


def _served_start(clients, phase, budget):
    """[(request index, tokens emitted, tokens allowed)] a client, when
    ``serve.Driver._stagger``'s loop would end, and the rounds it takes. A
    count by ``SplitFuseScheduler._compose``'s rule: every decoding request
    takes one token of the round's budget, the rest goes to the prompts in
    the order they were submitted; a request's first token comes with its
    prompt's last chunk, and a client submits its next request after the
    round its last one finished in."""
    lengths = [[(len(p), o) for p, o in queue] for queue in clients]
    live = [{"client": c, "at": 0, "prompt": queue[0][0], "pos": 0, "n": 0,
             "max_new": max(2, int(queue[0][1] * ph))}
            for c, (queue, ph) in enumerate(zip(lengths, phase))]
    rounds = 0
    while any(r["n"] == 0 for r in live):
        left = budget
        for r in live:
            if r["n"]:
                r["n"] += 1
                left -= 1
        for r in live:
            if r["pos"] < r["prompt"] and left > 0:
                take = min(left, r["prompt"] - r["pos"])
                r["pos"] += take
                left -= take
                r["n"] = int(r["pos"] == r["prompt"])
        for r in [r for r in live if r["n"] >= r["max_new"]]:
            live.remove(r)
            queue, at = lengths[r["client"]], r["at"] + 1
            prompt, max_new = queue[at % len(queue)]
            live.append({"client": r["client"], "at": at, "prompt": prompt, "pos": 0,
                         "n": 0, "max_new": max_new})
        rounds += 1
    state = sorted(live, key=lambda r: r["client"])
    return [(r["at"], r["n"], r["max_new"]) for r in state], rounds


@functools.partial(jax.jit, static_argnums=(1,))
def _layer_params(key, rows, crcs):
    """One layer's subtree, ``weights.leaf``'s values with the layer traced:
    ``rows`` are the layer's rows of the spec with their paths below
    ``layers_<l>`` (alike for every layer of a kind, so a kind compiles
    once), ``crcs`` that layer's leaf keys' folds in the rows' order."""
    def leaf(i, shape, fill, dtype, stacked):
        k = jax.random.fold_in(key, crcs[i])
        if not stacked:
            return weights._fill(k, shape, fill, dtype)
        return jax.vmap(lambda j: weights._fill(jax.random.fold_in(k, j), shape[1:], fill,
                                                dtype))(jnp.arange(shape[0]))
    return weights._nest([(path, leaf(i, *row)) for i, (path, *row) in enumerate(rows)])


def make_params(seed, spec, reference):
    """``weights.make_params``'s tree, value for value, from three programs
    (the leaves no layer owns, the dense layer, the expert layer): ONE
    program of all 178 leaves takes the chip's compiler a minute."""
    key = weights.base_key(seed)
    spec = tuple((tuple(p), tuple(s), f, d, st) for p, s, f, d, st in spec)
    layers = sorted({p[0] for p, *_ in spec if p[0].startswith("layers_")},
                    key=lambda name: int(name.split("_")[1]))
    tree = weights._make(key, tuple(row for row in spec if row[0][0] not in layers))
    for name in layers:
        rows = reference._layer_rows(spec, int(name.split("_")[1]))
        crcs = jnp.asarray([reference._crc((name,) + path) for path, *_ in rows], jnp.int32)
        tree[name] = _layer_params(key, rows, crcs)
    return tree


class Driver(serve_mellum2.Driver):
    def __init__(self, cell, seed, rec, devices=None, seconds=0.0):
        from deepspeed_tpu.inference.v2.engine_factory import build_engine
        from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
        from deepspeed_tpu.models.kanana2 import Kanana2Config, Kanana2ForCausalLM

        self.cell, self.seed, self.rec = cell, seed, rec
        self.devices = devices or jax.devices()[:1]
        cfg, mix = cell.config, cell.traffic
        self.reference = harness.load("references", cfg["reference"])
        with rec.span("setup/weights"):
            params = make_params(seed, self.reference.param_spec(cfg), self.reference)
            jax.block_until_ready(params)
        with rec.span("setup/engine"):
            first, count = self.reference.held(cfg)
            model = Kanana2ForCausalLM(Kanana2Config.from_hf(
                cfg, dtype=jnp.bfloat16, experts_held=(first, count),
                n_routed_experts=self.reference.router_width(cfg)))
            self.engine = build_engine(model, params, cfg["engine"])
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

    def _stagger(self):
        """Module docstring. A client that is through its own context
        decodes one token in every later round of set-up, so clients are
        submitted by falling count of emitted tokens, and each is given the
        tokens it will emit during the rest of set-up on top of what it has
        still to get, and that much less random context: a count of rounds
        from the token budget, last client first."""
        budget = self.cell.config["engine"]["state_manager"]["max_ragged_batch_size"]
        clients = self.load["clients"]
        start, served_rounds = _served_start(clients, self.load["phase"], budget)
        order = sorted(range(len(clients)), key=lambda c: -start[c][1])
        in_setup, after = {}, 0.0        # rounds of set-up after a client's prefill
        for decoding, c in reversed(list(enumerate(order))):
            at, emitted, _ = start[c]
            in_setup[c] = min(1 + int(after), emitted)
            context = len(clients[c][at % len(clients[c])][0]) + emitted - in_setup[c]
            after += context / max(1, budget - decoding)
        rng = np.random.default_rng([int(self.seed), 0x636F6E74])
        vocab = self.cell.config["vocab_size"]
        self.cursor = [0] * len(clients)
        for c in order:
            at, emitted, max_new = start[c]
            prompt = clients[c][at % len(clients[c])][0]
            self.cursor[c] = at + 1
            before = rng.integers(0, vocab, emitted - in_setup[c], dtype=np.int32)
            self._submit(np.concatenate([prompt, before]), max_new - emitted + in_setup[c],
                         time.perf_counter(), client=c)
        while any(r["n"] == 0 for r in self.active.values()):
            self._round()
        print(json.dumps({"staggered_start": {
            "rounds_the_base_driver_would_serve": served_rounds,
            "rounds": len(self.rec.named("round")), **self._state()}}), flush=True)

    def _state(self):
        """What the active requests hold now: for the start's line and the
        window's, to be laid side by side."""
        reqs = self.sched._requests
        decoding = [u for u in self.active if reqs[u].prefill_pos == len(reqs[u].prompt)]
        stats = self.sched.kv_stats()
        return {"decoding": len(decoding), "in_prefill": len(self.active) - len(decoding),
                "tokens_in_context": sum(reqs[u].prefill_pos + len(reqs[u].generated)
                                         for u in self.active),
                "latent_pages": stats["occupied_blocks"], "preempted": stats["swap_outs"]}

    def window(self, seconds, out_dir):
        """``serve.Driver.window``; beside its facts a line on what the
        window held: decode rows a round, the tokens under them and the
        prompt tokens in each third of its rounds, and the state it ended
        in, to lay beside the start's."""
        before = len(self.rec.named("round"))
        facts = super().window(seconds, out_dir)
        rounds = [attrs for _, _, _, attrs in self.rec.named("round")[before:]]
        thirds = [rounds[i * len(rounds) // 3:(i + 1) * len(rounds) // 3] for i in range(3)]
        mean = lambda part, key: round(sum(a[key] for a in part) / max(len(part), 1), 1)
        print(json.dumps({"window_state": {
            "rounds": len(rounds),
            "rounds_without_prompt_tokens": sum(a["prefill_tokens"] == 0 for a in rounds),
            **{f"{key}_by_third": [mean(part, key) for part in thirds]
               for key in ("decode_rows", "context_tokens", "prefill_tokens")},
            "at_end": self._state()}}), flush=True)
        return facts

    # -- correct ------------------------------------------------------------------

    def _checks(self, controls):
        """{"served" or a control: [(name, value, limit)]} as
        ``serve_mellum2.Driver._checks`` has them, but for ``served_gap.max``:
        one token's gap is decided by one router near-tie and reads the same
        under the int8 control (PERF.md section 2), so it is printed and not
        compared. The mean and its share of the int8 control's mean over the
        SAME tokens decide."""
        mix = self.cell.traffic
        sample = self._sample()
        if sample is None:
            return {}
        got = self.reference.gaps(
            self.cell.config, self.seed, [p for p, _ in sample], [o for _, o in sample],
            mix["check_pad_to"], mix["check_max_new"], ("int8",) + tuple(controls))
        print(f"compared {len(got['served'])} served tokens of {len(sample)} requests "
              f"(longest {max(len(p) + len(o) for p, o in sample)} tokens); served_gap.max, "
              f"not compared: " + ", ".join(f"{k} {max(g):.6g}" for k, g in got.items()),
              flush=True)
        int8 = float(np.mean(got["int8"]))
        return {name: [
            ("served_gap.mean", float(np.mean(g)), self.cell.limit("served_gap_mean")),
            ("served_gap.mean_vs_int8", float(np.mean(g)) / int8 if int8 else float("inf"),
             self.cell.limit("served_gap_mean_vs_int8"))] for name, g in got.items()}
