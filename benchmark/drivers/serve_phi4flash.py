"""Driver for the Phi-4-mini-flash-reasoning serving cells.

``benchmark/drivers/serve.py`` builds its model by name, so this one builds
``Phi4FlashForCausalLM`` from the configuration file's own keys and inherits
everything that is timed or compared: ``_round`` (wrapped, not replaced),
``window``, ``release``, ``_sample``, ``compare``, ``control``,
``_warm_shapes``.

The staggered start differs from ``serve.Driver``'s in what a client's
context holds when the window opens: the part of its first answer it has
already got is IN its context, as ``(1 - phase) x answer`` random tokens
prefilled after the prompt through the scheduler's chunked path, and
``phase x answer`` tokens are still to come. A reasoning trace of thousands
of tokens makes that the difference between contexts of ~300 and ~4000.
"""

import time

import numpy as np

from benchmark import harness, traffic, weights
from benchmark.drivers import serve

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
              "sliding_window", "mb_per_layer", "layer_norm_eps")


class Driver(serve.Driver):
    def __init__(self, cell, seed, rec, devices=None, seconds=0.0):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.inference.v2.engine_factory import build_engine
        from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
        from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM

        self.cell, self.seed, self.rec = cell, seed, rec
        self.devices = devices or jax.devices()[:1]
        cfg, mix = cell.config, cell.traffic
        self.reference = harness.load("references", cfg["reference"])
        with rec.span("setup/weights"):
            params = weights.make_params(seed, self.reference.param_spec(cfg))
            jax.block_until_ready(params)
        with rec.span("setup/engine"):
            model = Phi4FlashForCausalLM(Phi4FlashConfig(
                dtype=jnp.bfloat16, **{k: cfg[k] for k in MODEL_KEYS},
                **cfg.get("assumed", {}).get("sizes", {})))
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
        """Start every client with the emitted part of its first answer in
        its context (module docstring) and run rounds until every client is
        decoding. ``stagger_cap`` of the traffic file caps what the clients
        of the smallest phases have still to get, so that a traced window of
        a few seconds sees requests finish.

        The prompts here take hundreds of rounds to prefill, and a client
        that is through its own decodes one token in every later round of
        set-up. So clients are submitted by falling remainder (the scheduler
        prefills in that order), and each is given the tokens it will emit
        during the rest of set-up ON TOP of its remainder and that much less
        random context: a count of rounds from the engine's token budget and
        the contexts still to prefill. It is an estimate; what it has to get
        right is the last few clients, whose count is small."""
        mix, sm = self.cell.traffic, self.cell.config["engine"]["state_manager"]
        cap = mix.get("stagger_cap", {"clients": 0, "remaining": 0})
        rng = np.random.default_rng([int(self.seed), 0x636F6E74])
        vocab = self.cell.config["vocab_size"]
        phases = self.load["phase"]
        capped = set(np.argsort(phases)[:cap["clients"]].tolist())
        remaining = []
        for c, (queue, phase) in enumerate(zip(self.load["clients"], phases)):
            left = max(2, int(queue[0][1] * phase))
            remaining.append(min(left, cap["remaining"]) if c in capped else left)
        order = sorted(range(len(phases)), key=lambda c: -remaining[c])
        rounds_to = []                   # set-up rounds until client c is through its prefill
        for decoding, c in enumerate(order):
            prompt, answer = self.load["clients"][c][0]
            room = max(1, sm["max_ragged_batch_size"] - decoding)
            took = -(-(len(prompt) + answer - remaining[c]) // room)
            rounds_to.append((rounds_to[-1] if rounds_to else 0) + took)
        self.cursor = [0] * len(self.load["clients"])
        for c, through in zip(order, rounds_to):
            prompt, answer = self.load["clients"][c][0]
            self.cursor[c] = 1
            in_setup = min(rounds_to[-1] - through, answer - remaining[c])
            emitted = rng.integers(0, vocab, answer - remaining[c] - in_setup, dtype=np.int32)
            self._submit(np.concatenate([prompt, emitted]), remaining[c] + in_setup,
                         time.perf_counter(), client=c)
        while any(r["n"] == 0 for r in self.active.values()):
            self._round()

    def _round(self):
        """``serve.Driver._round`` as it is; afterwards its span also says
        how many of the decode rows' context tokens lie inside the window
        layers' reach (what ``hybrid_attn_roofline.decode`` counts)."""
        reqs = self.sched._requests
        before = {u: (reqs[u].prefill_pos, len(reqs[u].generated)) for u in self.active}
        t = super()._round()
        window = self.cell.config["sliding_window"]
        reach = sum(min(pos0 + n0, window) for u, (pos0, n0) in before.items()
                    if len(reqs[u].generated) > n0 and pos0 == len(reqs[u].prompt))
        self.rec.spans[-1][3]["window_context_tokens"] = reach
        return t
