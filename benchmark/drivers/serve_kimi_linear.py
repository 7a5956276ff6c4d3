"""Driver for the Kimi-Linear serving cells.

It builds ``KimiLinearForCausalLM`` from the configuration file's own keys (the
published ``config.json``'s, with the share of the routed experts this chip
holds: ``num_experts`` counts the experts HELD, ``experts_held`` names their
range, ``num_experts_published`` is the router's width) and takes everything
that is timed or compared from ``serve_kanana2.Driver``: the weights a layer
at a time (``make_params``; then the reference's ``finish`` maps the decay's
two leaves from their raw draws), ``_round`` with ``attn_rows`` on its span,
``window``, ``release``, ``_sample``, ``_checks`` (the served gap's mean and
its share of the int8 control's), ``compare`` and ``control`` (the traffic
file's ``control_without`` names what the second control changes: the decay).

``_stagger`` is ``serve_phi4flash.Driver``'s: each client starts part-way
through its first answer with the part already emitted IN its context (random
ids, prefilled after the prompt through the scheduler's chunked path), so the
state the window opens on (64 KDA slots and some 350 k tokens of latent pages)
is built by prefill in set-up, not served to.

What it adds: the program's device counters (``engine.device_counters()``:
rows routed, rows that landed on a held expert, held experts hit) read when
the window opens and when it has closed, outside every round, their difference
into ``facts``.
"""

import json

import jax
import jax.numpy as jnp

from benchmark import harness, traffic
from benchmark.drivers import serve_kanana2, serve_phi4flash


class Driver(serve_kanana2.Driver):
    def __init__(self, cell, seed, rec, devices=None, seconds=0.0):
        # first, so that a tree without the family fails before any weight is made
        from deepspeed_tpu.models.kimi_linear import (
            KimiLinearConfig, KimiLinearForCausalLM)
        from deepspeed_tpu.inference.v2.engine_factory import build_engine
        from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler

        self.cell, self.seed, self.rec = cell, seed, rec
        self.devices = devices or jax.devices()[:1]
        cfg, mix = cell.config, cell.traffic
        self.reference = harness.load("references", cfg["reference"])
        with rec.span("setup/weights"):
            params = self.reference.finish(serve_kanana2.make_params(
                seed, self.reference.param_spec(cfg), self.reference))
            jax.block_until_ready(params)
        with rec.span("setup/engine"):
            model = KimiLinearForCausalLM(KimiLinearConfig.from_hf(
                cfg, dtype=jnp.bfloat16, experts_held=self.reference.held(cfg),
                num_experts=self.reference.router_width(cfg)))
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
                print(json.dumps({"staggered_start": {
                    "rounds": len(self.rec.named("round")), **self._state()}}), flush=True)

    _stagger = serve_phi4flash.Driver._stagger

    def window(self, seconds, out_dir):
        """``serve_kanana2.Driver.window``; beside its facts the device
        counters' counts of the window (module docstring) and a line of them."""
        before = self.engine.device_counters()
        facts = super().window(seconds, out_dir)
        after = self.engine.device_counters()
        counts = {k: after[k] - before[k] for k in after}
        routed = max(counts.get("routed_rows", 0), 1)
        print(json.dumps({"device_counters": {
            **counts, "held_rows_share": counts.get("held_rows", 0) / routed}}), flush=True)
        return dict(facts, device_counters=counts)
