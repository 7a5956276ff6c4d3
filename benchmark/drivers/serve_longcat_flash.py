"""Driver for the LongCat-Flash-Chat serving cells.

It builds ``LongcatFlashForCausalLM`` from the configuration file's own keys
(the published ``config.json``'s, with the share of the real experts this chip
holds: ``n_routed_experts`` counts the experts HELD, ``experts_held`` names
their range, ``n_routed_experts_published`` is their published count; the
router's width is that plus ``zero_expert_num``) and takes everything that is
timed or compared from ``serve_kanana2.Driver``: the weights a layer at a time
(``make_params``), ``_stagger`` (the window opens on the state
``serve.Driver._stagger``'s loop ends in, built by prefill), ``_round`` with
``attn_rows`` on its span, ``window``, ``release``, ``_sample``, ``_checks``
(the served gap's mean and its share of the int8 control's), ``compare`` and
``control`` (the traffic file's ``control_without`` names the term the second
control drops: the zero experts').

What it adds: the program's device counters (``engine.device_counters()``:
rows routed, rows that took a zero expert, rows that landed on a held expert,
held experts hit, dispatches) read when the window opens and when it has
closed, outside every round, their difference into ``facts`` for the
``scmoe_gmm_roofline.serve`` and ``zero_expert_rows_share.serve`` readers.
"""

import json

import jax
import jax.numpy as jnp

from benchmark import harness, traffic
from benchmark.drivers import serve_kanana2


class Driver(serve_kanana2.Driver):
    def __init__(self, cell, seed, rec, devices=None, seconds=0.0):
        # first, so that a tree without the family fails before any weight is made
        from deepspeed_tpu.models.longcat_flash import (
            LongcatFlashConfig, LongcatFlashForCausalLM)
        from deepspeed_tpu.inference.v2.engine_factory import build_engine
        from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler

        self.cell, self.seed, self.rec = cell, seed, rec
        self.devices = devices or jax.devices()[:1]
        cfg, mix = cell.config, cell.traffic
        self.reference = harness.load("references", cfg["reference"])
        with rec.span("setup/weights"):
            params = serve_kanana2.make_params(
                seed, self.reference.param_spec(cfg), self.reference)
            jax.block_until_ready(params)
        with rec.span("setup/engine"):
            model = LongcatFlashForCausalLM(LongcatFlashConfig.from_hf(
                cfg, dtype=jnp.bfloat16, experts_held=self.reference.held(cfg),
                n_routed_experts=self.reference.real_experts(cfg)))
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

    def window(self, seconds, out_dir):
        """``serve_kanana2.Driver.window``; beside its facts the device
        counters' counts of the window (module docstring) and a line of them."""
        before = self.engine.device_counters()
        facts = super().window(seconds, out_dir)
        after = self.engine.device_counters()
        counts = {k: after[k] - before[k] for k in after}
        routed = max(counts.get("routed_rows", 0), 1)
        print(json.dumps({"device_counters": {
            **counts,
            "zero_rows_share": counts.get("zero_rows", 0) / routed,
            "held_rows_share": counts.get("held_rows", 0) / routed,
            "experts_hit_a_layer_and_dispatch": counts.get("experts_hit", 0) / max(
                counts.get("dispatches", 0) * self.cell.config["num_layers"], 1)}}),
              flush=True)
        return dict(facts, device_counters=counts)
