"""Driver for the Keye-VL-2.0 serving cells.

It builds ``KeyeVL2ForCausalLM`` from the configuration file's own keys (the
published ``config.json``'s, with the share of the experts this chip holds:
``num_experts`` counts the experts HELD, ``experts_held`` names their range,
``num_experts_published`` is the router's width) and takes everything that is
timed or compared from ``serve_kanana2.Driver``: the weights a layer at a time
(``make_params``), ``_stagger`` (the window opens on the state
``serve.Driver._stagger``'s loop ends in, built by prefill), ``_round`` with
``attn_rows`` on its span (read here by the ``dsa_*`` metrics), ``window``,
``release`` and ``_sample``.

``_checks`` compares three numbers. A near-tie that the program's precision
decides otherwise than float32 (the router's 8th against its 9th expert in
~1.2 % of (token, layer) pairs; a few of the 2,048 selected tokens at the
selection's boundary in nearly every one) moves ONE token's gap by up to
several units whatever the precision, as often under the int8 control as in
the program, and such tokens carry half of the plain mean. So each token's gap
is also counted at most ``check_gap_cap`` (the traffic file's; 0.1):
``served_gap.capped_mean`` says how OFTEN and by how little the served tokens
leave the reference's best, which is what precision moves, and
``served_gap.capped_mean_vs_int8`` is its share of the int8 control's over the
same tokens. ``served_gap.mean`` stays as a coarse guard for what is rare and
large. The largest gap is printed and not compared.

``control`` gives the int8 control's readings and those of the float32 forward
with each term of the traffic file's ``control_without`` left out (the
selection: dense attention; the indexer: a window of the last ``topk`` tokens
in the selection's place), under the names ``without_<term>.served_gap.*``:
every one of them has to come out as not correct.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, traffic
from benchmark.drivers import serve_kanana2


class Driver(serve_kanana2.Driver):
    def __init__(self, cell, seed, rec, devices=None, seconds=0.0):
        from deepspeed_tpu.inference.v2.engine_factory import build_engine
        from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
        from deepspeed_tpu.models.keye_vl2 import KeyeVL2Config, KeyeVL2ForCausalLM

        self.cell, self.seed, self.rec = cell, seed, rec
        self.devices = devices or jax.devices()[:1]
        cfg, mix = cell.config, cell.traffic
        self.reference = harness.load("references", cfg["reference"])
        with rec.span("setup/weights"):
            params = serve_kanana2.make_params(
                seed, self.reference.param_spec(cfg), self.reference)
            jax.block_until_ready(params)
        with rec.span("setup/engine"):
            model = KeyeVL2ForCausalLM(KeyeVL2Config.from_hf(
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

    def _state(self):
        state = super()._state()
        state["pages"] = state.pop("latent_pages")      # K, V and index keys
        return state

    def _checks(self, controls):
        """{"served" or a control: [(name, value, limit)]} (module
        docstring); a control's numbers are those of the token it puts first."""
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
        capped = {k: float(np.mean(np.minimum(g, mix["check_gap_cap"]))) for k, g in got.items()}
        return {name: [
            ("served_gap.mean", float(np.mean(g)), self.cell.limit("served_gap_mean")),
            ("served_gap.capped_mean", capped[name], self.cell.limit("served_gap_capped_mean")),
            ("served_gap.capped_mean_vs_int8",
             capped[name] / capped["int8"] if capped["int8"] else float("inf"),
             self.cell.limit("served_gap_capped_mean_vs_int8"))] for name, g in got.items()}

    def control(self):
        terms = self.cell.traffic["control_without"]
        got = self._checks(tuple(f"without:{term}" for term in terms))
        if not got:
            return []
        return got["int8"] + [(f"without_{term}.{name}", value, limit)
                              for term in terms
                              for name, value, limit in got[f"without:{term}"]]
