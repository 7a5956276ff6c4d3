"""Driver for the Mellum2 serving cells.

``benchmark/drivers/serve.py`` builds its model by name, so this one builds
``Mellum2ForCausalLM`` from the configuration file's own keys (the published
``config.json``'s, ``rope_parameters`` and ``layer_types`` among them) and
inherits everything that is timed or compared: ``_round`` (wrapped, not
replaced), ``window``, ``_stagger``, ``release``, ``_sample``, ``compare``,
``_warm_shapes``.

``_round`` adds to its span what each row of the round asked of attention: the
new tokens it ran and the position it ended at (``attn_rows``), which
``mixed_attn_roofline.serve`` turns into the bytes and operations the window
layers and the full layers need.

``compare`` holds the served tokens' gap under the plain reference to three
limits: its max, its mean, and its mean as a share of the int8 control's mean
over the same tokens (``_checks``). ``control`` gives the int8 control's
readings (its share is 1 by construction) and, under the names
``without_<term>.served_gap.*``, those of the float32 forward with a term left
out (``control_without`` of the traffic file, ``attention_factor`` unless it
says otherwise): both have to come out as not correct.
"""

from benchmark import harness, traffic, weights
from benchmark.drivers import serve


class Driver(serve.Driver):
    def __init__(self, cell, seed, rec, devices=None, seconds=0.0):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.inference.v2.engine_factory import build_engine
        from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
        from deepspeed_tpu.models.mellum2 import Mellum2Config, Mellum2ForCausalLM

        self.cell, self.seed, self.rec = cell, seed, rec
        self.devices = devices or jax.devices()[:1]
        cfg, mix = cell.config, cell.traffic
        self.reference = harness.load("references", cfg["reference"])
        with rec.span("setup/weights"):
            params = weights.make_params(seed, self.reference.param_spec(cfg))
            jax.block_until_ready(params)
        with rec.span("setup/engine"):
            model = Mellum2ForCausalLM(Mellum2Config.from_hf(cfg, dtype=jnp.bfloat16))
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

    def _round(self):
        """``serve.Driver._round`` as it is; afterwards its span also lists,
        for every row the round ran, ``(new tokens, position it ended at)``."""
        reqs = self.sched._requests
        before = {u: (reqs[u].prefill_pos, len(reqs[u].generated)) for u in self.active}
        t = super()._round()
        rows = []
        for u, (pos0, n0) in before.items():
            r = reqs[u]
            if r.prefill_pos > pos0:                       # a chunk of the prompt
                rows.append((r.prefill_pos - pos0, r.prefill_pos))
            elif len(r.generated) > n0 and pos0 == len(r.prompt):
                rows.append((1, pos0 + n0))                # a decode row
        self.rec.spans[-1][3]["attn_rows"] = rows
        return t

    # -- correct ------------------------------------------------------------------

    def _checks(self, controls):
        """{"served" or a control: [(name, value, limit)]}: the served gap's
        max and mean as ``serve.Driver._gaps`` has them, and the mean as a
        share of the int8 control's mean over the SAME tokens
        (``served_gap.mean_vs_int8``). A router near-tie that bfloat16 decides
        otherwise than float32 (1-4 % of a sample's (token, layer) pairs) makes
        the served gap swing with the sample by 16x, the int8 control's with
        it: the share does not (PERF.md section 2)."""
        import numpy as np
        mix = self.cell.traffic
        sample = self._sample()
        if sample is None:
            return {}
        got = self.reference.gaps(
            self.cell.config, self.seed, [p for p, _ in sample], [o for _, o in sample],
            mix["check_pad_to"], mix["check_max_new"], ("int8",) + tuple(controls))
        print(f"compared {len(got['served'])} served tokens of {len(sample)} requests "
              f"(longest {max(len(p) + len(o) for p, o in sample)} tokens)", flush=True)
        int8 = float(np.mean(got["int8"]))
        return {name: [
            ("served_gap.max", float(np.max(g)), self.cell.limit("served_gap_max")),
            ("served_gap.mean", float(np.mean(g)), self.cell.limit("served_gap_mean")),
            ("served_gap.mean_vs_int8", float(np.mean(g)) / int8 if int8 else float("inf"),
             self.cell.limit("served_gap_mean_vs_int8"))] for name, g in got.items()}

    def compare(self):
        return self._checks(()).get("served", [])

    def control(self):
        term = self.cell.traffic.get("control_without", "attention_factor")
        got = self._checks((f"without:{term}",))
        if not got:
            return []
        return got["int8"] + [(f"without_{term}.{name}", value, limit)
                              for name, value, limit in got[f"without:{term}"]]
