"""Driver for training cells: the engine's fused step, driven as a training
loop drives it.

Set-up builds ONE engine from seeded weights, takes it through its first
steps (kept for the comparison with the plain reference) and hands the same
object to the window. The window dispatches steps without waiting for any,
fetches the loss every ``loss_every``-th step, counts whole steps only and
divides their tokens by the time from the first counted dispatch to the last
counted step's completion.
"""

import gc
import json
import os
import queue
import threading
import time

import numpy as np

from benchmark import harness, weights


def make_batches(seed, traffic, vocab):
    """``pool`` distinct batches of uniform random token ids from the seed."""
    rng = np.random.default_rng([int(seed), 0x7261696E])
    return rng.integers(0, vocab, size=(traffic["pool"], traffic["batch"], traffic["seq"]),
                        dtype=np.int32)


def whole_step_rate(tokens_per_step, t_first_dispatch, done_times):
    """tokens/s over whole steps: nothing is counted against a fixed clock,
    so a window's edge cannot cut a step."""
    if not done_times:
        return None
    return tokens_per_step * len(done_times) / (done_times[-1] - t_first_dispatch)


class Driver:
    CHECK_STEPS = 3

    def __init__(self, cell, seed, rec, devices=None, seconds=0.0):
        import jax
        import jax.numpy as jnp

        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
        from deepspeed_tpu.parallel import groups
        from deepspeed_tpu.parallel.topology import MeshTopology

        self.cell, self.seed, self.rec = cell, seed, rec
        cfg, tr = cell.config, cell.traffic
        self.reference = harness.load("references", cfg["reference"])
        self.spec = self.reference.param_spec(cfg)
        devices = devices or jax.devices()[:1]
        self.devices = devices

        with rec.span("setup/weights"):
            params = weights.make_params(seed, self.spec)
            jax.block_until_ready(params)
        with rec.span("setup/engine"):
            model = GPT2LMHeadModel(GPT2Config(
                vocab_size=cfg["vocab_size"], n_positions=cfg["n_positions"],
                n_embd=cfg["n_embd"], n_layer=cfg["n_layer"], n_head=cfg["n_head"],
                layer_norm_epsilon=cfg["layer_norm_epsilon"], **cfg["model_flags"]))
            groups.reset()
            self.engine = deepspeed_tpu.initialize(
                model=model, model_parameters=params,
                mesh=MeshTopology(dp=len(devices), devices=devices),
                config={"train_micro_batch_size_per_gpu": tr["batch"] // len(devices),
                        **cfg["engine"]})[0]
            del params
        with rec.span("setup/batches"):
            self.batches_host = make_batches(seed, tr, cfg["vocab_size"])
            self.batches = [{"input_ids": jnp.asarray(b), "labels": jnp.asarray(b)}
                            for b in self.batches_host]
        self.tokens_per_step = tr["batch"] * tr["seq"]
        self.n_dispatched = 0
        b1 = cfg["optimizer_reference"]["b1"]
        norms = weights.leaf_norms
        spec = tuple(self.spec)
        with rec.span("setup/first_steps"):
            # the window's own call and feed; step 1 compiles or loads the step
            losses = [self._step()]
            jax.block_until_ready(losses[0])
            mu = _find_mu(self.engine.state.opt_state)
            grad = jax.jit(lambda mu: norms(jax.tree.map(lambda x: x / (1 - b1), mu)))(mu)
            losses += [self._step() for _ in range(self.CHECK_STEPS - 1)]
            master = self.engine.state.master or self.engine.state.params
            delta = jax.jit(lambda p, k: norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b, p, weights.full_tree(k, spec))))(
                    master, weights.base_key(seed))
            self.program = {"loss": [float(x) for x in losses],
                            "grad_norm": {k: float(v) for k, v in grad.items()},
                            "delta_norm": {k: float(v) for k, v in delta.items()}}
        with rec.span("setup/warm_steps"):
            t0 = time.perf_counter()
            last = [self._step() for _ in range(tr["warm_steps"])][-1]
            jax.block_until_ready(last)
            self.step_estimate_s = (time.perf_counter() - t0) / tr["warm_steps"]

    def _step(self):
        engine = self.engine
        batch = self.batches[self.n_dispatched % len(self.batches)]
        self.n_dispatched += 1
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        return loss

    def window(self, seconds, out_dir):
        tr, rec = self.cell.traffic, self.rec
        done, pending = [], queue.Queue()

        def watch():                      # completion times, off the dispatch path
            while True:
                loss = pending.get()
                if loss is None:
                    return
                loss.block_until_ready()
                done.append(time.perf_counter())

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        loss_every, n = tr["loss_every"], 0
        t0 = time.perf_counter()
        # stop dispatching when what is queued will finish at about `seconds`
        while True:
            queued = n - len(done)
            # a traced window is short by design; a measured one holds min_steps
            if time.perf_counter() - t0 + queued * self.step_estimate_s >= seconds \
                    and (rec.annotate or n >= tr["min_steps"]):
                break
            with rec.span("dispatch", step=n):
                loss = self._step()
            pending.put(loss)
            n += 1
            if n % loss_every == 0:
                with rec.span("fetch_loss", step=n):
                    float(loss)
        with rec.span("drain"):
            pending.put(None)
            watcher.join()
        rate = whole_step_rate(self.tokens_per_step, t0, done)
        steps = np.diff([t0] + done)
        with open(os.path.join(out_dir, "step_times.json"), "w") as f:
            json.dump({"done_s": [t - t0 for t in done], "step_s": steps.tolist()}, f)
        return {"train_tokens_per_s_per_chip": rate / len(self.devices),
                "attempted": n, "failed": 0, "steps": n,
                "window_s": done[-1] - t0,
                "tokens_per_s": rate, "seq": tr["seq"], "batch": tr["batch"],
                "step_s_median": float(np.median(steps[1:])) if n > 1 else None}

    def release(self):
        import jax
        from deepspeed_tpu.parallel import groups
        self.engine = None
        self.batches = None
        groups.reset()
        gc.collect()
        jax.clear_caches()
        gc.collect()

    def compare(self, precision="f32"):
        """[(name, value, limit)]: the program's first steps against the
        plain reference's (``precision`` "int8" gives the control's side)."""
        ref = self.reference.train_steps(
            self.cell.config, self.seed, self.batches_host[:self.CHECK_STEPS], precision,
            rows=self.cell.traffic.get("reference_rows", 2))
        return compare_runs(self.program, ref, self.cell.limit)

    def control(self):
        """The control's numbers: the int8 reference in the program's place,
        against the float32 reference."""
        cfg, tr = self.cell.config, self.cell.traffic
        batches = self.batches_host[:self.CHECK_STEPS]
        rows = tr.get("reference_rows", 2)
        ref = self.reference.train_steps(cfg, self.seed, batches, "f32", rows=rows)
        low = self.reference.train_steps(cfg, self.seed, batches, "int8", rows=rows)
        return compare_runs(low, ref, self.cell.limit)


def _find_mu(opt_state):
    """Adam's first moment inside an optax state, wherever it is nested."""
    found = []

    def walk(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for x in node:
                walk(x)
        elif hasattr(node, "inner_state"):
            walk(node.inner_state)
    walk(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer state, found {len(found)}")
    return found[0]


def leaf_gaps(got, want):
    """|norm_got - norm_want| per leaf, against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    median = float(np.median(list(want.values())))
    return {k: abs(got[k] - want[k]) / max(want[k], median) for k in want}


def compare_runs(got, ref, limit):
    out = [(f"loss_gap.step{i + 1}", abs(a - b), limit("loss_gap"))
           for i, (a, b) in enumerate(zip(got["loss"], ref["loss"]))]
    out.append(("grad_norm_gap.worst_leaf",
                max(leaf_gaps(got["grad_norm"], ref["grad_norm"]).values()),
                limit("grad_norm_gap")))
    # the median leaf's change, not the worst: softmax does not depend on the
    # key bias, so a third of c_attn/bias has a gradient of rounding noise
    # alone, which Adam scales to full-size updates (PERF.md, Findings PR 26)
    delta = leaf_gaps(got["delta_norm"], ref["delta_norm"])
    out.append(("delta_norm_gap.median_leaf", float(np.median(list(delta.values()))),
                limit("delta_norm_gap")))
    worst = max(delta, key=delta.get)
    print(f"not compared: delta_norm_gap.worst_leaf {delta[worst]:.6g} ({worst})", flush=True)
    return out
