"""Records ``benchmark/tests/data/spans.xplane.pb`` on the chip: a few rounds
of a small serving engine behind ``SplitFuseScheduler`` and a few steps of a
small training engine inside one profiler session, under the harness's own
``bench/window`` and ``bench/round`` spans, with the Python tracer off and
without the programs' HLO. ``chiprun -- python3 -m benchmark.tests.record_spans_trace``
leaves the capture and its slimmed copy ``spans.xplane.pb`` under
``chiprun_out/``; the tests of the readers (``test_program_spans.py``) run on
that copy under ``data/``. Slimming (``slim``) keeps every event the readers
read, with its time and name, and the host events' attributes; it drops what
makes up nine tenths of a small capture: the per-operation metadata (backend
configurations, source locations), the operands in an operation's HLO text,
the device events' own statistics and the device lines no reader reads.

The sizes are the smallest the Pallas kernels take on the chip (heads of 128
and 64), not the CPU tests' tiny presets."""

import glob
import os
import re
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness, program_spans  # noqa: E402


def _fields(buf):
    """(field number, wire type, the field's whole bytes, its payload) for
    each field of one protobuf message."""
    i = 0
    while i < len(buf):
        start, key, shift = i, 0, 0
        while True:
            key |= (buf[i] & 0x7F) << shift
            shift += 7
            i += 1
            if buf[i - 1] < 0x80:
                break
        number, wire = key >> 3, key & 7
        if wire == 0:
            while buf[i] >= 0x80:
                i += 1
            i += 1
            payload = None
        elif wire == 2:
            size, shift = 0, 0
            while True:
                size |= (buf[i] & 0x7F) << shift
                shift += 7
                i += 1
                if buf[i - 1] < 0x80:
                    break
            payload = buf[i:i + size]
            i += size
        else:
            i += {1: 8, 5: 4}[wire]
            payload = None
        yield number, wire, buf[start:i], payload


def _message(number, payload):
    out, size = bytearray([number << 3 | 2]), len(payload)
    while size >= 0x80:
        out.append(size & 0x7F | 0x80)
        size >>= 7
    out.append(size)
    return bytes(out) + payload


def _rebuilt(buf, rewrite):
    """The message with each length-delimited field's payload replaced by
    ``rewrite[number](payload)`` (None drops the field)."""
    out = []
    for number, wire, whole, payload in _fields(buf):
        if wire == 2 and number in rewrite:
            new = rewrite[number](payload)
            if new is not None:
                out.append(_message(number, new))
        else:
            out.append(whole)
    return b"".join(out)


def slim(data):
    """An ``XSpace`` (tsl's xplane.proto) without XEventMetadata's
    ``metadata``, ``display_name`` and ``stats`` (fields 3, 4, 5), with an
    operation's HLO text cut behind ``name = type op(`` (what
    ``trace.short_name`` reads; the operands and attributes go), and, on
    device planes, without the events' own ``stats`` (XEvent field 4) and
    without the lines no reader reads (all but ``XLA Ops`` and ``XLA
    Modules``)."""
    drop = lambda payload: None
    name_of = lambda msg: next(pl for n, w, _, pl in _fields(msg) if n == 2 and w == 2)
    head = re.compile(rb"%?\S+ = .*?\b[a-z][\w-]*\(")
    cut = lambda text: (head.match(text) or re.match(rb".*", text, re.S)).group(0)
    entry = lambda e: _rebuilt(e, {2: lambda m: _rebuilt(m, {2: cut, 3: drop, 4: drop, 5: drop})})

    def device_line(line):
        if name_of(line) not in (b"XLA Ops", b"XLA Modules"):
            return None
        return _rebuilt(line, {4: lambda ev: _rebuilt(ev, {4: drop})})

    def plane(p):
        rewrite = {4: entry}
        if name_of(p).startswith(b"/device:"):
            rewrite[3] = device_line
        return _rebuilt(p, rewrite)
    return _rebuilt(data, {1: plane})


def main(out="chiprun_out/spans_trace"):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.models.mistral import MistralForCausalLM, mistral_config
    from deepspeed_tpu.parallel.topology import MeshTopology

    devices = harness.require_chips(1)
    rng = np.random.default_rng(0)

    cfg = mistral_config(dtype=jnp.bfloat16, vocab_size=512, hidden_size=256,
                         intermediate_size=512, num_hidden_layers=1, num_attention_heads=2,
                         num_key_value_heads=1, max_position_embeddings=512,
                         sliding_window=256)
    model = MistralForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 8, "max_ragged_batch_size": 128,
                          "max_context": 512, "num_kv_blocks": 64, "kv_dtype": "fp"},
        "kv_cache": {"block_size": 64}})
    sched = SplitFuseScheduler(engine)
    prompt = lambda n: rng.integers(0, 512, n).astype(np.int32)

    def serve(rec, first_uid):
        sched.submit(first_uid, prompt(40), max_new_tokens=4)
        sched.submit(first_uid + 1, prompt(100), max_new_tokens=3)
        while sched.has_work:
            with rec.span("round"):
                sched.step()

    trainer = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(GPT2Config(vocab_size=512, n_positions=128, n_embd=128,
                                         n_layer=1, n_head=2)),
        mesh=MeshTopology(dp=1, devices=devices),
        config={"train_micro_batch_size_per_gpu": 4, "bf16": {"enabled": True},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 1}, "fused_step": True})[0]
    ids = rng.integers(0, 512, (4, 128)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids)}

    def train(rec, steps):
        for n in range(steps):
            with rec.span("dispatch", step=n):
                loss = trainer(batch)
                trainer.backward(loss)
                trainer.step()
        with rec.span("fetch_loss"):
            float(loss)

    warm = harness.Recorder()
    serve(warm, 0)                     # every shape compiled before the session
    train(warm, 2)

    rec = harness.Recorder(annotate=True)
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False   # the programs' HLO is most of a small trace
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        with rec.span("window"):
            serve(rec, 10)
            time.sleep(0.2)            # the two programs' device work well apart
            train(rec, 3)
    finally:
        jax.profiler.stop_trace()

    path, = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))
    kept = os.path.join(os.path.dirname(out), "spans.xplane.pb")
    with open(path, "rb") as f, open(kept, "wb") as g:
        g.write(slim(f.read()))
    loaded = program_spans.load(kept)
    table = program_spans.round_table(loaded)
    print(f"{kept}: {os.path.getsize(kept)} bytes, {len(loaded['spans'])} ds/ spans, "
          f"{len(loaded['ops'])} device operations, {len(table)} rounds, "
          f"offset {program_spans.offset(table)}", flush=True)


if __name__ == "__main__":
    main()
