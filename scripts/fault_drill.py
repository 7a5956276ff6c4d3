"""End-to-end fault drill (docs/RESILIENCE.md) — kill/resume on CPU.

Four drills, each exercising a real process boundary (SIGKILL/SIGTERM on a
live training subprocess), pinning the acceptance behaviors the unit suite
(tests/test_resilience.py) checks in-process:

1. ``kill-async-save``  SIGKILL the trainer while an async checkpoint
   worker is inside the publish window (held open by a ``ckpt.publish``
   sleep fault). The live tag must remain loadable — the atomic
   tmp+rename publish means a crash at ANY instant leaves a complete tag.
2. ``bitflip``          flip one byte in the newest tag's array shard; the
   checksum manifest must catch it, quarantine the tag, and the load must
   transparently fall back to the prior tag (and repair ``latest``).
3. ``preemption``       real SIGTERM to a training process with the
   preemption handler enabled: it writes an emergency checkpoint at the
   next step boundary and exits 83 (clean preemption — budget-free for the
   elastic agent); a fresh engine then resumes from the emergency tag.
4. ``watchdog``         inject a ``step.hang`` stall into a process running
   the watchdog with ``abort`` on; the watchdog must dump stacks and
   hard-exit 85 within one heartbeat.
5. ``slice-loss``       elastic shrink under the agent: a 4-host gang loses
   its upper half mid-async-publish (SIGKILL), the survivors detect the
   slice loss, save an emergency universal checkpoint, and exit 84
   (reshardable slice loss); the elastic agent excludes the dead hosts and
   relaunches the 2 survivors budget-free, which resume from the exact
   checkpointed step — the loss trajectory continues.
6. ``replica-loss``     SERVING fleet chaos (subprocess on 8 forced CPU
   devices): a ``replica.lost`` fault kills a decode replica mid-stream;
   survivors must stay bit-exact, the dead replica's streams must re-admit
   and complete bit-exact against the fault-free run (seeded sampling
   included), and the fleet page census must show zero leaked KV pages.

``--emit-elastic-baseline PATH`` additionally runs the in-process 8→4→8
mesh reshard drill (resilience/elastic_reshard.py, 8 forced CPU devices)
and writes its payload — the checked-in
``onchip_results/elastic_drill_baseline.json`` that
``perf_gate.py --dry-run`` ratchets (``check_elastic_baseline``).

Usage:  python scripts/fault_drill.py [--drill NAME] [--keep]
Exit 0 iff every selected drill passes.
"""

import argparse
import importlib.util
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

EXIT_CLEAN_PREEMPTION = 83
EXIT_WATCHDOG_ABORT = 85

POSTMORTEM_ENV = "DS_TPU_POSTMORTEM_DIR"


def _postmortem_mod():
    """Load scripts/postmortem.py standalone (stdlib-only analyzer)."""
    spec = importlib.util.spec_from_file_location(
        "ds_tpu_postmortem", os.path.join(REPO, "scripts", "postmortem.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_bundles(pm_dir, expect, desc):
    """Forensics leg of every drill: the kill/crash left EXACTLY the
    expected postmortem bundles, each schema-valid and classified by
    scripts/postmortem.py to the drill's incident type. ``expect`` maps
    incident type -> exact bundle count."""
    pm = _postmortem_mod()
    bundles = pm.find_bundles([pm_dir])
    got = {}
    for b in bundles:
        errs = pm.validate_bundle(b)
        assert not errs, f"{desc}: malformed bundle {b}: {errs}"
        typ, evidence = pm.classify_bundle(pm.load_bundle(b))
        got[typ] = got.get(typ, 0) + 1
    assert got == expect, (f"{desc}: bundle classification {got} != "
                           f"{expect} (bundles: {bundles})")
    return bundles

# one trainer template, parameterized by the resilience config and loop
# behavior — every drill runs this as a real subprocess
TRAINER = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
import deepspeed_tpu
from tests.simple_model import SimpleModel, random_batches

out = sys.argv[1]
model = SimpleModel()
batch = random_batches(1, 8)[0]
params = model.init(jax.random.PRNGKey(0), batch)["params"]
engine, _, _, _ = deepspeed_tpu.initialize(
    model=model, model_parameters=params, config={config})
batches = random_batches(4, 8)
{body}
"""


def _write_trainer(workdir, config, body):
    p = os.path.join(workdir, "trainer.py")
    with open(p, "w") as f:
        f.write(TRAINER.format(repo=REPO, config=config,
                               body=textwrap.dedent(body)))
    return p


def _spawn(trainer, out, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.Popen([sys.executable, trainer, out], env=env)


def _wait_for(path, proc, timeout=180, desc="marker"):
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if proc.poll() is not None:
            raise AssertionError(
                f"trainer exited {proc.returncode} before {desc}")
        if time.monotonic() > deadline:
            proc.kill()
            raise AssertionError(f"timed out waiting for {desc}")
        time.sleep(0.05)


def _fresh_engine():
    import jax
    import deepspeed_tpu
    from tests.simple_model import SimpleModel, random_batches
    model = SimpleModel()
    batch = random_batches(1, 8)[0]
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    return engine


BASE_CFG = {"train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}}


# ---------------------------------------------------------------------------
# drills
# ---------------------------------------------------------------------------

def drill_kill_async_save(workdir):
    """SIGKILL mid-async-save: the publish window is held open by a sleep
    fault, the process dies inside it, and 'latest' must still load."""
    out = os.path.join(workdir, "ckpt")
    cfg = dict(BASE_CFG)
    # the async worker stalls 120s between finishing the tmp dir and the
    # atomic publish — the deterministic SIGKILL window. n2: the first
    # publish hit is the durable sync save, the second is the async worker
    cfg["resilience"] = {"faults": "ckpt.publish:n2!sleep120"}
    trainer = _write_trainer(workdir, cfg, """
        loss = engine(batches[0]); engine.backward(loss); engine.step()
        engine.save_checkpoint(out)                       # durable tag
        loss = engine(batches[1]); engine.backward(loss); engine.step()
        engine.save_checkpoint(out, async_save=True)      # stalls in publish
        import time
        time.sleep(1.0)  # let the worker reach the fault point
        open(os.path.join(out, "armed"), "w").close()
        time.sleep(600)  # parent SIGKILLs us here
    """)
    pm_dir = os.path.join(workdir, "pm")
    p = _spawn(trainer, out, extra_env={POSTMORTEM_ENV: pm_dir})
    try:
        _wait_for(os.path.join(out, "armed"), p, desc="publish-window marker")
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
    latest = os.path.join(out, "latest")
    assert os.path.exists(latest), "no 'latest' after SIGKILL"
    tag = open(latest).read().strip()
    assert tag == "global_step1", f"latest moved to unpublished tag: {tag}"
    engine = _fresh_engine()
    path, _ = engine.load_checkpoint(out)
    assert engine.global_steps == 1, engine.global_steps
    # forensics: the long publish stall flushed a "stall" bundle BEFORE the
    # SIGKILL landed — the black box survived the unflushable death
    _assert_bundles(pm_dir, {"stall": 1}, "kill-async-save")
    print(f"  latest={tag!r} loads, resumed at step {engine.global_steps}; "
          f"1 stall bundle left by the killed process")


def drill_bitflip(workdir):
    """Bit-flip in the newest tag: checksum catches it, loader quarantines
    and falls back to the prior tag, repairing 'latest'."""
    out = os.path.join(workdir, "ckpt")
    engine = _fresh_engine()
    from tests.simple_model import random_batches
    for i, b in enumerate(random_batches(2, 8)):
        loss = engine(b); engine.backward(loss); engine.step()
        engine.save_checkpoint(out)
    shard = os.path.join(out, "global_step2", "arrays.npz")
    raw = bytearray(open(shard, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(shard, "wb").write(bytes(raw))
    # this drill runs in-process: point the flight recorder at a scratch
    # destination so the quarantine path flushes a bundle here
    from deepspeed_tpu.telemetry import flightrec
    pm_dir = os.path.join(workdir, "pm")
    flightrec.reset()
    flightrec.configure(dir=pm_dir)
    try:
        path, _ = engine.load_checkpoint(out)
    finally:
        flightrec.reset()
    assert path.endswith("global_step1"), path
    assert os.path.isdir(os.path.join(out, "global_step2.corrupt"))
    assert open(os.path.join(out, "latest")).read().strip() == "global_step1"
    _assert_bundles(pm_dir, {"corrupt_ckpt": 1}, "bitflip")
    print("  bit-flip caught; fell back to global_step1; latest repaired; "
          "1 corrupt_ckpt bundle flushed at quarantine")


def drill_preemption(workdir):
    """Real SIGTERM → emergency checkpoint → exit 83 → resume."""
    out = os.path.join(workdir, "ckpt")
    cfg = dict(BASE_CFG)
    cfg["resilience"] = {"preemption": {
        "enabled": True, "save_dir": out, "tag": "emergency"}}
    trainer = _write_trainer(workdir, cfg, """
        i = 0
        while True:
            b = batches[i % 4]; i += 1
            loss = engine(b); engine.backward(loss); engine.step()
            open(os.path.join(out, "ready"), "w").close()
    """)
    os.makedirs(out, exist_ok=True)
    pm_dir = os.path.join(workdir, "pm")
    p = _spawn(trainer, out, extra_env={POSTMORTEM_ENV: pm_dir})
    try:
        _wait_for(os.path.join(out, "ready"), p, desc="first step")
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()
    assert rc == EXIT_CLEAN_PREEMPTION, f"exit {rc}, want 83"
    assert open(os.path.join(out, "latest")).read().strip() == "emergency"
    engine = _fresh_engine()
    path, _ = engine.load_checkpoint(out)
    assert path.endswith("emergency")
    _assert_bundles(pm_dir, {"preemption": 1}, "preemption")
    print(f"  SIGTERM → exit {rc}; emergency tag resumed at step "
          f"{engine.global_steps}; 1 preemption bundle")


def drill_watchdog(workdir):
    """Injected step.hang + watchdog abort: the process must self-terminate
    with exit 85 (and dump stacks) instead of wedging forever."""
    out = os.path.join(workdir, "ckpt")
    dump = os.path.join(workdir, "hang_dump.txt")
    cfg = dict(BASE_CFG)
    cfg["resilience"] = {
        "faults": "step.hang:once@step2!sleep600",
        "watchdog": {"enabled": True, "min_interval_s": 1.0,
                     "poll_interval_s": 0.2, "hang_factor": 1e-3,
                     "abort": True, "dump_file": dump},
    }
    trainer = _write_trainer(workdir, cfg, """
        for b in batches:
            loss = engine(b); engine.backward(loss); engine.step()
    """)
    os.makedirs(out, exist_ok=True)
    pm_dir = os.path.join(workdir, "pm")
    p = _spawn(trainer, out, extra_env={POSTMORTEM_ENV: pm_dir})
    try:
        rc = p.wait(timeout=180)
    finally:
        if p.poll() is None:
            p.kill()
    assert rc == EXIT_WATCHDOG_ABORT, f"exit {rc}, want 85"
    assert os.path.exists(dump), "watchdog wrote no stack dump"
    report = open(dump).read()
    assert "no step progress" in report and "--- thread" in report
    # the injected long stall flushes first; the watchdog's own flush is
    # then skipped by the one-bundle-per-process guard → exactly one
    # artifact, classified stall
    _assert_bundles(pm_dir, {"stall": 1}, "watchdog")
    print(f"  hang flagged; aborted with exit {rc}; stack dump "
          f"({len(report)} bytes) written; 1 stall bundle")


# per-"host" worker for the slice-loss drill: rank/world come from the
# elastic agent's env contract; DS_ELASTIC_RESHARD_COUNT tells a worker
# which gang generation it belongs to
SLICE_WORKER = """
import json, os, signal, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
import deepspeed_tpu
from deepspeed_tpu.checkpoint.universal import (latest_universal_tag,
                                                load_universal_checkpoint,
                                                save_universal_checkpoint)
from deepspeed_tpu.resilience import faults
from tests.simple_model import SimpleModel, random_batches

out = sys.argv[1]
rank = int(os.environ["RANK"])
world = int(os.environ["DS_ELASTIC_WORLD_SIZE"])
gen = int(os.environ.get("DS_ELASTIC_RESHARD_COUNT", "0"))
ckpt = os.path.join(out, f"rank{{rank}}")
cfg = {{"train_batch_size": 8,
        "optimizer": {{"type": "Adam", "params": {{"lr": 1e-2}}}},
        "resilience": {{"elastic": {{"enabled": True, "save_dir": ckpt}}}}}}
model = SimpleModel()
batches = random_batches(4, 8)
params = model.init(jax.random.PRNGKey(0), batches[0])["params"]
engine, _, _, _ = deepspeed_tpu.initialize(
    model=model, model_parameters=params, config=cfg)

if gen == 0:
    losses = {{}}
    for i in range(2):  # steps 0, 1 commit with a durable tag each
        loss = engine(batches[i]); engine.backward(loss); engine.step()
        losses[i] = float(loss)
        save_universal_checkpoint(engine, ckpt, tag=f"ustep{{engine.global_steps}}")
    loss = engine(batches[2])  # step 2's forward — the step the slice kills
    losses[2] = float(loss)
    engine.backward(loss)
    with open(os.path.join(out, f"gen0_rank{{rank}}.json"), "w") as f:
        json.dump({{"losses": losses, "world": world}}, f)
    if rank >= world // 2:
        # the dying half: SIGKILL mid-async-publish (the publish window is
        # held open by a sleep fault) — exactly how a slice disappears
        faults.configure("ckpt.publish:once!sleep120")
        engine.save_checkpoint(os.path.join(out, f"async{{rank}}"),
                               async_save=True)
        time.sleep(0.5)  # let the worker thread reach the publish stall
        os.kill(os.getpid(), signal.SIGKILL)
    # the surviving half detects the loss mid-step: slice.lost fires before
    # the apply, the engine emergency-saves and exits 84
    time.sleep(1.0)  # let the upper half die first
    faults.configure("slice.lost:once")
    engine.step()
    sys.exit(97)  # unreachable: step() must SystemExit(84)

# gen 1: the survivors' gang at half world — resume and continue
tag = latest_universal_tag(ckpt)
assert tag == "ustep2", f"latest tag {{tag}} != ustep2"
load_universal_checkpoint(engine, os.path.join(ckpt, tag))
assert engine.global_steps == 2, engine.global_steps
with open(os.path.join(out, f"gen0_rank{{rank}}.json")) as f:
    gen0 = json.load(f)
losses = {{}}
for i in range(2, 4):  # replay step 2 (never applied), continue through 3
    loss = engine(batches[i]); engine.backward(loss); engine.step()
    losses[i] = float(loss)
with open(os.path.join(out, f"gen1_rank{{rank}}.json"), "w") as f:
    json.dump({{"losses": losses, "world": world, "resumed_at": 2,
               "gen0_loss2": gen0["losses"]["2"]}}, f)
sys.exit(0)
"""


def drill_slice_loss(workdir):
    """SIGKILL half the simulated hosts mid-async-publish; the elastic
    agent must classify the survivors' exit-84, exclude the dead hosts,
    relaunch at half world budget-free, and the relaunched gang must resume
    from the exact checkpointed step with the loss trajectory continuing."""
    import json
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
    from deepspeed_tpu.utils.retry import BackoffPolicy
    out = os.path.join(workdir, "gang")
    os.makedirs(out, exist_ok=True)
    worker = os.path.join(workdir, "slice_worker.py")
    with open(worker, "w") as f:
        f.write(SLICE_WORKER.format(repo=REPO))
    agent = DSElasticAgent(worker, user_args=[out], hosts=["localhost"] * 4,
                           max_restarts=1,
                           backoff=BackoffPolicy(base=0.05, factor=1.0,
                                                 max_delay=0.05,
                                                 jitter="none"))
    # elastic-agent workers inherit os.environ: deliver the bundle
    # destination to every gang member through it
    pm_dir = os.path.join(workdir, "pm")
    os.environ[POSTMORTEM_ENV] = pm_dir
    try:
        rc = agent.run()
    finally:
        os.environ.pop(POSTMORTEM_ENV, None)
    assert rc == 0, f"agent exited {rc}"
    assert agent.world_history == [4, 2], agent.world_history
    assert agent.restart_counts["reshard"] == 1, dict(agent.restart_counts)
    assert agent.reshards == 1 and agent.restarts == 0, (
        agent.reshards, agent.restarts)
    for rank in (0, 1):
        with open(os.path.join(out, f"gen1_rank{rank}.json")) as f:
            g1 = json.load(f)
        assert g1["world"] == 2 and g1["resumed_at"] == 2
        # the replayed step-2 forward after restore matches the loss the
        # first gang computed before dying — the trajectory continued
        assert g1["losses"]["2"] == g1["gen0_loss2"], (
            g1["losses"]["2"], g1["gen0_loss2"])
    # forensics: the SIGKILLed half each flushed a stall bundle from the
    # held-open publish window; the surviving half each flushed a
    # slice_loss bundle on the exit-84 path. Gen-1 exits clean → no more.
    _assert_bundles(pm_dir, {"stall": 2, "slice_loss": 2}, "slice-loss")
    print(f"  4-host gang lost its upper half; agent relaunched 2 "
          f"survivors budget-free (reasons={agent.restart_reasons}); "
          f"resumed at step 2 with bitwise loss continuity")


# drill 6 worker: serving-fleet decode replica loss mid-stream, run as a
# real subprocess on 8 forced CPU host devices (the fleet needs one device
# per replica; the flag must land before jax first initializes). Runs the
# SAME seeded sampled trace fault-free then with ``replica.lost:n3@step3``
# (third hit at step 3 = decode0, with 2 prefill replicas ahead of it) and
# writes a JSON verdict for the parent. @REPO@ is substituted at write time.
REPLICA_LOSS_WORKER = '''
import json, os, sys
sys.path.insert(0, @REPO@)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
import numpy as np
import jax
from deepspeed_tpu.inference.v2.fleet import PrefillDecodeFleet
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.resilience import faults

out_path = sys.argv[1]
cfg = LlamaConfig.tiny(remat=False)
model = LlamaForCausalLM(cfg)
ids = np.random.default_rng(0).integers(
    0, cfg.vocab_size, (1, 8)).astype(np.int32)
params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
ENG = {"state_manager": {"max_ragged_sequence_count": 9,
                         "max_ragged_batch_size": 64,
                         "max_context": 96,
                         "num_kv_blocks": 96},
       "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}}
MAX_NEW = 6

def requests():
    rng = np.random.default_rng(5)
    out = {}
    for uid in range(6):
        prompt = rng.integers(0, cfg.vocab_size,
                              int(rng.integers(6, 60))).astype(np.int32)
        # seeded non-greedy sampling: recovery must preserve the
        # deterministic (seed, position) sampling contract, not just argmax
        out[uid] = (prompt, dict(max_new_tokens=MAX_NEW, seed=100 + uid,
                                 temperature=0.8, top_k=20, top_p=0.95))
    return out

def run(chaos):
    faults.reset()
    fleet = PrefillDecodeFleet(model, params, prefill_replicas=2,
                               decode_replicas=2, engine_config=ENG,
                               token_budget=48)
    for uid, (p, kw) in requests().items():
        fleet.submit(uid, p, **kw)
    if chaos:
        faults.configure(chaos)
    out = fleet.run_to_completion()
    faults.reset()
    return fleet, {u: [int(t) for t in v] for u, v in out.items()}

_, ref = run(None)
fleet, got = run("replica.lost:n3@step3")
readmitted_uids = sorted(fleet._readmit_prefix)
verdict = {
    "replica_losses": fleet.replica_losses,
    "readmitted": fleet.readmitted,
    "readmitted_uids": readmitted_uids,
    "bit_exact": all(got.get(u) == ref[u] for u in ref),
    "all_complete": sorted(got) == sorted(ref)
    and all(len(v) == MAX_NEW for v in got.values()),
    "leaked_pages": fleet.page_census()["leaked_pages"],
    "dead_replicas": fleet.lifecycle.counts()["dead"],
}
with open(out_path, "w") as f:
    json.dump(verdict, f)
'''


def drill_replica_loss(workdir):
    """Decode replica loss mid-stream on a live serving fleet: the failure
    path must re-admit the dead replica's streams and finish them BIT-EXACT
    against the fault-free run (seeded sampling included), leave survivors
    untouched, and leak zero KV pages."""
    import json
    worker = os.path.join(workdir, "replica_loss_worker.py")
    with open(worker, "w") as f:
        f.write(REPLICA_LOSS_WORKER.replace("@REPO@", repr(REPO)))
    verdict_path = os.path.join(workdir, "verdict.json")
    pm_dir = os.path.join(workdir, "pm")
    p = _spawn(worker, verdict_path, extra_env={POSTMORTEM_ENV: pm_dir})
    try:
        rc = p.wait(timeout=420)
    finally:
        if p.poll() is None:
            p.kill()
    assert rc == 0, f"worker exited {rc}"
    with open(verdict_path) as f:
        v = json.load(f)
    assert v["replica_losses"] == 1, v
    assert v["readmitted"] > 0, f"loss fired but nothing re-admitted: {v}"
    assert v["bit_exact"], f"recovery diverged from fault-free run: {v}"
    assert v["all_complete"], f"re-admitted streams incomplete: {v}"
    assert v["leaked_pages"] == 0, f"KV pages leaked: {v}"
    _assert_bundles(pm_dir, {"replica_loss": 1}, "replica-loss")
    print(f"  decode replica lost mid-stream; {v['readmitted']} request(s) "
          f"re-admitted (uids {v['readmitted_uids']}); all 6 streams "
          f"bit-exact vs fault-free; 0 pages leaked; 1 replica_loss bundle")


DRILLS = {
    "kill-async-save": drill_kill_async_save,
    "bitflip": drill_bitflip,
    "preemption": drill_preemption,
    "watchdog": drill_watchdog,
    "slice-loss": drill_slice_loss,
    "replica-loss": drill_replica_loss,
}


def emit_elastic_baseline(path):
    """Run the in-process 8→4→8 mesh reshard drill and write its payload —
    the baseline ``perf_gate.py check_elastic_baseline`` ratchets."""
    import json
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    from deepspeed_tpu.resilience.elastic_reshard import (
        RESTORE_LOSS_MAX_ULPS, run_elastic_drill)
    workdir = tempfile.mkdtemp(prefix="elastic_baseline_")
    try:
        payload = run_elastic_drill(os.path.join(workdir, "uni"))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"elastic baseline written to {path}: "
          f"worlds={payload['world_sequence']} "
          f"steps_lost={payload['steps_lost']} "
          f"bitwise={payload['restore_loss_bitwise_equal']} "
          f"ulps={payload['restore_loss_ulps']}")
    ok = (payload["world_sequence"] == [8, 4, 8]
          and payload["steps_lost"] == 0
          and payload["restore_loss_bitwise_equal"]
          and max(payload["restore_loss_ulps"].values())
          <= RESTORE_LOSS_MAX_ULPS)
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--drill", choices=sorted(DRILLS), default=None,
                    help="run one drill (default: all)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch directories for inspection")
    ap.add_argument("--emit-elastic-baseline", metavar="PATH", default=None,
                    help="run the in-process 8→4→8 reshard drill and write "
                         "the perf_gate elastic baseline payload, then exit")
    args = ap.parse_args(argv)
    if args.emit_elastic_baseline:
        return emit_elastic_baseline(args.emit_elastic_baseline)
    names = [args.drill] if args.drill else list(DRILLS)
    failures = []
    for name in names:
        workdir = tempfile.mkdtemp(prefix=f"fault_drill_{name}_")
        print(f"drill {name} ({workdir})")
        t0 = time.monotonic()
        try:
            DRILLS[name](workdir)
            print(f"  PASS ({time.monotonic() - t0:.1f}s)")
        except Exception as e:
            failures.append(name)
            print(f"  FAIL: {type(e).__name__}: {e}")
        finally:
            if not args.keep:
                shutil.rmtree(workdir, ignore_errors=True)
    if failures:
        print(f"fault drill FAILED: {failures}")
        return 1
    print(f"fault drill: all {len(names)} drills passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
