"""Two-process KV fabric: prefill and decode in separate OS processes.

The in-process fleet's wire codec serializes pages and immediately parses
them back — same address space, so "the wire" is an act of discipline. This
module removes the act: the PREFILL side lives in the parent process, the
DECODE side in a spawned child, and every KV page crosses the boundary as a
``fleet/wire.py`` frame over a duplex ``multiprocessing`` Pipe (the
socket-equivalent channel — ``Connection.send_bytes`` is length-prefixed
framing over a kernel pipe). The CRC32 check therefore runs on the
RECEIVING side of a real process boundary, exactly where a cross-host DCN
deployment runs it.

Determinism gives parity: both processes derive identical weights from
``PRNGKey(0)`` (the two-process analog of loading the same checkpoint), the
sampling stream is deterministic per (seed, position), and the parent
drives the child in lockstep (one ``step`` op per parent round), so greedy
output matches the in-process fleet token for token (pinned by
tests/test_kv_fabric.py and the ``bench_serving --fleet --two-process``
leg).

A host with ONE TPU cannot run this: both processes ask for
``jax.devices()[0]`` of the same host, and a chip belongs to one process at a
time — the child would fail or hang at start-up. It runs on the CPU backend
(tests/test_kv_fabric.py) and would run with one chip per process; it is not
on ``chip_smoke.py``'s path, and ROADMAP C5 decides its fate.

Control protocol (JSON header + optional binary payload per message)::

    parent -> child                      child -> parent
    ----------------------------------   --------------------------------
    query  {chains: {uid: [hex]}}        held    {held: {uid: n}}
    ship   {adopts: [...]} + frame       ack     {bound} | nak {error,
                                                 retryable}
    readmit{meta: {...}}                 ack
    step   {}                            stepped {finished, has_work}
    results{}                            results {outputs, stats}
    shutdown{}                           bye

A retryable nak (CRC mismatch — the frame was corrupted in flight) re-sends
the SAME frame (it is intact on the parent; the corruption models the
channel); exhaustion falls back to a ``readmit`` op — re-prefill on the
decode side, the same bit-exact fallback the in-process fleet uses — so a
poisoned link degrades throughput, never correctness and never a lost
request.
"""

import json
import secrets

import numpy as np

from deepspeed_tpu.resilience import faults
from deepspeed_tpu.resilience.faults import InjectedFault
from deepspeed_tpu.utils.logging import logger

PROTOCOL_VERSION = 1


def _send(conn, header, payload=b""):
    hb = json.dumps(header).encode()
    conn.send_bytes(len(hb).to_bytes(4, "little") + hb + payload)


def _recv(conn):
    raw = conn.recv_bytes()
    hl = int.from_bytes(raw[:4], "little")
    return json.loads(raw[4:4 + hl].decode()), raw[4 + hl:]


def _build_decode_replica(model_config, engine_config, token_budget,
                          init_len):
    """Deterministic from-scratch decode replica — the child's analog of
    loading the checkpoint the parent serves. ``model_config`` is a plain
    dict of ``LlamaConfig`` fields (``dtype`` as a jnp dtype name)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.replica_group import build_replica
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    mc = dict(model_config)
    if isinstance(mc.get("dtype"), str):
        mc["dtype"] = getattr(jnp, mc["dtype"])
    model = LlamaForCausalLM(LlamaConfig(**mc))
    ids = np.zeros((1, int(init_len)), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return build_replica(model, params, [jax.devices()[0]],
                         engine_config=engine_config,
                         token_budget=token_budget)


def _adopt_kwargs(meta):
    return dict(max_new_tokens=int(meta["max_new_tokens"]),
                eos_token_id=meta["eos_token_id"],
                temperature=float(meta["temperature"]),
                top_k=int(meta["top_k"]), top_p=float(meta["top_p"]),
                seed=int(meta["seed"]), slo_class=meta.get("slo_class"))


def decode_worker_main(conn, model_config, engine_config, token_budget,
                       init_len):
    """Child process entry: serve the decode side of the fabric until a
    ``shutdown`` op. Every exception inside an op is answered as a ``nak``
    (typed by name) so the parent can distinguish the retryable CRC reject
    from a deterministic bind failure."""
    from deepspeed_tpu.inference.v2.fleet import wire
    mesh, sched = _build_decode_replica(model_config, engine_config,
                                        token_budget, init_len)
    _send(conn, {"op": "ready", "protocol": PROTOCOL_VERSION})
    while True:
        header, payload = _recv(conn)
        op = header["op"]
        if op == "shutdown":
            _send(conn, {"op": "bye"})
            return
        if op == "query":
            chains = {int(u): [bytes.fromhex(d) for d in ds]
                      for u, ds in header["chains"].items()}
            held = sched.engine.held_prefix_lens(chains)
            _send(conn, {"op": "held",
                         "held": {str(u): int(n) for u, n in held.items()}})
        elif op == "ship":
            try:
                out = wire.decode_frame(payload)
                with mesh:
                    import jax
                    sharding = sched.engine.kv_page_sharding
                    out["k"] = jax.device_put(out["k"], sharding)
                    out["v"] = jax.device_put(out["v"], sharding)
                    bound = sched.engine.import_pages_many(out)
                    for meta in header["adopts"]:
                        sched.adopt(
                            int(meta["uid"]),
                            np.asarray(meta["prompt"], np.int32),
                            [int(t) for t in meta["generated"]],
                            **_adopt_kwargs(meta))
                _send(conn, {"op": "ack", "bound": int(bound)})
            except Exception as e:  # answered, never fatal: the parent
                # retries (CRC) or falls back to a readmit (anything else)
                _send(conn, {"op": "nak",
                             "error": f"{type(e).__name__}: {e}",
                             "retryable":
                                 isinstance(e, wire.WireCRCError)})
        elif op == "readmit":
            meta = header["meta"]
            with mesh:
                sched.readmit(int(meta["uid"]),
                              np.asarray(meta["prompt"], np.int32),
                              [int(t) for t in meta["generated"]],
                              **_adopt_kwargs(meta))
            _send(conn, {"op": "ack", "bound": 0})
        elif op == "step":
            finished = []
            if sched.has_work:
                with mesh:
                    finished = list(sched.step())
            _send(conn, {"op": "stepped",
                         "finished": [int(u) for u in finished],
                         "has_work": bool(sched.has_work)})
        elif op == "results":
            res = sched.results()
            _send(conn, {"op": "results",
                         "outputs": {str(u): [int(t) for t in v]
                                     for u, v in res.items()},
                         "kv_stats": {k: v for k, v in
                                      sched.kv_stats().items()
                                      if isinstance(v, (int, float))}})
        else:
            _send(conn, {"op": "nak", "error": f"unknown op {op!r}",
                         "retryable": False})


class TwoProcessFleet:
    """One prefill replica in THIS process, one decode replica in a spawned
    child; KV pages cross as serialized wire frames over a Pipe.

    The deliberately minimal fabric leg: same submit/step/results/
    run_to_completion surface as ``PrefillDecodeFleet`` (the bench drives
    both identically), one replica per side, re-prefill fallback on an
    unshippable handoff. ``model_config`` is a plain dict of
    ``LlamaConfig`` fields — the child rebuilds the model and derives
    identical weights from ``PRNGKey(0)``, so the parent's ``params`` must
    come from the same init (asserted nowhere: parity tests catch a
    mismatch immediately).
    """

    def __init__(self, model, params, model_config, engine_config=None,
                 token_budget=None, decode_engine_config=None,
                 decode_token_budget=None, delta_shipping=True,
                 wire_quantize=True, retries=2, init_len=8):
        import multiprocessing as mp

        import jax
        from deepspeed_tpu.inference.v2.replica_group import build_replica
        self._mesh, self._sched = build_replica(
            model, params, [jax.devices()[0]],
            engine_config=engine_config, token_budget=token_budget)
        self._sched.on_finish = self._on_prefill_finish
        self._delta = bool(delta_shipping)
        self._wire_quantize = bool(wire_quantize)
        self._retries = int(retries)
        self._meta = {}
        self._pending = []       # requests awaiting ship this round
        self._remote_has_work = False
        # fabric counters (the bench payload's two-process leg)
        self.handoffs = 0
        self.transfers = 0
        self.pages_shipped = 0
        self.pages_delta_skipped = 0
        self.wire_bytes_shipped = 0
        self.wire_bytes_saved = 0
        self.crc_naks = 0
        self.fallbacks = 0
        self.lost_requests = 0
        ctx = mp.get_context("spawn")
        self._conn, child_conn = ctx.Pipe()
        mc = dict(model_config)
        if not isinstance(mc.get("dtype", ""), str):
            mc["dtype"] = np.dtype(mc["dtype"]).name if hasattr(
                mc["dtype"], "itemsize") else mc["dtype"].__name__
        self._proc = ctx.Process(
            target=decode_worker_main,
            args=(child_conn, mc,
                  decode_engine_config or engine_config,
                  decode_token_budget or token_budget, init_len),
            daemon=True)
        self._proc.start()
        child_conn.close()
        header, _ = _recv(self._conn)
        if header.get("op") != "ready" or \
                header.get("protocol") != PROTOCOL_VERSION:
            raise RuntimeError(f"decode worker handshake failed: {header}")
        logger.info("TwoProcessFleet: decode worker pid "
                    f"{self._proc.pid} ready")

    # -- request surface ---------------------------------------------------
    def submit(self, uid, prompt, max_new_tokens=16, eos_token_id=None,
               temperature=0.0, top_k=0, top_p=1.0, seed=None,
               slo_class=None):
        if seed is None:
            seed = secrets.randbits(31)
        self._meta[uid] = {"uid": int(uid),
                           "max_new_tokens": int(max_new_tokens),
                           "eos_token_id": eos_token_id,
                           "temperature": float(temperature),
                           "top_k": int(top_k), "top_p": float(top_p),
                           "seed": int(seed), "slo_class": slo_class}
        with self._mesh:
            self._sched.submit(uid, prompt, max_new_tokens=1,
                               eos_token_id=eos_token_id,
                               temperature=temperature, top_k=top_k,
                               top_p=top_p, seed=seed, slo_class=slo_class)

    def _on_prefill_finish(self, sched, req):
        meta = self._meta.get(req.uid)
        if meta is None:
            return False
        tok = req.generated[-1]
        if len(req.generated) + req.pos_offset >= meta["max_new_tokens"] \
                or (meta["eos_token_id"] is not None and
                    tok == meta["eos_token_id"]):
            return False  # complete at prefill: normal flush + finish
        self._pending.append(req)
        return True

    # -- the fabric --------------------------------------------------------
    def _rpc(self, header, payload=b""):
        _send(self._conn, header, payload)
        return _recv(self._conn)

    def _flush_ships(self):
        if not self._pending:
            return
        reqs, self._pending = self._pending, []
        uids = [r.uid for r in reqs]
        engine = self._sched.engine
        from deepspeed_tpu.inference.v2.fleet import wire
        skip = None
        if self._delta:
            chains = {u: c for u, c in
                      engine.sequence_block_digests(uids).items() if c}
            if chains:
                held, _ = self._rpc(
                    {"op": "query",
                     "chains": {str(u): [d.hex() for d in c]
                                for u, c in chains.items()}})
                skip = {int(u): n for u, n in held["held"].items() if n} \
                    or None
        with self._mesh:
            handle = engine.export_pages_many(uids, skip=skip) if skip \
                else engine.export_pages_many(uids)
        frame = wire.encode_handle(handle, fetch=engine.host_fetch,
                                   wire_quantize=self._wire_quantize)
        adopts = [dict(self._meta[r.uid],
                       prompt=[int(t) for t in r.prompt],
                       generated=[int(t) for t in r.generated])
                  for r in reqs]
        skipped = sum(int(m.get("skipped", 0)) for m in handle["seqs"])
        per_page = len(frame) // max(int(handle["n"]), 1)
        for attempt in range(self._retries + 1):
            send_frame = frame
            try:
                faults.maybe_fail("transport.corrupt", "two_process")
            except InjectedFault:
                send_frame = wire.corrupt(frame)
            header, _ = self._rpc({"op": "ship", "adopts": adopts},
                                  send_frame)
            if header["op"] == "ack":
                self.handoffs += len(reqs)
                self.transfers += 1
                self.pages_shipped += int(handle["n"])
                self.pages_delta_skipped += skipped
                self.wire_bytes_shipped += len(frame)
                self.wire_bytes_saved += skipped * per_page
                self._remote_has_work = True
                return
            if header.get("retryable"):
                self.crc_naks += 1
                continue
            break  # deterministic reject: no retry can help
        # exhausted or non-retryable: bit-exact re-prefill on the decode
        # side (the pages left the parent with the export — only the
        # prefill compute is paid again)
        logger.warning(f"two-process handoff failed for uids {uids} "
                       f"({header.get('error')}); re-prefilling remotely")
        for a in adopts:
            self._rpc({"op": "readmit", "meta": a})
            self.fallbacks += 1
        self._remote_has_work = True

    # -- serving loop ------------------------------------------------------
    @property
    def has_work(self):
        return self._sched.has_work or bool(self._pending) or \
            self._remote_has_work

    def step(self):
        """One lockstep round: parent prefill forward, ship the round's
        finished prefills, then one decode round in the child. Returns
        uids that finished on either side this round."""
        finished = []
        if self._sched.has_work:
            with self._mesh:
                finished = list(self._sched.step())
        self._flush_ships()
        header, _ = self._rpc({"op": "step"})
        self._remote_has_work = bool(header["has_work"])
        finished.extend(header["finished"])
        return finished

    def run_to_completion(self, max_rounds=10000):
        for _ in range(max_rounds):
            if not self.has_work:
                break
            self.step()
        else:
            raise RuntimeError("two-process fleet did not converge")
        return self.results()

    def results(self):
        """Merged {uid: tokens}; child-side entries win (they extend the
        prefill side's first token)."""
        out = {u: np.asarray(v, np.int32)
               for u, v in self._sched.results().items()}
        header, _ = self._rpc({"op": "results"})
        for u, v in header["outputs"].items():
            out[int(u)] = np.asarray(v, np.int32)
        return out

    def stats(self):
        return {"handoffs": self.handoffs, "transfers": self.transfers,
                "pages_shipped": self.pages_shipped,
                "pages_delta_skipped": self.pages_delta_skipped,
                "wire_bytes_shipped": self.wire_bytes_shipped,
                "wire_bytes_saved": self.wire_bytes_saved,
                "crc_naks": self.crc_naks, "fallbacks": self.fallbacks,
                "lost_requests": self.lost_requests}

    def close(self):
        if self._proc is None:
            return
        try:
            self._rpc({"op": "shutdown"})
        except (EOFError, OSError, BrokenPipeError):
            pass
        self._proc.join(timeout=30)
        if self._proc.is_alive():
            self._proc.terminate()
        self._conn.close()
        self._proc = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
