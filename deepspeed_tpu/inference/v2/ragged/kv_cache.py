"""Blocked (paged) KV cache (mirrors reference
``deepspeed/inference/v2/ragged/kv_cache.py:40``).

Device layout: one K pool and one V pool per cache group, shaped
``[num_layers, num_blocks, num_kv_heads, block_size, head_dim]`` — (block_size,
head_dim) minor so the Pallas paged kernel's per-block DMA is a legal Mosaic
tile. Block ids are
handed out by the host-side ``BlockedAllocator``; the model's paged-attention
path scatters new KVs into the pool and gathers per-sequence views through
block tables. One extra *trash block* (index ``num_blocks``) absorbs writes
from padded token slots, keeping every scatter shape static for XLA.

A group of ONE leaf (``leaves=1``, ``ragged/cache_groups.py``: a latent row a
token) has the K pool alone: ``v_pool`` is None, ``fwd`` is a 1-tuple, and a
pool's bytes are tokens x row width x itemsize. It takes fp pages and no host
tier; swap-out and swap-in of a preempted sequence move its one leaf.

A K and V pair with an index leaf (``index_dim``: an indexer's key a token
and layer, ``cache_groups.py``) has a third pool ``i_pool`` ``[num_layers,
num_blocks + 1, 1, block_size, index_dim]`` under the same block ids: ``fwd``
is ``(K, V, index)``, swap-out and swap-in move all three, and it takes fp
pages, no host tier and no page wire, as a group of one leaf does.

Storage tiers (the long-context capacity axes):

* ``kv_dtype="int8"`` stores the pools int8 with per-token fp32 scales in
  side pools shaped ``[num_layers, num_blocks, num_kv_heads, 1, block_size]``
  (one scale per token row over head_dim — incremental decode appends one row
  at a time, so per-row scales never rescale a page). The EQuARX-style wire
  format of ``ops/pallas/quant_collective.py`` applied to pages: quantization
  happens on-write inside the jitted forward, dequantization fuses into the
  paged-attention read. Throughout this file a "page array" is either a plain
  array (fp) or a ``(int8_data, fp32_scale)`` tuple — jax pytrees make the
  pair flow through jit/scan/device_put unchanged.
* a host-DRAM spill tier (``host_capacity`` blocks) behind the allocator's
  fourth block state: parked prefix blocks spill device->host through a
  double-buffered ``HostKVSwapper`` instead of being evicted, and restore on
  prefix hits. All device->host landings route through the injectable
  accounted fetch (``set_host_fetch`` — the engine wires ``host_fetch`` in so
  the host-sync ratchet sees them).
* an NVMe tier (``nvme_capacity`` blocks) under the host tier — the
  allocator's fifth state, fed by demotion when the host tier fills: the
  oldest host payload is persisted through the in-tree ``swap_tensor`` aio
  path (``NVMeKVStore``) and restores transparently. Pressure order:
  spill -> NVMe -> evict -> preempt.
"""

import time

import jax.numpy as jnp

from deepspeed_tpu.inference.v2.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.runtime.swap_tensor.kv_swapper import HostKVSwapper, _Payload

_DTYPES = {"bf16": jnp.bfloat16, "fp16": jnp.float16, "fp32": jnp.float32}

# injectable clock alias: the zero-overhead test proves the disabled
# telemetry path never reads it (same pattern as inference/v2/scheduler.py)
_now = time.perf_counter


def split_pages(x):
    """Page array -> (data, scale_or_None); accepts both conventions."""
    return x if isinstance(x, tuple) else (x, None)


class _NVMeAdapter:
    """Bridges the allocator's opaque spill payloads to an ``NVMeKVStore``:
    a demotion lands a still-pending payload first (the store persists host
    numpy, never in-flight device arrays), and a read comes back as an
    already-landed payload so ``restore_block``'s ``land`` is a no-op."""

    def __init__(self, store, swapper):
        self._store = store
        self._swapper = swapper

    def write(self, payload):
        return self._store.write(self._swapper.land(payload))

    def read(self, key):
        p = _Payload(self._store.read(key))
        p.landed = True
        return p

    def drop(self, key):
        self._store.drop(key)


class BlockedKVCache:

    def __init__(self, num_layers, num_blocks, block_size, num_kv_heads,
                 head_dim, dtype="bf16", kv_dtype="fp", host_capacity=0,
                 nvme_capacity=0, nvme_dir=None, leaves=2, index_dim=None):
        self.num_layers = num_layers
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.leaves = leaves
        self.quantized = (kv_dtype == "int8")
        if kv_dtype not in ("fp", "int8"):
            raise ValueError(f"kv_dtype must be 'fp' or 'int8', got {kv_dtype!r}")
        self.kv_pair = leaves == 2 and index_dim is None
        if not self.kv_pair:
            kind = "of one leaf" if leaves == 1 else "with an index leaf"
            for on, what in ((self.quantized, "kv_dtype int8"),
                             (host_capacity, "host_kv_blocks (the host tier)"),
                             (nvme_capacity, "nvme_kv_blocks (the NVMe tier)")):
                if on:
                    raise ValueError(f"{what} is not supported for a paged "
                                     f"group {kind}")
        self.dtype = jnp.int8 if self.quantized else _DTYPES.get(dtype, dtype)
        # +1 trash block for masked writes
        shape = (num_layers, num_blocks + 1, num_kv_heads, block_size, head_dim)
        self.k_pool = jnp.zeros(shape, self.dtype)
        self.v_pool = jnp.zeros(shape, self.dtype) if leaves == 2 else None
        self.i_pool = None if index_dim is None else jnp.zeros(
            (num_layers, num_blocks + 1, 1, block_size, index_dim), self.dtype)
        if self.quantized:
            # one fp32 scale per (layer, block, kv head, token row); the
            # trailing (1, block_size) layout makes the kernel's scale tile a
            # legal [1, bs] lane row under the same block-table index map
            sshape = (num_layers, num_blocks + 1, num_kv_heads, 1, block_size)
            self.k_scale = jnp.ones(sshape, jnp.float32)
            self.v_scale = jnp.ones(sshape, jnp.float32)
        else:
            self.k_scale = self.v_scale = None
        self._allocator = BlockedAllocator(num_blocks,
                                           host_capacity=host_capacity)
        self._fetch = None  # injectable accounted device->host fetch
        self._swapper = HostKVSwapper(self._fetch_arrays, buffer_count=2,
                                      land_wrapper=self._timed_land)
        self._nvme_store = None
        if nvme_capacity:
            if not host_capacity:
                raise ValueError("nvme tier requires a host tier "
                                 "(pressure order spill -> NVMe)")
            import tempfile
            from deepspeed_tpu.runtime.swap_tensor.nvme_kv_store import \
                NVMeKVStore
            self._nvme_store = NVMeKVStore(
                nvme_dir or tempfile.mkdtemp(prefix="ds_tpu_nvme_kv_"))
            self._allocator.bind_nvme(
                _NVMeAdapter(self._nvme_store, self._swapper), nvme_capacity)

    @property
    def nvme_store(self):
        """Bound ``NVMeKVStore`` (None when the tier is off)."""
        return self._nvme_store

    @property
    def allocator(self) -> BlockedAllocator:
        """Host-side block allocator (refcounts, prefix-cache binding)."""
        return self._allocator

    @property
    def free_blocks(self) -> int:
        return self._allocator.free_blocks

    @property
    def occupancy(self) -> float:
        """Fraction of pool blocks currently allocated (host-side read)."""
        return 1.0 - self._allocator.free_blocks / self.num_blocks

    def allocator_stats(self):
        """Free-list depth + fragmentation (``BlockedAllocator.stats``)."""
        return self._allocator.stats()

    @property
    def trash_block(self) -> int:
        return self.num_blocks

    def reserve(self, num_blocks):
        """Allocate block ids (reference ``kv_cache.py:144``)."""
        return self._allocator.allocate(num_blocks)

    def free(self, blocks):
        """Return block ids to the pool (reference ``kv_cache.py:155``)."""
        self._allocator.free(blocks)

    # -- forward-pass pool views ------------------------------------------
    @property
    def fwd_k(self):
        """K pages as the forward wants them: the pool array, or the
        ``(int8, scale)`` pair when quantized (one donated pytree arg)."""
        return (self.k_pool, self.k_scale) if self.quantized else self.k_pool

    @property
    def fwd_v(self):
        return (self.v_pool, self.v_scale) if self.quantized else self.v_pool

    @property
    def fwd(self):
        """The group's entry of a forward's ``cache``: ``(K, V)``, ``(K, V,
        index)``, or the one leaf alone ``(pages,)``."""
        if self.i_pool is not None:
            return (self.k_pool, self.v_pool, self.i_pool)
        return (self.fwd_k, self.fwd_v) if self.leaves == 2 else (self.k_pool,)

    @property
    def pool_bytes(self):
        """Device bytes of the pools (trash page and scales included)."""
        pools = (self.k_pool, self.v_pool, self.i_pool, self.k_scale,
                 self.v_scale)
        return sum(p.size * p.dtype.itemsize for p in pools if p is not None)

    def update(self, k, v=None, index=None):
        """Swap in pools returned by the jitted forward (pairs when
        quantized, mirroring ``fwd``)."""
        if self.i_pool is not None:
            self.k_pool, self.v_pool, self.i_pool = k, v, index
        elif self.leaves == 1:
            self.k_pool = k
        elif self.quantized:
            (self.k_pool, self.k_scale) = k
            (self.v_pool, self.v_scale) = v
        else:
            self.k_pool, self.v_pool = k, v

    def place(self, sharding):
        """Commit the pools onto an explicit device/sharding. Freshly zeroed
        pools are UNCOMMITTED (default-device) until the first forward runs;
        a replica pinned to a submesh must commit them eagerly so
        cross-replica page shipping (``import_blocks`` before any forward)
        lands on the replica's devices, not device 0."""
        import jax
        self.k_pool = jax.device_put(self.k_pool, sharding)
        if self.v_pool is not None:
            self.v_pool = jax.device_put(self.v_pool, sharding)
        if self.i_pool is not None:
            self.i_pool = jax.device_put(self.i_pool, sharding)
        if self.quantized:
            self.k_scale = jax.device_put(self.k_scale, sharding)
            self.v_scale = jax.device_put(self.v_scale, sharding)

    # -- accounted device->host transfers ----------------------------------
    def set_host_fetch(self, fetch):
        """Route every device->host landing (swap_out, spill) through
        ``fetch(value, what) -> numpy`` — the engine wires its accounted
        ``host_fetch`` in so the host-sync ratchet sees KV swap traffic."""
        self._fetch = fetch

    def land_arrays(self, arrays, what):
        """Land a tuple of dispatched device arrays on host through the
        accounted fetch (the state manager's slot leaves use it too)."""
        return self._fetch_arrays(arrays, what)

    def _fetch_arrays(self, arrays, what):
        """Land a tuple of dispatched device arrays on host."""
        if self._fetch is not None:
            return tuple(self._fetch(a, what) for a in arrays)
        import jax
        import numpy as np
        out = jax.device_get(tuple(arrays))  # graftlint: allow[GL003] unwired fallback; the engine injects the accounted host_fetch here
        return tuple(np.asarray(a) for a in out)  # graftlint: allow[GL004] device_get above already landed the arrays on host

    def _timed_land(self, thunk):
        """Swap-out landing hook: time the host fetch only when telemetry is
        on (the disabled path never reads the clock — test-pinned)."""
        from deepspeed_tpu import telemetry
        tm = telemetry.get_telemetry()
        if not tm.enabled:
            return thunk()
        t0 = _now()
        out = thunk()
        tm.record_hist("serving/kv_swap_out_s", _now() - t0)
        return out

    def _gather_pages(self, idx):
        """Dispatch gathers of the given block rows (and their scales) —
        all before any fetch, so the device->host copies pipeline."""
        parts = [jnp.take(self.k_pool, idx, axis=1)]
        if self.leaves == 1:
            return tuple(parts)
        parts.append(jnp.take(self.v_pool, idx, axis=1))
        if self.i_pool is not None:
            parts.append(jnp.take(self.i_pool, idx, axis=1))
        if self.quantized:
            parts += [jnp.take(self.k_scale, idx, axis=1),
                      jnp.take(self.v_scale, idx, axis=1)]
        return tuple(parts)

    def _scatter_pages(self, idx, parts):
        """Bind host (or shipped device) page rows under the given ids."""
        self.k_pool = self.k_pool.at[:, idx].set(
            jnp.asarray(parts[0], self.dtype))
        if self.leaves == 1:
            return
        self.v_pool = self.v_pool.at[:, idx].set(
            jnp.asarray(parts[1], self.dtype))
        if self.i_pool is not None:
            self.i_pool = self.i_pool.at[:, idx].set(
                jnp.asarray(parts[2], self.dtype))
        if self.quantized:
            self.k_scale = self.k_scale.at[:, idx].set(
                jnp.asarray(parts[2], jnp.float32))
            self.v_scale = self.v_scale.at[:, idx].set(
                jnp.asarray(parts[3], jnp.float32))

    # -- host swap tier (ZeRO-Inference KV offload analog) -----------------
    # Reference capability: ``deepspeed/inference`` ZeRO-Inference offloads
    # KV to host so more/longer sequences fit (README "20x" claim combines
    # this with weight quant). TPU mechanics: block rows gather device→host
    # between forwards (jax async dispatch overlaps the copy), the ids return
    # to the allocator, and a later ``swap_in`` scatters the bytes into fresh
    # blocks — sequences preempt under KV pressure WITHOUT losing their cache.
    def swap_out(self, blocks):
        """Pull the given block rows to host memory and release the caller's
        reference on their ids. Shared (prefix-cached) blocks stay live under
        their other holders — the copy is conservative but the handle must be
        self-contained. Returns an opaque host handle for ``swap_in``."""
        blocks = list(blocks)
        # dispatch every gather before fetching so the device→host copies
        # pipeline (jax async dispatch), instead of stalling on K before V
        parts = self._gather_pages(jnp.asarray(blocks, jnp.int32))
        landed = self._fetch_arrays(parts, "kv_cache/swap_out")
        self._allocator.free(blocks)
        return {"n": len(blocks), "parts": landed}

    def swap_in(self, handle):
        """Restore swapped blocks into freshly allocated ids (order preserved:
        the i-th restored block holds what the i-th swapped-out block held).
        Returns the new block ids."""
        new_blocks = self._allocator.allocate(handle["n"])
        self._scatter_pages(jnp.asarray(new_blocks, jnp.int32),
                            handle["parts"])
        return new_blocks

    # -- host-DRAM spill tier (parked prefix blocks) -----------------------
    # Unlike ``swap_out`` (live-sequence preemption: synchronous handle, ids
    # freed), spills keep the block's identity alive in the allocator's
    # fourth state: the gather is dispatched here but only LANDS on host when
    # the double-buffered swapper rotates (or a restore demands it), so
    # decode steps dispatched in between overlap the copies.
    def spill_block(self, block):
        """Dispatch a parked block's pages device->host; returns the opaque
        payload for ``BlockedAllocator.spill`` (pending until landed)."""
        return self._swapper.submit(
            self._gather_pages(jnp.asarray([block], jnp.int32)))

    def restore_block(self, payload, block):
        """Scatter a spilled payload's pages into device block ``block``
        (freshly allocated by the caller). Lands the payload first if its
        device->host copy is still in flight."""
        parts = self._swapper.land(payload)
        from deepspeed_tpu import telemetry
        tm = telemetry.get_telemetry()
        if not tm.enabled:
            self._scatter_pages(jnp.asarray([block], jnp.int32), parts)
            return
        t0 = _now()
        self._scatter_pages(jnp.asarray([block], jnp.int32), parts)
        tm.record_hist("serving/kv_swap_in_s", _now() - t0)

    @property
    def swapper(self) -> HostKVSwapper:
        return self._swapper

    # -- page transfer (prefill/decode disaggregation) ---------------------
    # Unlike the swap tier above, these never round-trip through host numpy:
    # the gather stays a device array so ``KVPageTransport`` can device_put
    # it straight onto the destination pool's submesh (ICI path), and the
    # scatter accepts whatever placement the transport delivered. Quantized
    # pools ship ``(int8, scale)`` pairs — the pytree flows through
    # device_put like a plain array.
    def _refuse_one_leaf(self, what):
        if not self.kv_pair:
            raise ValueError(f"{what}: the page wire carries K and V pairs, "
                             f"not a paged group of one leaf or one with an "
                             f"index leaf")

    def _pad_pages(self, blocks):
        """Pad a block-id list to the next power of two with trash-block
        reads/writes. Transfers bucket their shapes so the gather/scatter
        pair compiles once per bucket, not once per page count — a cold
        compile per handoff would dwarf the copy it measures."""
        b = 1
        while b < len(blocks):
            b *= 2
        return list(blocks) + [self.trash_block] * (b - len(blocks))

    def export_blocks(self, blocks):
        """Gather the given block rows as DEVICE arrays for shipping to
        another pool. The gather COPIES, so the caller may free or donate
        the source ids immediately — later eviction of a donated block
        cannot corrupt the shipped pages. Returns ``(k, v)`` shaped
        ``[num_layers, bucket(len(blocks)), heads, block_size, head_dim]``
        (each a ``(data, scale)`` pair when quantized) — rows past
        ``len(blocks)`` are trash-block padding."""
        self._refuse_one_leaf("export_blocks")
        idx = jnp.asarray(self._pad_pages(list(blocks)), jnp.int32)
        parts = self._gather_pages(idx)
        if self.quantized:
            return (parts[0], parts[2]), (parts[1], parts[3])
        return parts

    def import_blocks(self, k, v, n):
        """Bind the first ``n`` shipped block rows into this pool under
        freshly allocated ids (refcount 1 via the allocator, evicting parked
        cached blocks first under pressure); padding rows scatter into the
        trash block. Returns the new ids in shipping order."""
        self._refuse_one_leaf("import_blocks")
        k, ks = split_pages(k)
        v, vs = split_pages(v)
        if (ks is not None) != self.quantized:
            raise ValueError("page dtype mismatch: shipment and pool must "
                             "both be quantized or both fp")
        new_blocks = self._allocator.allocate(n)
        idx = jnp.asarray(
            new_blocks + [self.trash_block] * (int(k.shape[1]) - n),
            jnp.int32)
        parts = (k, v) if ks is None else (k, v, ks, vs)
        self._scatter_pages(idx, parts)
        return new_blocks
