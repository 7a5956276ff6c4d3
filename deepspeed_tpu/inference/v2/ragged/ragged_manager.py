"""Ragged state manager (mirrors reference
``deepspeed/inference/v2/ragged/ragged_manager.py:19``): tracks live sequences
and owns what they keep on the device between dispatches, as the model's
cache groups declare it (``ragged/cache_groups.py``): the blocked KV cache of
the ``"kv"`` group (a K and a V pool, a third pool of an indexer's keys in
the same pages where the group declares ``index_dim``, or ONE pool of latent
rows where it declares ``leaves=1``), further paged groups whose pages are freed
behind a window, a slot group of recurrent state, and a counter group (an
int32 accumulator the forward adds to, no sequence's: ``device_counters``)."""

import numpy as np

from deepspeed_tpu.inference.v2.ragged.cache_groups import (
    CounterGroup, PagedGroup, SlotGroup)
from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
from deepspeed_tpu.inference.v2.ragged.prefix_cache import PrefixCache
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import DSSequenceDescriptor
from deepspeed_tpu.utils.logging import logger


def selected_tokens(seen, new, topk):
    """What a row of ``new`` tokens behind ``seen`` cached ones reads of a
    layer under learned sparse attention, from the lengths alone: the sum
    over its new tokens of ``min(position + 1, topk)``."""
    full = min(max(seen + new - topk, 0), new)       # tokens at topk or past
    short = new - full                               # positions seen .. < topk
    return full * topk + short * seen + short * (short + 1) // 2


class DSStateManager:

    def __init__(self, config, groups):
        self._config = config
        sm, kv = config.state_manager, config.kv_cache
        primary = groups[0]
        counters = [g for g in groups[1:] if isinstance(g, CounterGroup)]
        further = [g for g in groups[1:] if g not in counters]
        self._init_counters(counters)
        if not isinstance(primary, PagedGroup) or primary.name != "kv":
            raise ValueError("a model's first cache group is the paged "
                             "group \"kv\"")
        num_layers, num_kv_heads, head_dim = \
            primary.layers, primary.kv_heads, primary.head_dim
        self.primary_group = primary
        self._init_further_groups(config, further)
        self._refuse_for_one_leaf(config, groups)
        num_blocks = sm.num_kv_blocks
        if num_blocks is None:
            num_blocks = self._blocks_from_memory_budget(
                num_layers, num_kv_heads, head_dim, kv,
                kv_dtype=sm.kv_dtype, leaves=primary.leaves,
                index_dim=primary.index_dim)
        self.kv_cache = BlockedKVCache(num_layers, num_blocks, kv.block_size,
                                       num_kv_heads, head_dim, kv.cache_dtype,
                                       kv_dtype=sm.kv_dtype,
                                       host_capacity=sm.host_kv_blocks,
                                       nvme_capacity=getattr(
                                           sm, "nvme_kv_blocks", 0),
                                       nvme_dir=getattr(
                                           sm, "nvme_kv_dir", "") or None,
                                       leaves=primary.leaves,
                                       index_dim=primary.index_dim)
        # block-granular prefix sharing (config_v2.py prefix_caching knob,
        # default off). None when disabled — every cache-path branch below
        # is a single attribute test, so the disabled path does zero
        # hashing/refcount/clock work.
        self.prefix_cache = None
        if getattr(config, "prefix_caching", False):
            self.prefix_cache = PrefixCache(self.kv_cache.allocator,
                                            kv.block_size)
            if sm.host_kv_blocks > 0:
                # pressure then demotes LRU parked blocks to host DRAM
                # (pages move through the kv_cache's async swapper) before
                # dropping anything
                self.prefix_cache.bind_spiller(self.kv_cache)
        # second, smaller page-size class for draft-model KV (speculative
        # decode); carved lazily out of the same refcounted pool so census
        # invariants and pool pressure see draft pages as ordinary tenants
        self.draft_pages = None
        spec = getattr(config, "speculative", None)
        if spec is not None and getattr(spec, "draft_page_divisor", 0) > 1:
            self.draft_pages = self.kv_cache.allocator.draft_pages(
                spec.draft_page_divisor)
        self._init_report()
        self._seqs = {}
        self.swap_outs = 0  # host swap tier counters (kv_cache swap_out/in)
        self.swap_ins = 0
        self.peak_occupancy = 0.0  # high-water KV occupancy (kv_stats)
        logger.info(f"DSStateManager: {num_blocks} KV blocks x {kv.block_size} "
                    f"tokens ({num_layers} layers, {num_kv_heads} kv heads, "
                    f"prefix_caching={'on' if self.prefix_cache else 'off'})")
        for name, (g, cache) in self.paged_groups.items():
            logger.info(f"DSStateManager: group {name!r}: {cache.num_blocks} "
                        f"blocks x {g.layers} layers, window {g.window}")
        if self.slot_group is not None:
            logger.info(f"DSStateManager: group {self.slot_group.name!r}: "
                        f"{self.trash_slot} slots")

    @staticmethod
    def _refuse_for_one_leaf(config, groups):
        """What works on a K and V pair and was not extended to a page of
        one leaf is refused for a model that declares such a group: here
        what the manager itself would build on it, in ``BlockedKVCache`` what
        the pools would (int8 pages, the host tiers)."""
        odd = [g for g in groups if isinstance(g, PagedGroup) and not g.kv_pair]
        if not odd:
            return
        kind = "of one leaf" if odd[0].leaves == 1 else "with an index leaf"
        for on, option in (
                (getattr(config, "prefix_caching", False), "prefix_caching"),
                (config.speculative.enabled, "speculative.enabled")):
            if on:
                raise ValueError(f"{option} is not supported for a model with "
                                 f"a paged cache group {kind}")

    @property
    def one_leaf(self):
        """Whether the ``"kv"`` group's page is one leaf (a latent row)."""
        return self.primary_group.leaves == 1

    @property
    def indexed(self):
        """Whether the ``"kv"`` group's page keeps an indexer's key beside
        K and V."""
        return self.primary_group.index_dim is not None

    @property
    def _kv_pair_only(self):
        """Whether the model keeps the one paged group of K and V and nothing
        else: what rollback and the page wire work on."""
        return not self.has_further_groups and self.primary_group.kv_pair

    def _init_further_groups(self, config, further):
        """Paged groups beyond ``"kv"`` (an allocator and pools each) and
        the slot group (pools and a free list). What the ``"kv"`` group's
        prefix cache, page wire and speculation rollback would have to carry
        for them does not exist yet, so those are refused here."""
        sm, kv = config.state_manager, config.kv_cache
        self.paged_groups = {}       # name -> (PagedGroup, BlockedKVCache)
        self.table_width = {}        # name -> entries of a sequence's table
        self.slot_group = None
        self.slot_pools = {}
        self._free_slots = []
        self.window_pages_freed = 0  # pages the windows gave back, in all
        if not further:
            return
        # a prefix hit or a rolled-back draft would need the window pages
        # and the recurrent state as they were at that token
        for on, option in ((getattr(config, "prefix_caching", False), "prefix_caching"),
                           (config.speculative.enabled, "speculative.enabled"),
                           (sm.kv_dtype != "fp", "kv_dtype int8")):
            if on:
                raise ValueError(f"{option} is not supported for a model with "
                                 f"more than the one paged cache group")
        bs = kv.block_size
        for g in further:
            if isinstance(g, SlotGroup):
                if self.slot_group is not None:
                    raise ValueError("one slot group a model")
                import jax.numpy as jnp
                self.slot_group = g
                n = sm.max_ragged_sequence_count
                # +1: the trash slot padded rows read and write
                self.slot_pools = {
                    name: jnp.zeros((shape[0], n + 1) + tuple(shape[1:]), dtype)
                    for name, shape, dtype in g.leaves}
                self._free_slots = list(range(n - 1, -1, -1))
                continue
            if g.window:
                # a sequence holds the window's pages, one more while a page
                # fills, and its share of a round's new tokens
                per_seq = -(-g.window // bs) + 2
                blocks = sm.max_ragged_sequence_count * per_seq \
                    + -(-sm.max_ragged_batch_size // bs)
                width = -(-g.window // bs) + -(-sm.max_ragged_batch_size // bs) + 1
            else:
                blocks = sm.num_kv_blocks or self._blocks_from_memory_budget(
                    g.layers, g.kv_heads, g.head_dim, kv, leaves=g.leaves)
                width = -(-sm.max_context // bs)
            self.paged_groups[g.name] = (g, BlockedKVCache(
                g.layers, blocks, bs, g.kv_heads, g.head_dim, kv.cache_dtype,
                leaves=g.leaves))
            self.table_width[g.name] = width

    def _init_counters(self, declared):
        """The counter group's accumulator, zeros: not a sequence's, so it is
        no further group (no table, nothing to allocate, free or refuse)."""
        if len(declared) > 1:
            raise ValueError("one counter group a model")
        self.counter_group = declared[0] if declared else None
        self.counters = None
        if declared:
            import jax.numpy as jnp
            self.counters = jnp.zeros((len(declared[0].fields),), jnp.int32)

    def device_counters(self):
        """{field: count} of the counter group since the engine was built, by
        ONE fetch; {} for a model that declared none. It waits for every
        dispatch in flight, so it is called outside a round (when a window
        closes), never by ``step()``."""
        if self.counter_group is None:
            return {}
        import jax
        values = jax.device_get(self.counters)  # graftlint: allow[GL003] on demand and outside any round: the engine's accessor counts the sync; no serving/fetch span, which the readers pair with rounds
        return dict(zip(self.counter_group.fields, (int(v) for v in values)))

    @property
    def has_further_groups(self):
        return bool(self.paged_groups) or self.slot_group is not None

    @property
    def trash_slot(self):
        return self._config.state_manager.max_ragged_sequence_count

    @property
    def free_slots(self):
        return len(self._free_slots)

    @property
    def slots_in_use(self):
        return self.trash_slot - self.free_slots if self.slot_group else 0

    # -- what a dispatch reports of the groups -------------------------------
    def _init_report(self):
        """``dispatch_report``'s constants, from the ``"kv"`` group's
        declaration and pools: the bytes of a token's latent row, or of its
        indexer's key, in one layer."""
        g, kvc = self.primary_group, self.kv_cache
        self._report_rides = {}
        # what the write's rule takes beside a dispatch's token slots a row:
        # the static shapes the forward sees (``paged_layer.writes_pages``)
        self._write_shape = (*kvc.k_pool.shape[2:4], kvc.quantized)
        if g.leaves == 1:
            self._report_rides["latent_row_bytes"] = (
                kvc.k_pool.shape[2] * kvc.k_pool.shape[4]
                * kvc.k_pool.dtype.itemsize)
        if g.index_dim is not None:
            self._report_rides["index_row_bytes"] = \
                kvc.i_pool.shape[4] * kvc.i_pool.dtype.itemsize
        self._window_freed_reported = 0

    def dispatch_report(self, seqs, seen, q_len, chunk):
        """What a dispatch of the sequences ``seqs`` (after their allocation)
        reports of the cache groups: ``(adds, rides)``, what it adds to the
        round's counts and what rides on its ``serving/build`` span alone.
        The engine carries both and reads neither. ``seen`` and ``q_len``:
        the dispatch's rows as the program gets them (numpy, padded rows of
        no tokens among them), ``chunk`` its token slots a row.

        Every group: how the ``"kv"`` group's table is written, from the
        lengths alone (``paged_layer.writes_pages`` on the shapes the forward
        sees): ``write_pages``, the table entries a page-wise dispatch writes
        whole (a row's ``ceil((seen + new) / bs) - seen // bs``), or
        ``write_rows``, the token slots a row-wise dispatch writes one by
        one; each in every layer and leaf. Beyond that the one K and V group
        reports nothing, and what reports nothing a row costs nothing a row.

        One leaf: ``latent_pages`` held now. An index leaf: ``index_pages``
        held now, the ``sparse_rows`` whose context passes ``index_topk``
        (they score, select and read sparsely) and the (token, layer) reads
        of ``selected_tokens``, from the lengths alone. Further groups: added
        are ``state_slots`` held and ``window_pages_freed`` since the last
        dispatch (the round before's retire); ``global_pages`` and
        ``window_pages`` held by tracked sequences and each further paged
        group's ``<name>_live_pages`` of these rows ride."""
        g, kvc = self.primary_group, self.kv_cache
        held = kvc.num_blocks - kvc.free_blocks
        # imported here: importing the forwards' package reaches this module
        from deepspeed_tpu.inference.v2.model_implementations.paged_layer \
            import writes_pages
        bs = self.kv_block_size
        if writes_pages(chunk, *self._write_shape):
            written = np.where(q_len > 0, -(-(seen + q_len) // bs) - seen // bs, 0)
            adds = {"write_pages": int(written.sum()), "write_rows": 0}
        else:
            adds = {"write_pages": 0, "write_rows": int(q_len.sum())}
        rides = self._report_rides
        if g.leaves == 1:
            adds["latent_pages"] = held
        elif g.index_dim is not None:
            topk = g.index_topk
            adds.update(
                index_pages=held,
                sparse_rows=sum(s.seen_tokens + s.in_flight_tokens > topk
                                for s in seqs),
                selected_tokens=g.layers * sum(
                    selected_tokens(s.seen_tokens, s.in_flight_tokens, topk)
                    for s in seqs))
        if self.has_further_groups:
            freed = self.window_pages_freed - self._window_freed_reported
            self._window_freed_reported += freed
            adds.update(window_pages_freed=freed,
                        state_slots=self.slots_in_use)
            rides = dict(rides, global_pages=held, window_pages=sum(
                c.num_blocks - c.free_blocks
                for _, c in self.paged_groups.values()))
            for name in self.paged_groups:
                rides[name + "_live_pages"] = sum(
                    len(seq.group_blocks.get(name, ())) for seq in seqs)
        return adds, rides

    # -- the cache and tables pytrees of a dispatch -------------------------
    def cache_view(self):
        """The donated ``cache`` argument of a forward: ``{"kv": (K, V)}``
        (``(K, V, index)`` with an index leaf, ``(pages,)`` for a group of one
        leaf) and, for a model that declared them, the further groups' pools
        and the counter group's accumulator."""
        view = {"kv": self.kv_cache.fwd}
        for name, (_, cache) in self.paged_groups.items():
            view[name] = cache.fwd
        if self.slot_group is not None:
            view[self.slot_group.name] = self.slot_pools
        if self.counter_group is not None:
            view[self.counter_group.name] = self.counters
        return view

    def cache_update(self, view):
        """Swap in the cache a forward returned."""
        self.kv_cache.update(*view["kv"])
        for name, (_, cache) in self.paged_groups.items():
            cache.update(*view[name])
        if self.slot_group is not None:
            self.slot_pools = view[self.slot_group.name]
        if self.counter_group is not None:
            self.counters = view[self.counter_group.name]

    def group_tables(self, seqs, n_rows):
        """The further groups' entries of a dispatch's ``tables`` for the
        sequences ``seqs`` (its rows in order) padded to ``n_rows``: a paged
        group's live pages ``[n_rows, width]`` and the token its first page
        starts at (``<name>_base``), the slot group's slot ids."""
        out = {}
        bs = self.kv_block_size
        for name, (_, cache) in self.paged_groups.items():
            table = np.full((n_rows, self.table_width[name]),
                            cache.trash_block, np.int32)
            base = np.zeros((n_rows,), np.int32)
            for i, seq in enumerate(seqs):
                blocks = seq.group_blocks.get(name, ())
                table[i, :len(blocks)] = blocks
                base[i] = seq.group_base.get(name, 0) * bs
            out[name], out[name + "_base"] = table, base
        if self.slot_group is not None:
            ids = np.full((n_rows,), self.trash_slot, np.int32)
            ids[:len(seqs)] = [seq.slot for seq in seqs]
            out[self.slot_group.name] = ids
        return out

    def first_live_block(self, group, seen):
        """Index of the first block a query at position ``seen`` or later
        can still see in a group of window ``group.window``."""
        if not group.window:
            return 0
        return max(0, (seen - group.window + 1) // self.kv_block_size)

    def further_blocks_needed(self, seq, seen, new_tokens):
        """{group name: extra pages} to run ``new_tokens`` more tokens of a
        sequence (``seq`` None: a new one) in the further paged groups."""
        need = {}
        end = -(-(seen + new_tokens) // self.kv_block_size)
        for name, (g, _) in self.paged_groups.items():
            if seq is None or name not in seq.group_base:
                held_to = self.first_live_block(g, seen)
            else:
                held_to = seq.group_base[name] + len(seq.group_blocks[name])
            need[name] = max(0, end - held_to)
        return need

    def take_slot(self, seq):
        """Give ``seq`` its slot of recurrent state (kept until flush or
        swap-out). The slot is not cleared: the first chunk's dispatch
        starts from zero state (its rows have ``seen`` 0)."""
        if self.slot_group is not None and seq.slot is None:
            if not self._free_slots:
                raise RuntimeError("no free state slot")
            seq.slot = self._free_slots.pop()
        return seq.slot

    def _release_further(self, seq):
        for name, (_, cache) in self.paged_groups.items():
            cache.free(seq.group_blocks.pop(name, []))
            seq.group_base.pop(name, None)
        if seq.slot is not None:
            self._free_slots.append(seq.slot)
            seq.slot = None

    def retire_window(self, seq):
        """After a forward: free the pages of ``seq`` that lie wholly before
        ``seen - window`` in every windowed group. Returns the count."""
        freed = 0
        for name, (g, cache) in self.paged_groups.items():
            if not g.window or name not in seq.group_base:
                continue
            drop = self.first_live_block(g, seq.seen_tokens) - seq.group_base[name]
            if drop > 0:
                blocks = seq.group_blocks[name]
                cache.free(blocks[:drop])
                del blocks[:drop]
                seq.group_base[name] += drop
                freed += drop
        self.window_pages_freed += freed
        return freed

    @staticmethod
    def _blocks_from_memory_budget(num_layers, num_kv_heads, head_dim, kv,
                                   kv_dtype="fp", leaves=2, index_dim=None):
        """Size the pool from device memory (the reference derives block count
        from a reserved memory fraction, ``ragged_manager.py`` memory_config):
        ~60% of the device's memory limit, fallback 1 GiB when unknown.
        int8 pages cost 1 byte/element plus one fp32 scale per token row —
        the capacity lever: the same budget holds ~itemsize/(1+4/Dh) times
        more blocks than fp."""
        import numpy as np
        if kv_dtype == "int8":
            # int8 page + fp32 per-(token, kv head) scale
            elt_bytes = 1 + 4 / head_dim
        else:
            elt_bytes = np.dtype(
                "float32" if kv.cache_dtype == "fp32" else "uint16").itemsize
        bytes_per_block = int(num_layers * kv.block_size * elt_bytes * (
            leaves * num_kv_heads * head_dim + (index_dim or 0)))  # K + V pools
        try:
            from deepspeed_tpu import telemetry
            stats = telemetry.sample_memory("kv_cache_budget") or {}
            budget = int(stats.get("bytes_limit", 0) * 0.6)
        except Exception:
            budget = 0
        if budget <= 0:
            budget = 1 << 30
        return max(16, budget // bytes_per_block)

    @staticmethod
    def blocks_needed_for(seen, have, new_tokens, block_size):
        """Extra blocks to grow a sequence with ``seen`` cached tokens and
        ``have`` allocated blocks by ``new_tokens`` — single source of truth
        for admission control and allocation."""
        return max(0, -(-(seen + new_tokens) // block_size) - have)

    # -- sequence tracking (reference ragged_manager.py:100-205) -----------
    @property
    def tracked_sequences(self):
        return self._seqs

    @property
    def n_tracked_sequences(self):
        return len(self._seqs)

    @property
    def kv_block_size(self):
        return self.kv_cache.block_size

    @property
    def free_blocks(self):
        """Blocks available to new allocations: the raw free list plus
        (with prefix caching on) idle cached blocks the allocator will evict
        on demand — admission control must see the reclaimable total or it
        would preempt live sequences while free-for-the-taking cached blocks
        sit parked."""
        free = self.kv_cache.free_blocks
        if self.prefix_cache is not None:
            free += self.prefix_cache.evictable_blocks
        return free

    def kv_stats(self):
        """Pure host-side KV pool read: occupancy, free-list depth,
        fragmentation, swap counters. Never touches the device — the block
        bookkeeping is the deque in ``BlockedAllocator`` — so samplers can
        call this every scheduler step (the PR 4 ``sample_memory`` sync-free
        pattern applied to the KV pool). ``occupancy`` counts blocks *live
        under sequences*; idle prefix-cached blocks are reclaimable and
        reported separately (``cached_blocks``/``evictable_blocks``), and
        host-resident blocks hold no HBM at all — ``total_blocks``/
        ``occupancy``/``occupied_blocks`` are the DEVICE census
        (``num_blocks``, never the host-grown ``counts()`` total), so
        spilling can't inflate the ratcheted ``serving/kv_occupancy``
        gauge; the host tier reports via the ``host_kv_*`` fields."""
        a = self.kv_cache.allocator_stats()
        total, free = self.kv_cache.allocator.num_blocks, a["free"]
        parked = self.kv_cache.allocator.cached_blocks
        occupancy = 1.0 - (free + parked) / total if total else 0.0
        if occupancy > self.peak_occupancy:
            self.peak_occupancy = occupancy
        swapped = sum(1 for s in self._seqs.values() if s.is_swapped)
        hs = self.kv_cache.allocator.host_swap_stats()
        stats = {"total_blocks": total, "free_blocks": free,
                 "occupied_blocks": total - free - parked,
                 "occupancy": occupancy,
                 "peak_occupancy": self.peak_occupancy,
                 "free_runs": a["free_runs"],
                 "largest_free_run": a["largest_free_run"],
                 "fragmentation": a["fragmentation"],
                 "tracked_sequences": len(self._seqs),
                 "swapped_sequences": swapped,
                 # swap_outs/ins count whole-sequence preemptions of LIVE
                 # sequences (the expensive tier); the host tier's
                 # block-granular prefix traffic is the kv_* trio below
                 "swap_outs": self.swap_outs, "swap_ins": self.swap_ins,
                 "swap_outs_live": self.swap_outs,
                 "host_kv_blocks": hs["resident"],
                 "host_kv_capacity": hs["capacity"],
                 "host_kv_occupancy": (hs["resident"] / hs["capacity"]
                                       if hs["capacity"] else 0.0),
                 "kv_spilled": hs["spilled"], "kv_restored": hs["restored"],
                 "kv_dropped": hs["dropped"],
                 # NVMe tier (fifth allocator state): extends the identity to
                 # kv_spilled == kv_restored + kv_dropped
                 #              + host_kv_blocks + nvme_kv_blocks
                 "nvme_kv_blocks": hs.get("nvme_resident", 0),
                 "nvme_kv_capacity": hs.get("nvme_capacity", 0),
                 "nvme_kv_demotions": hs.get("nvme_demotions", 0)}
        if self.prefix_cache is not None:
            stats.update(self.prefix_cache.stats())
        if not self._kv_pair_only:
            # occupancy per group; "kv" repeats the device census above.
            # ``bytes``: the group's pools on the device, every leaf
            groups = {"kv": {"total": total, "free": free,
                             "occupancy": occupancy,
                             "leaves": self.primary_group.leaves
                             + self.indexed,
                             "bytes": self.kv_cache.pool_bytes}}
            for name, (g, cache) in self.paged_groups.items():
                groups[name] = {"total": cache.num_blocks,
                                "free": cache.free_blocks,
                                "occupancy": cache.occupancy,
                                "leaves": g.leaves,
                                "bytes": cache.pool_bytes,
                                "freed_by_window": self.window_pages_freed}
            if self.slot_group is not None:
                groups[self.slot_group.name] = {
                    "total": self.trash_slot, "free": self.free_slots,
                    "occupancy": self.slots_in_use / self.trash_slot}
            stats["groups"] = groups
        return stats

    def sample_kv_stats(self, point="step"):
        """``kv_stats`` + serving-gauge recording when telemetry is enabled
        (occupancy / free-list depth / fragmentation counter tracks, plus the
        prefix-cache gauges when caching is on)."""
        stats = self.kv_stats()
        from deepspeed_tpu import telemetry
        tm = telemetry.get_telemetry()
        if tm.enabled:
            tm.serving_gauge("serving/kv_occupancy", stats["occupancy"],
                             point=point)
            tm.serving_gauge("serving/kv_free_blocks", stats["free_blocks"],
                             point=point)
            tm.serving_gauge("serving/kv_fragmentation",
                             stats["fragmentation"], point=point)
            if self.prefix_cache is not None:
                tm.serving_gauge("serving/prefix_hit_rate",
                                 stats["prefix_hit_rate"], point=point)
                tm.serving_gauge("serving/cached_blocks",
                                 stats["cached_blocks"], point=point)
                tm.serving_gauge("serving/prefill_tokens_saved",
                                 stats["prefill_tokens_saved"], point=point)
            if stats["host_kv_capacity"]:
                tm.serving_gauge("serving/host_kv_blocks",
                                 stats["host_kv_blocks"], point=point)
            if stats["nvme_kv_capacity"]:
                tm.serving_gauge("serving/nvme_kv_blocks",
                                 stats["nvme_kv_blocks"], point=point)
        return stats

    def get_sequence(self, uid):
        return self._seqs.get(uid)

    def get_or_create_sequence(self, uid):
        if uid in self._seqs:
            return self._seqs[uid]
        if len(self._seqs) >= self._config.state_manager.max_tracked_sequences:
            raise RuntimeError(
                f"already tracking {len(self._seqs)} sequences "
                f"(max_tracked_sequences)")
        seq = DSSequenceDescriptor(uid=uid)
        self._seqs[uid] = seq
        return seq

    # -- prefix caching (ragged/prefix_cache.py) ---------------------------
    def match_prefix(self, uid, prompt_tokens):
        """Longest-cached-prefix match at sequence creation: on a hit the
        sequence is created holding the shared blocks with ``seen_tokens``
        advanced past the matched tokens, so the scheduler never re-runs
        them. Returns the number of matched tokens (0 = miss or disabled).
        The match is block-aligned and strictly shorter than the prompt —
        the tail always runs through a forward (COW boundary: only full,
        immutable blocks are ever shared)."""
        cache = self.prefix_cache
        if cache is None or uid in self._seqs:
            return 0
        if len(self._seqs) >= self._config.state_manager.max_tracked_sequences:
            cache.misses += 1
            return 0
        blocks, digests = cache.lookup_chain(prompt_tokens)
        if not blocks:
            cache.misses += 1
            return 0
        # host-resident links swap back in here; the resolved chain may be a
        # prefix of the match when the pool can't hold a restore
        resolved = cache.acquire_chain(blocks, digests)
        if not resolved:
            return 0
        seq = self.get_or_create_sequence(uid)
        matched = len(resolved) * cache.block_size
        seq.kv_blocks = list(resolved)
        seq.digests = list(digests[:len(resolved)])
        seq.seen_tokens = matched
        seq.tokens = [int(t) for t in prompt_tokens[:matched]]
        return matched

    def commit_cached_blocks(self, seq):
        """Register every newly FILLED full block of ``seq`` in the prefix
        cache (called after post_forward, and at flush as the donation step).
        When another sequence concurrently cached identical content, dedup:
        adopt the canonical shared block and free the private copy — the
        contents are bit-identical (same tokens, same deterministic
        per-row forward), so the block table swap is invisible to
        attention."""
        cache = self.prefix_cache
        bs = cache.block_size
        n_full = seq.seen_tokens // bs
        while len(seq.digests) < n_full:
            i = len(seq.digests)
            parent = seq.digests[i - 1] if i else b""
            digest, canonical = cache.insert(
                parent, seq.tokens[i * bs:(i + 1) * bs], seq.kv_blocks[i])
            if canonical != seq.kv_blocks[i]:
                self.kv_cache.free([seq.kv_blocks[i]])
                seq.kv_blocks[i] = canonical
            seq.digests.append(digest)

    def rollback_sequence(self, uid, n_tokens):
        """Roll a sequence's paged cursor back ``n_tokens`` — the rejected
        tail of a speculative verify chunk. Tail blocks that fall wholly
        past the new cursor are released via ``kv_cache.free`` (deref-aware:
        a shared or cached block just drops one reference; only a private
        refcount-1 block actually returns to the pool). The cursor never
        crosses the committed-prefix boundary: digests registered in the
        prefix cache cover full, immutable, possibly-shared blocks, and the
        deferred-commit protocol (``engine.commit_prefix`` after rollback)
        guarantees no rejected token was ever committed — so the guard below
        is an invariant check, not a recovery path."""
        seq = self._seqs.get(uid)
        if seq is None:
            raise ValueError(f"rollback of untracked sequence {uid}")
        if n_tokens <= 0:
            return
        if not self._kv_pair_only:
            raise ValueError("rollback is not supported for a model with "
                             "more than the one paged cache group of K and V")
        assert seq.in_flight_tokens == 0, "cannot roll back mid-forward"
        assert not seq.is_swapped, "cannot roll back a swapped sequence"
        bs = self.kv_block_size
        new_seen = seq.seen_tokens - int(n_tokens)
        assert new_seen >= 0, "rollback past start of sequence"
        assert new_seen >= len(seq.digests) * bs, \
            "rollback would cross the committed prefix-cache boundary"
        keep = -(-new_seen // bs)
        tail = seq.kv_blocks[keep:]
        if tail:
            del seq.kv_blocks[keep:]
            self.kv_cache.free(tail)
        seq.seen_tokens = new_seen
        if self.prefix_cache is not None:
            del seq.tokens[new_seen:]

    def flush_sequence(self, uid):
        """Drop a sequence and release its KV blocks (reference :110). With
        prefix caching on, full blocks are donated back to the cache instead
        of freed — committed as cache entries, then deref'd so refcount-0
        blocks park (warm, evictable) rather than hit the free list. The
        partial tail block was never shared, so it frees normally. Blocks
        deref in reverse order so chain children park before parents — LRU
        eviction then reclaims leaves first and never orphans a reachable
        ancestor."""
        seq = self._seqs.pop(uid, None)
        if seq is None:
            logger.warning(f"flush of untracked sequence {uid}")
            return
        self._release_further(seq)
        if self.prefix_cache is not None and not seq.is_swapped:
            self.commit_cached_blocks(seq)
            self.kv_cache.free(list(reversed(seq.kv_blocks)))
        else:
            self.kv_cache.free(seq.kv_blocks)

    # -- page transfer (prefill/decode disaggregation) ---------------------
    def sequence_block_digests(self, uids):
        """Full-block chain digests for the given tracked sequences — what a
        delta-shipping transport exchanges with the destination before
        exporting, so blocks the destination's prefix cache already holds
        never cross the wire. Requires prefix caching (token streams are
        only tracked then); returns ``{}`` when disabled. Untracked uids are
        silently skipped (the transport treats them as nothing-to-skip)."""
        if self.prefix_cache is None:
            return {}
        bs = self.kv_block_size
        out = {}
        for uid in uids:
            seq = self._seqs.get(uid)
            if seq is None:
                continue
            full = min(seq.seen_tokens // bs, len(seq.kv_blocks))
            parent, chain = b"", []
            for i in range(full):
                parent = PrefixCache.chain_digest(
                    parent, seq.tokens[i * bs:(i + 1) * bs])
                chain.append(parent)
            out[uid] = chain
        return out

    def held_prefix_lens(self, chains):
        """Per-uid count of leading chain links this pool's prefix cache
        already holds (device or host/NVMe tier) — the delta-shipping
        set-difference answered from the destination side."""
        if self.prefix_cache is None:
            return {uid: 0 for uid in chains}
        return {uid: self.prefix_cache.held_prefix_len(chain)
                for uid, chain in chains.items()}

    def _refuse_page_wire(self, what):
        if not self._kv_pair_only:
            raise ValueError(
                f"page {what} is not supported for a model with more than "
                f"the one paged cache group of K and V: the wire carries "
                f"\"kv\" pairs only, not window pages, recurrent state, "
                f"a page of one leaf or an index leaf")

    def export_sequence_pages(self, uid):
        """Detach ``uid``'s KV pages for shipping to another engine's pool
        (single-sequence form of ``export_sequences_pages``). Returns a
        handle for ``import_sequence_pages``."""
        h = self.export_sequences_pages([uid])
        m = h["seqs"][0]
        return {"n": m["n"], "k": h["k"], "v": h["v"],
                "seen_tokens": m["seen_tokens"], "tokens": m["tokens"]}

    def export_sequences_pages(self, uids, skip=None):
        """Batched export: EVERY listed sequence's page rows leave in ONE
        device gather (``export_blocks`` over the concatenated block lists)
        — the fleet ships a whole round's finished prefills as one
        transfer, paying dispatch cost per transfer instead of per request.
        Each sequence is then released exactly as ``flush_sequence`` would
        — with prefix caching on, full blocks are donated to the cache
        first, so a prefill replica keeps serving warm prefixes after the
        handoff. Returns a handle for ``import_sequences_pages`` whose
        ``seqs`` list preserves submission order.

        ``skip`` (delta-shipping): ``{uid: k}`` leading full blocks the
        DESTINATION's prefix cache already holds — those rows are excluded
        from the gather and ride as ``skipped_digests`` instead, for the
        importer to re-acquire locally. Requires prefix caching."""
        self._refuse_page_wire("export")
        for uid in uids:  # validate everything before mutating anything
            seq = self._seqs.get(uid)
            if seq is None:
                raise ValueError(f"export of untracked sequence {uid}")
            if seq.is_swapped:
                raise ValueError(f"cannot export swapped sequence {uid}")
            assert seq.in_flight_tokens == 0, "cannot export mid-forward"
        if skip and self.prefix_cache is None:
            raise ValueError("delta export requires prefix caching")
        bs = self.kv_block_size
        blocks, seqs, popped = [], [], []
        for uid in uids:
            seq = self._seqs.pop(uid)
            popped.append(seq)
            hold = 0
            if skip:
                hold = min(int(skip.get(uid, 0)), seq.seen_tokens // bs,
                           len(seq.kv_blocks))
            m = {"uid": uid, "n": len(seq.kv_blocks) - hold,
                 "seen_tokens": seq.seen_tokens,
                 "tokens": list(seq.tokens)}
            if hold:
                parent, digs = b"", []
                for i in range(hold):
                    parent = PrefixCache.chain_digest(
                        parent, seq.tokens[i * bs:(i + 1) * bs])
                    digs.append(parent)
                m["skipped"] = hold
                m["skipped_digests"] = digs
            seqs.append(m)
            blocks.extend(seq.kv_blocks[hold:])
        # one gather for the whole group — it COPIES, so the ids can be
        # freed/donated immediately after
        k, v = self.kv_cache.export_blocks(blocks)
        for seq in popped:
            if self.prefix_cache is not None:
                self.commit_cached_blocks(seq)
                self.kv_cache.free(list(reversed(seq.kv_blocks)))
            else:
                self.kv_cache.free(seq.kv_blocks)
        return {"n": len(blocks), "k": k, "v": v, "seqs": seqs}

    def import_sequence_pages(self, uid, handle):
        """Bind shipped KV pages into this pool (single-sequence form of
        ``import_sequences_pages``). Returns the bound block count."""
        return self.import_sequences_pages(
            {"n": handle["n"], "k": handle["k"], "v": handle["v"],
             "seqs": [{"uid": uid, "n": handle["n"],
                       "seen_tokens": handle["seen_tokens"],
                       "tokens": handle.get("tokens", [])}]})

    def import_sequences_pages(self, handle):
        """Bind a batched shipment: ONE scatter allocates fresh block ids
        (refcount 1 via the ``BlockedAllocator``) for every sequence in the
        handle, then each sequence is created mid-stream with
        ``seen_tokens`` already past its shipped pages — decode never
        re-runs prefill. With prefix caching on, the token streams ride
        along so imported full blocks register in THIS pool's cache at the
        next commit. All-or-nothing: on any failure the partially created
        sequences and all imported blocks are released. Returns the total
        bound block count."""
        self._refuse_page_wire("import")
        for m in handle["seqs"]:
            if m["uid"] in self._seqs:
                raise ValueError(f"uid {m['uid']} already tracked")
        # delta-shipping: re-acquire skipped prefix blocks from the LOCAL
        # prefix cache first — a miss (evicted between the digest exchange
        # and the ship) aborts before anything binds, and the transport's
        # bind-failure path re-prefills the request
        prefix_ids, prefix_digs, acquired = {}, {}, []
        try:
            for m in handle["seqs"]:
                hold = int(m.get("skipped", 0))
                if not hold:
                    continue
                if self.prefix_cache is None:
                    raise ValueError("delta shipment without a prefix cache")
                digs = [bytes.fromhex(d) if isinstance(d, str) else d
                        for d in m["skipped_digests"]]
                got = self.prefix_cache.acquire_known(digs)
                acquired.extend(got)
                if len(got) < hold:
                    raise ValueError(
                        f"delta bind miss for {m['uid']}: "
                        f"held {len(got)}/{hold} skipped blocks")
                prefix_ids[m["uid"]] = got
                prefix_digs[m["uid"]] = digs
            ids = list(self.kv_cache.import_blocks(
                handle["k"], handle["v"], int(handle["n"])))
        except Exception:
            if acquired:
                self.kv_cache.free(acquired)
            raise
        off, created = 0, []
        try:
            for m in handle["seqs"]:
                seq = self.get_or_create_sequence(m["uid"])
                created.append(m["uid"])
                seq.kv_blocks = prefix_ids.get(m["uid"], []) \
                    + ids[off:off + int(m["n"])]
                off += int(m["n"])
                seq.seen_tokens = int(m["seen_tokens"])
                if self.prefix_cache is not None:
                    seq.tokens = [int(t) for t in m["tokens"]]
                    # skipped blocks are already-registered cache entries;
                    # seed their digests so commit starts past them
                    seq.digests = list(prefix_digs.get(m["uid"], []))
        except Exception:
            for uid in created:
                self._seqs.pop(uid, None)
            self.kv_cache.free(ids)
            if acquired:
                self.kv_cache.free(acquired)
            raise
        return len(ids) + len(acquired)

    # -- host swap tier (ZeRO-Inference KV offload analog) -----------------
    def swap_out_sequence(self, uid):
        """Move a tracked sequence's KV blocks to host memory; the sequence
        stays tracked (seen_tokens intact) but holds no device blocks."""
        seq = self._seqs[uid]
        if seq.is_swapped:
            return
        assert seq.in_flight_tokens == 0, "cannot swap a sequence mid-forward"
        seq.swap_handle = self.kv_cache.swap_out(seq.kv_blocks)
        seq.kv_blocks = []
        # the live window pages and the slot's leaves travel with them
        for name, (_, cache) in self.paged_groups.items():
            if name in seq.group_blocks:
                seq.group_swap[name] = cache.swap_out(seq.group_blocks[name])
                seq.group_blocks[name] = []
        if seq.slot is not None:
            leaves = tuple(pool[:, seq.slot] for pool in self.slot_pools.values())
            seq.group_swap[self.slot_group.name] = \
                self.kv_cache.land_arrays(leaves, "kv_cache/swap_out")
            self._free_slots.append(seq.slot)
            seq.slot = None
        self.swap_outs += 1

    def swap_in_sequence(self, uid):
        """Restore a swapped sequence into fresh device blocks."""
        seq = self._seqs[uid]
        if not seq.is_swapped:
            return
        seq.kv_blocks = list(self.kv_cache.swap_in(seq.swap_handle))
        seq.swap_handle = None
        for name, (_, cache) in self.paged_groups.items():
            if name in seq.group_swap:
                seq.group_blocks[name] = list(
                    cache.swap_in(seq.group_swap.pop(name)))
        if self.slot_group is not None and self.slot_group.name in seq.group_swap:
            leaves = seq.group_swap.pop(self.slot_group.name)
            slot = self.take_slot(seq)
            self.slot_pools = {
                name: pool.at[:, slot].set(leaf) for (name, pool), leaf in
                zip(self.slot_pools.items(), leaves)}
        self.swap_ins += 1

    def blocks_to_resume(self, uid):
        seq = self._seqs[uid]
        return seq.swap_handle["n"] if seq.is_swapped else 0

    def further_groups_fit_resume(self, uid):
        """Whether the further groups have room for what ``uid`` took to the
        host (``blocks_to_resume`` answers for the "kv" group): its window
        pages and one more a group, and a slot."""
        seq = self._seqs[uid]
        for name, (_, cache) in self.paged_groups.items():
            if name in seq.group_swap and \
                    cache.free_blocks < seq.group_swap[name]["n"] + 1:
                return False
        if self.slot_group is not None and \
                self.slot_group.name in seq.group_swap:
            return bool(self._free_slots)
        return True

    # -- block arithmetic --------------------------------------------------
    def blocks_needed(self, seq, new_tokens):
        """Extra blocks required to grow ``seq`` by ``new_tokens``."""
        return self.blocks_needed_for(seq.seen_tokens, seq.cur_allocated_blocks,
                                      new_tokens, self.kv_block_size)

    def ensure_capacity(self, seq, new_tokens):
        extra = self.blocks_needed(seq, new_tokens)
        if extra:
            seq.extend_blocks(self.kv_cache.reserve(extra))
        if not self.has_further_groups:
            return
        self.take_slot(seq)
        need = self.further_blocks_needed(seq, seq.seen_tokens, new_tokens)
        for name, (g, cache) in self.paged_groups.items():
            if name not in seq.group_base:
                seq.group_base[name] = self.first_live_block(g, seq.seen_tokens)
                seq.group_blocks[name] = []
            if need[name]:
                seq.group_blocks[name].extend(cache.reserve(need[name]))
