"""Cold-KV swap: host-tier spill/restore for preempted sequences.

PR-8 KV-pressure preemption pays a full prefill recompute at resume —
the worst cell in the perf table (0.44 tok/s @32k) is mostly that bill.
This module turns preemption into *swap*: before the scheduler flushes a
victim, its cold pages (ranked by PR-18 ``page_heat`` age, coldest
first) are exported in ``kv_ship`` canonical row space and parked in the
:class:`~deepspeed_tpu.runtime.swap_tensor.host_tier.HostPageTier`;
resume becomes an H2D copy + page-table patch (``import_kv``) and the
stream continues bit-exactly from the saved seed token.

Sharing one codec with the wire is the point: a spilled page IS a
``KVShipment`` row slab, so the host tier, disaggregated-prefill
shipping, and (future) NVMe all speak the same layout, and the
re-attestation built into ``import_kv`` (tokens must match the resuming
prompt) guards swap the same way it guards cross-replica grafts.

The radix prefix cache composes: under host-tier pressure its evictions
spill shared full pages here instead of dropping them
(:meth:`KVSwapManager.spill_prefix_node`, installed as
``RadixPrefixCache.spill_fn``), and ``graft_prefix`` extends a device
trie match through host-resident pages — a host tier multiplies how many
shared prefixes survive eviction.

Every failure path degrades to the pre-tier behavior (evict + prefill
recompute), which is slower but equally bit-exact; the ``kv_swap_out`` /
``kv_swap_in`` / ``host_alloc`` fault sites force those paths in the
chaos tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ....runtime.fault import injection
from ....utils.logging import logger
from ..kv_ship import KVShipment, export_kv, import_kv


@dataclasses.dataclass
class SwapEntry:
    """Book-keeping for one swapped-out sequence."""

    tokens: List[int]        # attested ids covering the spilled rows
    n_tokens: int
    nbytes: int


class KVSwapManager:
    """Spill/restore coordinator between one engine and the host tier.

    Owned by :class:`~deepspeed_tpu.inference.v2.engine_v2.InferenceEngineV2`
    when ``config.host_tier_mb > 0``; driven by the lifecycle scheduler at
    preempt (``spill``) and reserve (``restore``) time.  All calls run on
    the scheduler thread, same discipline as the allocator.
    """

    def __init__(self, engine, tier):
        self.eng = engine
        self.tier = tier
        self._entries: Dict[int, SwapEntry] = {}
        self.swapped_out = 0
        self.swapped_in = 0
        self.misses = 0
        self.spill_failures = 0
        self.swap_in_bytes = 0
        self.avoided_recompute_tokens = 0
        self.prefix_spilled = 0
        self.prefix_restored = 0

    # ------------------------------------------------------------------ #
    # Geometry helpers
    # ------------------------------------------------------------------ #
    def _page_row_bytes(self) -> int:
        """Bytes one logical page occupies in canonical row space (all
        layers, K+V, float32)."""
        return (self.eng.family.page_layers * self.eng.config.block_size
                * self.eng.family.row.read_values * 4)

    # ------------------------------------------------------------------ #
    # Sequence spill / restore
    # ------------------------------------------------------------------ #
    def spill(self, uid: int, tokens: List[int]) -> int:
        """Export ``uid``'s coldest contiguous prefix into the host tier.

        Called by the scheduler BEFORE it flushes the preemption victim
        (the export is a pure read).  Page selection is coldest-first by
        heat age, capped at what the tier can hold, then reduced to the
        longest contiguous page-prefix — restore grafts a token-contiguous
        prefix starting at token 0, so a kept page is only useful if every
        earlier page is kept too.  Returns the number of tokens parked
        (0 = nothing spilled; caller falls back to plain evict)."""
        seq = self.eng.state_manager.get_sequence(uid)
        if seq is None or seq.seen_tokens == 0:
            return 0
        n_max = min(len(tokens), seq.seen_tokens)
        if n_max <= 0:
            return 0
        bs = self.eng.config.block_size
        n_pages = -(-n_max // bs)
        pages = list(seq.blocks[:n_pages])
        heat = getattr(self.eng, "heat", None)
        ages = (heat.page_ages_for(pages) if heat is not None
                else np.zeros(len(pages), dtype=np.int64))
        page_bytes = self._page_row_bytes()
        budget = self.tier.capacity_bytes
        # coldest first; ties broken toward EARLIER pages, which are the
        # ones a contiguous-prefix restore can actually use
        order = sorted(range(len(pages)), key=lambda i: (-int(ages[i]), i))
        admitted = set()
        spent = 0
        for i in order:
            if spent + page_bytes > budget:
                break
            admitted.add(i)
            spent += page_bytes
        k = 0
        while k in admitted:
            k += 1
        if k == 0:
            return 0
        n_spill = min(n_max, k * bs)
        try:
            ship = export_kv(self.eng, uid, tokens, n_tokens=n_spill)
            if not self.tier.put(("kv", uid), ship.rows):
                return 0
        except (injection.InjectedSwapFailure, OSError) as e:
            self.spill_failures += 1
            self.misses += 1
            logger.warning(f"kv swap: spill of uid={uid} failed ({e}); "
                           f"falling back to evict+recompute")
            return 0
        self._entries[uid] = SwapEntry(tokens=list(ship.tokens),
                                       n_tokens=ship.n_tokens,
                                       nbytes=int(ship.rows.nbytes))
        self.swapped_out += 1
        logger.info(f"kv swap: spilled uid={uid} n={ship.n_tokens} tokens "
                    f"({ship.rows.nbytes} B, {k}/{len(pages)} pages)")
        return ship.n_tokens

    def restore(self, uid: int, resume_prompt: List[int]) -> int:
        """Graft ``uid``'s parked rows back as a fresh sequence.

        Returns tokens restored (``req._prefill_pos`` for the caller); 0
        means the caller must recompute — EXCEPT when an entry still
        exists (transient device-pool exhaustion: the caller should
        backpressure and retry, the parked rows remain valid)."""
        entry = self._entries.get(uid)
        if entry is None:
            return 0
        try:
            injection.inject("kv_swap_in")
        except (injection.InjectedSwapFailure, OSError) as e:
            self.drop(uid)
            self.misses += 1
            logger.warning(f"kv swap: restore of uid={uid} failed ({e}); "
                           f"recomputing prefill")
            return 0
        rows = self.tier.get(("kv", uid))
        if rows is None:                      # LRU-evicted under pressure
            self._entries.pop(uid, None)
            self.misses += 1
            return 0
        # >= 1 token must go through a real forward (logits for the next
        # token), mirroring the kv_import invariant; the decode seed
        # itself rides req._resume_seed, so bit-exactness is untouched.
        n = min(entry.n_tokens, len(resume_prompt) - 1)
        if n <= 0 or entry.tokens[:n] != list(resume_prompt[:n]):
            self.drop(uid)
            self.misses += 1
            logger.warning(f"kv swap: uid={uid} parked rows fail "
                           f"re-attestation; recomputing prefill")
            return 0
        row = self.eng.family.row
        ship = KVShipment(tokens=list(entry.tokens[:n]),
                          num_layers=self.eng.family.page_layers,
                          num_kv_heads=row.num_kv_heads,
                          head_dim=row.head_dim,
                          src_block_size=self.eng.config.block_size,
                          wire="fp32", rows=rows[:, :n])
        if not import_kv(self.eng, ship, uid):
            return 0          # transient exhaustion: entry kept, retry
        self.tier.pop(("kv", uid))
        self._entries.pop(uid, None)
        self.swapped_in += 1
        self.swap_in_bytes += int(ship.rows.nbytes)
        self.avoided_recompute_tokens += n
        return n

    def entry(self, uid: int) -> Optional[SwapEntry]:
        return self._entries.get(uid)

    def drop(self, uid: int) -> None:
        """Terminal cleanup (request retired/cancelled while parked)."""
        self._entries.pop(uid, None)
        self.tier.discard(("kv", uid))

    # ------------------------------------------------------------------ #
    # Prefix-cache spill path
    # ------------------------------------------------------------------ #
    def spill_prefix_node(self, node) -> None:
        """``RadixPrefixCache.spill_fn`` hook: called by ``_drop`` just
        before the trie frees an evicted page.  Full pages are parked
        keyed by their root-path token tuple so ``graft_prefix`` can pull
        them back; partial tail pages are not worth a host round-trip."""
        bs = self.eng.config.block_size
        if node.claim != bs or len(node.tokens) != bs:
            return
        path: Tuple[int, ...] = ()
        walk = node
        chain = []
        while walk is not None and walk.tokens:
            chain.append(walk.tokens)
            walk = walk.parent
        for seg in reversed(chain):
            path = path + tuple(seg)
        import jax.numpy as jnp
        c = self.eng.kv.config
        nb = c.num_blocks
        phys = np.asarray([node.block + layer * nb
                           for layer in range(self.eng.family.page_layers)],
                          np.int64)
        rows = np.asarray(self.eng.kv.pages[jnp.asarray(phys)], np.float32)
        try:
            if self.tier.put(("prefix", path), rows):
                self.prefix_spilled += 1
        except (injection.InjectedSwapFailure, OSError):
            self.spill_failures += 1

    def peek_prefix(self, path: Tuple[int, ...]) -> Optional[np.ndarray]:
        """Rows ``[L, block_size, 2*KV, HD]`` for a spilled prefix page,
        or None.  Pure lookup; call :meth:`confirm_prefix` once grafted."""
        rows = self.tier.get(("prefix", tuple(path)))
        return rows

    def confirm_prefix(self, path: Tuple[int, ...]) -> None:
        self.tier.pop(("prefix", tuple(path)))
        self.prefix_restored += 1

    # ------------------------------------------------------------------ #
    # Telemetry
    # ------------------------------------------------------------------ #
    def stats(self) -> Dict[str, Any]:
        hits = self.swapped_in
        total = hits + self.misses
        return {
            "swapped_out": self.swapped_out,
            "swapped_in": hits,
            "misses": self.misses,
            "spill_failures": self.spill_failures,
            "hit_rate": hits / max(1, total) if total else 1.0,
            "swap_out_bytes": self.tier.swap_out_bytes,
            "swap_in_bytes": self.swap_in_bytes,
            "avoided_recompute_tokens": self.avoided_recompute_tokens,
            "prefix_spilled": self.prefix_spilled,
            "prefix_restored": self.prefix_restored,
            "entries": len(self._entries),
            "host_used_bytes": self.tier.used_bytes,
            "host_capacity_bytes": self.tier.capacity_bytes,
        }
