"""Expert offloading: host-resident expert store + device-resident slot
buffer (the TPU adaptation of the paper's VRAM expert cache, DESIGN.md §4).

HostExpertStore keeps every MoE layer's expert weights as host numpy arrays
(= "host DRAM"). SlotBuffer is a fixed-capacity stack of expert weight slots
living on device (= "HBM"); fetching an expert is a host->device
``device_put`` into a slot. The control plane (which expert sits in which
slot, eviction order, prefetch decisions) is core.cache.ExpertCache.
HostExpertStore is also the single-host degenerate of the expert-store
interface — serving/expertstore.py generalises it to the tiered
device/host/peer/disk hierarchy behind the same ``fetch``/``demote``
calls, which is why SlotBuffer routes through ``store.fetch`` and demotes
on release.

Overlap model: the engines prefetch predicted experts before the layers
that need them run, double-buffering the slot stack — filled slots for
layer i+1 land while layer i computes (slow-tier experts are submitted
additional layers early, see the horizon-aware prefetch in
serving/engine.py). OverlapTracker models one serial async channel *per
storage tier* against a shared compute clock: ``submit`` queues a transfer
on its tier's channel, ``advance`` credits compute time that hides it,
``wait`` charges only the un-overlapped remainder as stall, attributed to
the critical tier (``stall_by_tier``). With zero credited compute the
stall degenerates to the blocking demand-fetch model
(``SlotBuffer.sim_fetch_s``) — tests pin both ends.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import ExpertCache
from repro.serving.telemetry import (NULL_TELEMETRY, PID_CHANNELS,
                                     PID_ENGINE)

Key = Tuple[int, int]  # (moe_layer_index, expert_id)

# Storage tiers, from the serving process's point of view. Tier 0 (the
# device slot buffer) is the ExpertCache/SlotBuffer's business; a *store*
# serves fetches from tier 1 (local host DRAM), tier 2 (a peer host's DRAM
# over the interconnect) or tier 3 (disk/mmap spill). HostExpertStore is
# the degenerate single-host store: everything is tier 1. The full
# hierarchy lives in serving/expertstore.py.
TIER_DEVICE, TIER_HOST, TIER_PEER, TIER_DISK = 0, 1, 2, 3

# Pseudo-tier for the OverlapTracker's *ship* channel: instead of fetching
# a peer-resident expert's weights (tier 2), the engine may ship the token
# activations to the peer, compute the expert FFN there, and pull the
# outputs back (serving/expertstore.DispatchPlanner prices the two paths).
# Ship traffic rides its own serial channel so stall/overlap attribution
# separates "waiting on weights" from "waiting on remote compute".
CHANNEL_SHIP = 4

# Telemetry track names for the per-tier serial channels (the async
# tracks a Chrome-trace export shows under the "channels" process)
CHANNEL_NAMES = {
    TIER_HOST: "tier1 host->device",
    TIER_PEER: "tier2 peer->device",
    TIER_DISK: "tier3 disk->device",
    CHANNEL_SHIP: "ship tokens->peer",
}


@dataclass
class FetchInfo:
    """Where a store served an expert fetch from, and the modeled cost.

    ``duration`` is the modeled transfer time for the whole path into the
    device slot; ``None`` means "use the caller's host-bandwidth model"
    (the single-host back-compat default)."""
    tier: int
    nbytes: int
    duration: Optional[float] = None


class HostExpertStore:
    """Expert FFN weights per MoE layer, host-side.

    Also the reference implementation of the *expert store* interface the
    engines consume (``fetch``/``tier_of``/``demote``/``prefetch_horizon``):
    one host's DRAM holds every expert, so every fetch is a tier-1 hit and
    there is nothing to demote into. ``serving/expertstore.py`` generalises
    this to the device/host/peer/disk hierarchy behind the same interface.
    """

    #: how many MoE layers ahead prefetch needs to look for this store —
    #: one layer of compute is enough to hide a host->device transfer
    max_horizon = 1

    def __init__(self, expert_params_per_layer):
        """expert_params_per_layer: list (per MoE layer) of dicts with
        w_gate/w_up/w_down of shape (E, d, f)/(E, d, f)/(E, f, d)."""
        self.layers = [
            {k: np.asarray(v) for k, v in lp.items()
             if k in ("w_gate", "w_up", "w_down")}
            for lp in expert_params_per_layer
        ]
        self.num_layers = len(self.layers)
        self.num_experts = self.layers[0]["w_gate"].shape[0]
        lp = self.layers[0]
        self.bytes_per_expert = sum(
            lp[k][0].nbytes for k in ("w_gate", "w_up", "w_down"))

    def get(self, key: Key):
        layer, e = key
        lp = self.layers[layer]
        return (lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])

    # --- store interface --------------------------------------------------
    def fetch(self, key: Key):
        """(weights, FetchInfo): everything lives in local DRAM."""
        w = self.get(key)
        return w, FetchInfo(TIER_HOST, self.bytes_per_expert)

    def tier_of(self, key: Key) -> int:
        return TIER_HOST

    def prefetch_horizon(self, key: Key) -> int:
        return 1

    def demote(self, key: Key) -> None:
        """Tier-0 eviction callback: the DRAM copy already exists."""


class OverlapTracker:
    """Modeled timeline of the async fetch channels against a compute clock.

    ``clock`` is modeled compute time. Each storage *tier* owns one serial
    fetch channel (host->device DMA, the peer interconnect, the disk queue);
    transfers submitted to a tier queue on that tier's ``channel_free``
    while different tiers' transfers overlap each other. A transfer
    submitted at compute time t starts at max(t, channel_free[tier]) and
    completes ``duration`` later. ``wait`` advances the clock to the
    completion time of the latest needed transfer, charging the gap as
    stall — exactly the part of the fetch NOT hidden by compute — and
    attributes that stall to the tier of the transfer that finished last
    (the critical path), so stall reports break down by tier.

    The single-tier default (every ``submit`` at tier 1, duration from
    ``host_bw``) reproduces the original one-serial-channel model exactly.

    Identical pending keys coalesce: when a key is re-submitted while its
    previous transfer is still on the wire (the slot was dropped before
    the modeled completion, then the key was demanded again), the new
    request rides the in-flight transfer instead of queueing a second
    serial one — unless a fresh fetch would land *earlier* (the store may
    now serve the key from a faster tier), in which case the fresh fetch
    wins. ``fetches_deduped`` counts the coalesced submissions.
    """

    def __init__(self, host_bw: float = 100e9, telemetry=None):
        self.host_bw = host_bw
        # telemetry: each real (non-coalesced) submission becomes one
        # "X" event on its tier's channel track, timed on the MODELED
        # clock — serial channels make each track's timestamps monotonic
        self.tel = telemetry if telemetry is not None else NULL_TELEMETRY
        self.clock = 0.0
        self._channel_free: Dict[int, float] = {}  # tier -> busy-until time
        self.pending: Dict[Key, float] = {}   # key -> modeled completion time
        self._dur: Dict[Key, float] = {}      # key -> transfer duration
        self._tier: Dict[Key, int] = {}       # key -> submitting tier
        # key -> (completion, duration, tier) of the latest transfer put on
        # the wire, surviving ``drop``: bytes in flight don't vanish when
        # their slot is released, so a re-submit can ride them
        self._wire: Dict[Key, Tuple[float, float, int]] = {}
        self.fetches_deduped = 0
        self.stall_s = 0.0
        self.overlapped_s = 0.0               # transfer time hidden by compute
        self.stall_by_tier: Dict[int, float] = {}
        self.overlapped_by_tier: Dict[int, float] = {}

    @property
    def channel_free(self) -> float:
        """Latest busy-until time across tier channels (back-compat view
        of the original single-channel attribute)."""
        return max(self._channel_free.values(), default=0.0)

    def submit(self, key: Key, nbytes: int, tier: int = TIER_HOST,
               duration: Optional[float] = None,
               coalesce: bool = True) -> bool:
        """Queue a transfer for ``key``; returns True when it coalesced
        onto an identical transfer already in flight (no new channel time
        or bytes charged). ``coalesce=False`` (the ship channel) neither
        rides nor leaves a wire record: shipped bytes are this step's
        activations/outputs, never re-servable to a later requester the
        way an in-flight weight transfer is."""
        dur = nbytes / self.host_bw if duration is None else duration
        if len(self._wire) > 4 * (len(self.pending) + 8):
            self._prune_wire()
        fresh = max(self.clock, self._channel_free.get(tier, 0.0)) + dur
        if coalesce:
            wire = self._wire.get(key)
            if wire is not None and self.clock < wire[0] <= fresh:
                # same bytes already on the wire and landing no later than
                # a fresh fetch would: ride them
                self.pending[key] = wire[0]
                self._dur[key] = wire[1]
                self._tier[key] = wire[2]
                self.fetches_deduped += 1
                return True
        self._channel_free[tier] = fresh
        self.pending[key] = fresh
        self._dur[key] = dur
        self._tier[key] = tier
        if coalesce:
            self._wire[key] = (fresh, dur, tier)
        if self.tel.enabled:
            self.tel.ensure_track(PID_CHANNELS, tier,
                                  CHANNEL_NAMES.get(tier, f"tier{tier}"))
            name = "ship" if tier == CHANNEL_SHIP else "fetch"
            self.tel.complete(PID_CHANNELS, tier, name, fresh - dur, dur,
                              {"key": str(key), "bytes": int(nbytes),
                               "tier": tier})
        return False

    def _prune_wire(self) -> None:
        """Drop wire records of transfers that have already landed."""
        self._wire = {k: v for k, v in self._wire.items()
                      if v[0] > self.clock}

    def drop(self, key: Key) -> None:
        """Forget a pending transfer (its slot was released before use).
        The wire record survives: the bytes are still in flight and a
        re-submit may coalesce onto them."""
        self.pending.pop(key, None)
        self._dur.pop(key, None)
        self._tier.pop(key, None)

    def advance(self, compute_s: float) -> None:
        """Compute time that overlaps any in-flight transfers."""
        self.clock += compute_s

    def wait(self, keys: Iterable[Key]) -> float:
        """Block until every needed key's transfer has landed; returns the
        stall charged for this wait."""
        needed = [k for k in keys if k in self.pending]
        if not needed:
            return 0.0
        done = {k: self.pending.pop(k) for k in needed}
        t = max(done.values())
        crit_tier = self._tier.get(max(done, key=done.get), TIER_HOST)
        stall = max(0.0, t - self.clock)
        self.stall_s += stall
        self.stall_by_tier[crit_tier] = (
            self.stall_by_tier.get(crit_tier, 0.0) + stall)
        if self.tel.enabled and stall > 0:
            self.tel.instant(PID_ENGINE, 1, "stall",
                             {"stall_s": stall,
                              "critical_tier": crit_tier})
        # transfer time not hidden by compute is stall; distribute the
        # hidden remainder over tiers, absorbing the stall into the
        # latest-completing transfers first (the critical path)
        remaining = stall
        for k in sorted(needed, key=done.get, reverse=True):
            dur = self._dur.pop(k, 0.0)
            tier = self._tier.pop(k, TIER_HOST)
            absorbed = min(dur, remaining)
            remaining -= absorbed
            self.overlapped_s += dur - absorbed
            self.overlapped_by_tier[tier] = (
                self.overlapped_by_tier.get(tier, 0.0) + dur - absorbed)
        self.clock = max(self.clock, t)
        return stall


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _write_slot(wg_buf, wu_buf, wd_buf, slot, wg, wu, wd):
    """Copy one expert's weights into row ``slot`` of the three slot
    buffers. The buffers are donated, so the write lands in place instead
    of copying the whole ``(slots, d, f)`` stack per fill."""
    return (wg_buf.at[slot].set(wg), wu_buf.at[slot].set(wu),
            wd_buf.at[slot].set(wd))


class SlotBuffer:
    """Fixed-capacity device buffer of expert slots + host slot table.

    ``store`` is anything implementing the expert-store interface
    (``HostExpertStore`` or ``serving/expertstore.TieredExpertStore``):
    ``fill`` pulls weights through ``store.fetch`` — charging the modeled
    transfer to the source tier's channel — and ``release`` (the tier-0
    eviction callback) *demotes* the expert into the store's host-side
    cache instead of dropping it, so a re-fetch is served from tier 1
    rather than the slow tier it originally came from.

    ``ship_slots`` appends that many *ephemeral* rows past the
    cache-managed ``n_slots``: the compute-dispatch path (``dispatch=
    "ship"``/``"auto"``) stages a peer-resident expert's weights there for
    exactly one expert-FFN program — the rows model the peer's own copy,
    are never registered in ``slot_of``/the ExpertCache, charge no fetch
    bytes, and are overwritten freely by the next step's shipped group.
    Running the shipped experts through the SAME jitted expert
    program as resident ones is what keeps fetch/ship streams bit
    identical."""

    def __init__(self, store: HostExpertStore, n_slots: int,
                 host_bw: float = 100e9,
                 tracker: Optional[OverlapTracker] = None,
                 ship_slots: int = 0, telemetry=None):
        lp = store.layers[0]
        e, d, f = lp["w_gate"].shape
        self.store = store
        self.n_slots = n_slots
        self.ship_slots = ship_slots
        self.host_bw = host_bw
        self.tracker = tracker
        self.tel = telemetry if telemetry is not None else NULL_TELEMETRY
        rows = n_slots + ship_slots
        self.w_gate = jnp.zeros((rows, d, f), lp["w_gate"].dtype)
        self.w_up = jnp.zeros((rows, d, f), lp["w_up"].dtype)
        self.w_down = jnp.zeros((rows, f, d), lp["w_down"].dtype)
        self.slot_of: Dict[Key, int] = {}
        self._free = list(range(n_slots))
        self.fetch_bytes = 0
        self.fetch_count = 0
        self.fetches_deduped = 0     # fills that rode an in-flight transfer
        self.sim_fetch_s = 0.0       # blocking model: every fetch stalls

    # --- control-plane callbacks wired into ExpertCache -------------------
    def release(self, key: Key) -> None:
        slot = self.slot_of.pop(key)
        self._free.append(slot)
        if self.tracker is not None:
            self.tracker.drop(key)
        self.store.demote(key)

    def fill(self, key: Key) -> None:
        slot = self._free.pop()
        self.slot_of[key] = slot
        with self.tel.span(PID_ENGINE, 1, "store.fill"):
            (wg, wu, wd), info = self.store.fetch(key)
            self._write(slot, wg, wu, wd)
        nbytes = wg.nbytes + wu.nbytes + wd.nbytes
        dur = (info.duration if info.duration is not None
               else nbytes / self.host_bw)
        coalesced = False
        if self.tracker is not None:
            coalesced = self.tracker.submit(key, nbytes, tier=info.tier,
                                            duration=dur)
        if coalesced:
            # the key's bytes were already in flight on this tier's channel
            # (slot released before the modeled transfer completed): no new
            # traffic is charged
            self.fetches_deduped += 1
        else:
            self.fetch_bytes += nbytes
            self.fetch_count += 1
        # the blocking model has no in-flight transfers to ride, so every
        # fetch stalls fully — keep it the upper bound
        self.sim_fetch_s += dur

    def fill_ship(self, idx: int, weights) -> int:
        """Stage shipped-expert weights in ephemeral row ``idx`` (0-based
        within the ship region); returns the absolute slot id to feed the
        expert program. No slot table entry, no fetch accounting — the
        modeled cost of the round trip is the ship channel's business
        (``OverlapTracker.submit`` at ``CHANNEL_SHIP``)."""
        assert 0 <= idx < self.ship_slots, \
            f"ship row {idx} out of range (ship_slots={self.ship_slots})"
        slot = self.n_slots + idx
        self._write(slot, *weights)
        return slot

    def _write(self, slot: int, wg, wu, wd) -> None:
        self.w_gate, self.w_up, self.w_down = _write_slot(
            self.w_gate, self.w_up, self.w_down, np.asarray(slot, np.int32),
            wg, wu, wd)

    def gather(self, keys) -> tuple:
        """Return (k, ...) stacked expert weights for resident keys."""
        slots = jnp.asarray([self.slot_of[k] for k in keys], jnp.int32)
        return (jnp.take(self.w_gate, slots, 0),
                jnp.take(self.w_up, slots, 0),
                jnp.take(self.w_down, slots, 0))


def make_offload_cache(store: HostExpertStore, capacity: int,
                       eviction: str = "lru", host_bw: float = 100e9,
                       tracker: Optional[OverlapTracker] = None,
                       scorer=None, ship_slots: int = 0, telemetry=None):
    """(ExpertCache, SlotBuffer) wired together. ``scorer`` (a
    ``core.policies.ReuseDistanceScorer``) is required for
    ``eviction="learned"`` — the engine feeds it the multi-horizon
    prediction window so tier-0 eviction picks the key predicted furthest
    from reuse. ``ship_slots`` sizes the buffer's ephemeral
    compute-dispatch rows (see :class:`SlotBuffer`). ``telemetry`` (a
    ``serving.telemetry.Telemetry``) lets the cache report evictions with
    victim provenance and the buffer time each fill (``store.fill``)."""
    buf = SlotBuffer(store, capacity, host_bw, tracker,
                     ship_slots=ship_slots, telemetry=telemetry)
    cache = ExpertCache(capacity, eviction, on_evict=buf.release,
                        on_insert=buf.fill, scorer=scorer,
                        telemetry=telemetry)
    return cache, buf
