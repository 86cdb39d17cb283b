"""Device-resident proposal pool: dense slot-indexed consensus state.

The pool is the TPU analogue of the reference's per-scope session maps
(reference: src/storage.rs:188-194): a fixed-capacity, structure-of-arrays
store of ``P`` proposal slots × ``V`` voter lanes living in device HBM.
Scalar per-session control flow becomes batched scatter/scan/gather kernels
(:mod:`hashgraph_tpu.ops`); the host keeps only the irregular bookkeeping XLA
cannot express with static shapes — the free list, slot↔proposal mapping,
owner-bytes→voter-lane dictionaries, and expiry timestamps.

Design notes (TPU):
- fixed capacity + power-of-two batch buckets: array shapes never vary with
  load, so each kernel compiles once per (pool geometry, bucket);
- buffer donation on every mutation: the pool state is updated in place in
  HBM, no copy-on-write traffic;
- readbacks are narrow: ingest returns per-vote statuses and touched-slot
  states only; full-row gathers (:meth:`ProposalPool.read_slot`) are a cold
  query path;
- the host mirrors the ``state`` vector (updated from kernel readbacks, never
  re-fetched) so stats and transition detection cost no device traffic;
- device work is isolated behind ``_dispatch_*`` hooks: the multi-device pool
  (:mod:`hashgraph_tpu.parallel`) overrides only those, inheriting all host
  bookkeeping.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Hashable

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import (
    POOL_ACTIVATE_DISPATCHES_TOTAL,
    POOL_ACTIVATED_SLOTS_TOTAL,
    observed_span,
    registry,
)
from ..ops.decide import (
    STATE_ACTIVE,
    STATE_FREE,
    timeout_kernel,
)
from ..ops.ingest import (
    fresh_ingest_kernel,
    fresh_ingest_laneless_kernel,
    group_batch,
    ingest_kernel,
    pack_grid,
    pack_slots,
)
from ..tracing import tracer as default_tracer

__all__ = ["ProposalPool", "SlotMeta", "PoolFullError"]


class PoolFullError(RuntimeError):
    """The pool has no free slots (capacity P exhausted)."""


def _bucket(size: int, floor: int = 8) -> int:
    """Round a batch dimension up to a power-of-two bucket so XLA compiles
    one program per bucket, not one per batch shape. Pad entries use the
    out-of-range slot sentinel (scatters drop, gathers clip) or
    ``valid=False`` cells, so padding is semantically inert."""
    return max(floor, 1 << max(size - 1, 0).bit_length())


def _pad_slot_ids(slots: np.ndarray, bucket: int, sentinel: int) -> np.ndarray:
    out = np.full(bucket, sentinel, np.int32)
    out[: len(slots)] = slots
    return out


def _pad1(arr: np.ndarray, bucket: int, dtype) -> np.ndarray:
    out = np.zeros(bucket, dtype)
    out[: len(arr)] = np.asarray(arr, dtype)
    return out


def _pad2(arr: np.ndarray, rows: int, cols: int, dtype) -> np.ndarray:
    out = np.zeros((rows, cols), dtype)
    out[: arr.shape[0], : arr.shape[1]] = arr
    return out


@dataclass
class PendingIngest:
    """An in-flight ingest dispatch: the device output plus the host-side
    coordinates needed to interpret it. Lets callers pipeline many dispatches
    (device work and transfers overlap) and pay the readback latency once —
    essential wherever each host↔device sync costs a round trip."""

    out: object  # device int32[rows, L+1]: statuses + final row state
    uniq: np.ndarray  # [S] touched slots
    row: np.ndarray  # [B] batch item -> grid row
    col: np.ndarray  # [B] batch item -> grid col
    row_select: np.ndarray  # routed-row indexer: out[row_select] -> [S, :]


@dataclass(slots=True)
class SlotMeta:
    """Host-side bookkeeping for one allocated slot. Voter-lane assignments
    live in the pool's dense ``_lane_gids``/``_lane_count`` tables (shared by
    the scalar and columnar resolution paths), not here."""

    key: Hashable  # engine-level key, e.g. (scope, proposal_id)
    expiry: int  # absolute expiration timestamp (seconds)
    created_at: int


def activate_body(
    state,
    yes,
    tot,
    vote_mask,
    vote_val,
    n,
    req,
    cap,
    gossip,
    liveness,
    slot_ids,
    n_new,
    req_new,
    cap_new,
    gossip_new,
    live_new,
):
    """Claim slots for new proposals: reset tallies, write per-slot config.
    (Body form reused inside shard_map blocks by the multi-device pool.)"""
    put = lambda arr, val: arr.at[slot_ids].set(val, mode="drop")
    state = put(state, STATE_ACTIVE)
    yes = put(yes, 0)
    tot = put(tot, 0)
    vote_mask = put(vote_mask, False)
    vote_val = put(vote_val, False)
    n = put(n, n_new)
    req = put(req, req_new)
    cap = put(cap, cap_new)
    gossip = put(gossip, gossip_new)
    liveness = put(liveness, live_new)
    return state, yes, tot, vote_mask, vote_val, n, req, cap, gossip, liveness


def load_body(
    state,
    yes,
    tot,
    vote_mask,
    vote_val,
    slot_ids,
    state_rows,
    yes_rows,
    tot_rows,
    mask_rows,
    val_rows,
):
    """Snapshot-restore tallies into already-activated slots (resume path:
    a network proposal arrives carrying validated votes, reference:
    src/session.rs:198-221 replays them — here the host replays through the
    scalar oracle and loads the resulting dense rows)."""
    put = lambda arr, rows: arr.at[slot_ids].set(rows, mode="drop")
    return (
        put(state, state_rows),
        put(yes, yes_rows),
        put(tot, tot_rows),
        put(vote_mask, mask_rows),
        put(vote_val, val_rows),
    )


def release_body(state, slot_ids):
    return state.at[slot_ids].set(STATE_FREE, mode="drop")


_activate_kernel = partial(jax.jit, donate_argnums=tuple(range(10)))(activate_body)
_load_kernel = partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))(load_body)
_release_kernel = partial(jax.jit, donate_argnums=(0,))(release_body)


@jax.jit
def _stack_kernel(*xs):
    """Stack same-shape dispatch outputs into one transferable array.
    Jitted: eager jnp.stack dispatches one broadcast per operand (~10ms of
    dispatch overhead each on a latency-bound link); this is a single call."""
    return jnp.stack(xs)


@jax.jit
def _read_kernel(state, yes, tot, vote_mask, vote_val, slot_id):
    take = lambda arr: jnp.take(arr, slot_id, axis=0, mode="clip")
    return take(state), take(yes), take(tot), take(vote_mask), take(vote_val)


class ProposalPool:
    """Fixed-capacity device pool of consensus proposal slots.

    ``capacity`` (P) bounds concurrent proposals; ``voter_capacity`` (V)
    bounds ``expected_voters_count`` per proposal. All mutating methods are
    batched; statuses and transitions are returned per call with no global
    readbacks.
    """

    def __init__(
        self, capacity: int, voter_capacity: int, use_pallas: bool | None = None
    ):
        if capacity < 1 or voter_capacity < 1:
            raise ValueError("capacity and voter_capacity must be >= 1")
        self.capacity = capacity
        self.voter_capacity = voter_capacity
        if use_pallas is None:
            use_pallas = os.environ.get("HASHGRAPH_TPU_PALLAS", "") == "1"
        self._ingest_kernel = ingest_kernel
        self._use_pallas = use_pallas
        if use_pallas:
            from ..ops.pallas_ingest import pallas_ingest_body

            self._ingest_kernel = partial(
                jax.jit, donate_argnums=(0, 1, 2, 3, 4)
            )(
                partial(
                    pallas_ingest_body,
                    interpret=jax.default_backend() != "tpu",
                )
            )
            # Keep the pallas A/B meaningful: with the opt-in kernel active
            # the engine must not silently route its dominant fast path to
            # the XLA closed-form kernel instead.
            self.supports_fresh_ingest = False
        # Spans (``pool.activate``) and counters of slot activation; the
        # sharded pool adds its routing's.
        self.tracer = default_tracer
        self._m_activated = registry.counter(POOL_ACTIVATED_SLOTS_TOTAL)
        self._m_activate_dispatches = registry.counter(
            POOL_ACTIVATE_DISPATCHES_TOTAL
        )
        # Activation device writes held by gather_activations (None outside
        # the scope): (slots, n, req, cap, gossip, liveness) per call.
        self._gathered: list[tuple[np.ndarray, ...]] | None = None
        self._init_device_arrays()

        # Host mirrors / bookkeeping.
        self._state_host = np.full(capacity, STATE_FREE, np.int32)
        self._expiry_host = np.zeros(capacity, np.int64)
        self._free: list[int] = list(range(capacity - 1, -1, -1))
        self._meta: dict[int, SlotMeta] = {}
        # Voter identity registry + dense lane tables. Owners are interned
        # once to a global integer id (exact-bytes dictionary — SURVEY §7:
        # duplicate-owner detection must not rely on a collidable hash);
        # per-slot lane assignment is first-come order in ``_lane_gids``
        # rows, resolvable one vote at a time (lane_for) or as a flat
        # vectorized batch (lanes_for_batch, the columnar hot path).
        self._gid_of: dict[bytes, int] = {}
        self._owners: list[bytes] = []
        # Registry bound: per-gid count of live slot-lane references. A gid
        # whose last referencing slot is released drops its owner mapping and
        # the id is recycled, so a long-lived pool churning through rotating
        # voter populations holds only the currently-live identities (plus
        # interned-but-never-voted ids, reclaimable via
        # clear_voter_registry at a quiesce point). numpy arrays (geometric
        # growth) keep the refcount bumps vectorized on the columnar path;
        # _gid_live distinguishes mapped ids from freed ones, and _gid_gen
        # counts how many times each index has been evicted: the public gid
        # is ``generation << 32 | index``, so a stale gid held across a
        # release AND a recycling re-intern never equals the new claimant's
        # gid — stale use is a typed rejection, not silent misattribution.
        self._gid_refs = np.zeros(0, np.int64)
        self._gid_live = np.zeros(0, bool)
        self._gid_gen = np.zeros(0, np.int64)
        # Generations start at this floor; clear_voter_registry raises it
        # past every generation ever minted so pre-clear gids can never
        # equal a post-clear claimant's gid.
        self._gen_floor = 0
        self._free_gids: list[int] = []
        self._lane_gids = np.full((capacity, voter_capacity), -1, np.int32)
        self._lane_count = np.zeros(capacity, np.int32)
        # Pipelining discipline: host mirror updates must apply in dispatch
        # order, and no other mutation may interleave with in-flight ingests
        # (the mirror would desync from the device). Enforced, not documented.
        self._inflight: list[PendingIngest] = []

    def _init_device_arrays(self) -> None:
        p, v = self.capacity, self.voter_capacity
        self._state = jnp.full(p, STATE_FREE, jnp.int32)
        self._yes = jnp.zeros(p, jnp.int32)
        self._tot = jnp.zeros(p, jnp.int32)
        self._vote_mask = jnp.zeros((p, v), bool)
        self._vote_val = jnp.zeros((p, v), bool)
        self._n = jnp.zeros(p, jnp.int32)
        self._req = jnp.zeros(p, jnp.int32)
        self._cap = jnp.zeros(p, jnp.int32)
        self._gossip = jnp.zeros(p, bool)
        self._liveness = jnp.zeros(p, bool)

    # ── Introspection ──────────────────────────────────────────────────

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def allocated_slots(self) -> int:
        return self.capacity - len(self._free)

    def meta(self, slot: int) -> SlotMeta:
        return self._meta[slot]

    # ── Voter identity / lane resolution ───────────────────────────────

    def voter_gid(self, owner: bytes) -> int:
        """Intern owner bytes to a generation-tagged global voter id
        (``generation << 32 | index``; first use assigns, indices of
        fully-released voters are recycled under a bumped generation).
        Columnar callers ship these ids instead of bytes. A gid freed by a
        release is rejected with a typed status from then on — including
        after its index is recycled to a new owner, whose gid carries a
        different generation. Holding a gid across membership-mutating
        calls is therefore safe-but-wasteful (it may start rejecting);
        re-intern per batch (a dict hit) for gids that track membership."""
        gid = self._gid_of.get(owner)
        if gid is None:
            if self._free_gids:
                gid = self._free_gids.pop()
                self._owners[gid] = owner
                self._gid_refs[gid] = 0
            else:
                gid = len(self._owners)
                self._owners.append(owner)
                if gid >= len(self._gid_refs):
                    grow = max(64, len(self._gid_refs))
                    self._gid_refs = np.concatenate(
                        [self._gid_refs, np.zeros(grow, np.int64)]
                    )
                    self._gid_live = np.concatenate(
                        [self._gid_live, np.zeros(grow, bool)]
                    )
                    self._gid_gen = np.concatenate(
                        [self._gid_gen, np.full(grow, self._gen_floor, np.int64)]
                    )
                self._gid_refs[gid] = 0
            self._gid_live[gid] = True
            self._gid_of[owner] = gid
        return (int(self._gid_gen[gid]) << 32) | gid

    def owner_of_gid(self, gid: int) -> bytes:
        """Owner bytes for a gid the caller has checked via gids_live
        (the generation tag is stripped; liveness is not re-checked)."""
        return self._owners[int(gid) & 0xFFFFFFFF]

    @property
    def voter_gid_count(self) -> int:
        """Size of the gid index-space (low 32 bits of public gids).
        Recycled indices keep this from growing with voter churn."""
        return len(self._owners)

    @property
    def live_voter_count(self) -> int:
        """Number of owner identities currently mapped to a gid."""
        return len(self._gid_of)

    def lane_owners(self, slot: int) -> dict[int, bytes]:
        """lane -> owner bytes for one slot's assigned lanes (export path)."""
        row = self._lane_gids[slot]
        out: dict[int, bytes] = {}
        for lane in range(int(self._lane_count[slot])):
            gid = int(row[lane])
            if 0 <= gid < len(self._owners) and self._gid_live[gid]:
                out[lane] = self._owners[gid]
        return out

    def gids_live(self, gids: np.ndarray) -> np.ndarray:
        """Bool mask: True where the gid currently maps an interned owner
        AND carries that index's current generation. Out-of-range ids,
        freed ids, and stale-generation ids (held across a release, even
        after the index was recycled to a new owner) are all False —
        columnar callers use this to reject stale gids with a typed status
        instead of attributing votes to the recycled index's new claimant."""
        gids = np.asarray(gids, np.int64)
        if len(gids) >= 512:
            # Fused native pass (GIL released); ~6 numpy passes otherwise.
            from .. import native as _native

            res = _native.gids_live(
                gids, self._gid_live[: len(self._owners)],
                self._gid_gen[: len(self._owners)],
            )
            if res is not None:
                return res
        idx = gids & 0xFFFFFFFF
        gen = gids >> 32
        out = np.zeros(len(gids), bool)
        ok = (gids >= 0) & (idx < len(self._owners))
        if ok.any():
            sel = idx[ok]
            out[ok] = self._gid_live[sel] & (self._gid_gen[sel] == gen[ok])
        return out

    def clear_voter_registry(self) -> None:
        """Reset the owner↔gid interning tables.

        The registry is append-only while sessions are live (gids are
        embedded in active slots' lane tables), so it grows with the
        distinct-voter population — bounded for real consensus deployments
        (a known peer set), but a long-lived pool that has churned through
        many transient identities can reclaim the memory at any quiesce
        point where no slots are allocated. Interned gids become invalid;
        columnar callers must re-intern via voter_gid."""
        if self._meta:
            raise RuntimeError(
                f"cannot clear voter registry with {len(self._meta)} slots "
                "allocated (their lane tables reference interned gids)"
            )
        # Raise the generation floor past everything ever minted: a gid
        # held across the clear must keep rejecting (typed), not become
        # bit-identical to the first post-clear claimant's gid.
        if len(self._gid_gen):
            self._gen_floor = int(self._gid_gen.max()) + 1
        self._gid_of.clear()
        self._owners.clear()
        self._gid_refs = np.zeros(0, np.int64)
        self._gid_live = np.zeros(0, bool)
        self._gid_gen = np.zeros(0, np.int64)
        self._free_gids.clear()

    def lane_for(self, slot: int, owner: bytes) -> int | None:
        """Resolve (or first-come assign) one owner's voter lane on a slot.
        Returns None when all V lanes are taken by *other* owners — the
        protocol bounds distinct voters by expected_voters_count ≤ V in P2P
        mode; Gossipsub mode accepts arbitrarily many distinct voters, so
        size ``voter_capacity`` accordingly."""
        idx = self.voter_gid(owner) & 0xFFFFFFFF  # lane tables store indices
        row = self._lane_gids[slot]
        hits = np.nonzero(row == idx)[0]
        if hits.size:
            return int(hits[0])
        count = int(self._lane_count[slot])
        if count >= self.voter_capacity:
            return None
        row[count] = idx
        self._lane_count[slot] = count + 1
        self._gid_refs[idx] += 1
        return count

    def lanes_for_batch(
        self, slots: np.ndarray, gids: np.ndarray, assume_live: bool = False
    ) -> np.ndarray:
        """Vectorized lane_for over a flat arrival-ordered batch.

        Existing assignments resolve by a dense [B, V] match; unseen
        (slot, gid) pairs are assigned fresh lanes in first-occurrence
        order. Returns int32 lanes with -1 marking voter-capacity
        exhaustion. Cost is O(B·V) int32 host work — the per-vote Python
        dictionary hop this replaces is ~50x slower per vote.

        ``assume_live=True`` skips the liveness/generation gate for callers
        that already filtered the batch through :meth:`gids_live` (the
        engine's columnar path — avoids a duplicate O(B) pass).
        """
        slots = np.asarray(slots, np.int64)
        gids_i64 = np.asarray(gids, np.int64)
        idx64 = gids_i64 & 0xFFFFFFFF
        gids32 = idx64.astype(np.int32)
        lanes = np.full(len(slots), -1, np.int32)
        if len(slots) == 0:
            return lanes
        # In-range ids are real registry indices: require live + current
        # generation, else refuse the lane (-1). A freed or stale-generation
        # gid must never claim a lane — it would be stored in _lane_gids and
        # then wrongly decrement the recycled index's refcount on slot
        # release, evicting a live voter. Out-of-range ids are synthetic
        # (direct pool callers) and pass through unrefcounted as before.
        if not assume_live:
            in_range = (gids_i64 >= 0) & (idx64 < len(self._owners))
            if in_range.any():
                ir = np.nonzero(in_range)[0]
                sel = idx64[ir]
                bad = ~(
                    self._gid_live[sel]
                    & (self._gid_gen[sel] == (gids_i64[ir] >> 32))
                )
                if bad.any():
                    keep = np.ones(len(slots), bool)
                    keep[ir[bad]] = False
                    ok_rows = np.nonzero(keep)[0]
                    lanes[ok_rows] = self.lanes_for_batch(
                        slots[ok_rows], gids_i64[ok_rows], assume_live=True
                    )
                    return lanes
        # The dense [B, V] match is only needed for votes whose slot already
        # has assignments — on fresh slots (the common streaming case) the
        # whole batch short-circuits to first-occurrence assignment.
        may_exist = self._lane_count[slots] > 0
        if may_exist.any():
            cand = np.nonzero(may_exist)[0]
            match = self._lane_gids[slots[cand]] == gids32[cand, None]
            has_c = match.any(axis=1)
            lanes[cand[has_c]] = np.argmax(match[has_c], axis=1)
        has = lanes >= 0

        rem = np.nonzero(~has)[0]
        if rem.size == 0:
            return lanes
        # One key per unseen (slot, gid); np.unique gives the first flat
        # occurrence of each, and within-slot arrival rank = lane offset.
        # Mask the gid to its unsigned 32-bit pattern: without it a gid
        # >= 2^31 sign-extends and corrupts the slot bits of the key.
        keys = (slots[rem] << 32) | (gids32[rem].astype(np.int64) & 0xFFFFFFFF)
        uniq_keys, first_pos, inverse = np.unique(
            keys, return_index=True, return_inverse=True
        )
        uslot = (uniq_keys >> 32).astype(np.int64)
        ugid = (uniq_keys & 0xFFFFFFFF).astype(np.int32)
        order = np.lexsort((first_pos, uslot))  # by slot, then arrival
        s_sorted = uslot[order]
        is_start = np.empty(len(s_sorted), bool)
        is_start[0] = True
        np.not_equal(s_sorted[1:], s_sorted[:-1], out=is_start[1:])
        grp_starts = np.nonzero(is_start)[0]
        within = np.arange(len(s_sorted)) - grp_starts[np.cumsum(is_start) - 1]
        lane_uniq = np.empty(len(uniq_keys), np.int64)
        lane_uniq[order] = self._lane_count[s_sorted] + within
        valid = lane_uniq < self.voter_capacity
        self._lane_gids[uslot[valid], lane_uniq[valid]] = ugid[valid]
        self._lane_count += np.bincount(
            uslot[valid], minlength=self.capacity
        ).astype(np.int32)
        assigned = ugid[valid].astype(np.int64)
        if assigned.size:
            # In-range ids reaching here are live current-generation indices
            # (stale and freed ids were refused above), so every stored
            # in-range reference is counted and _retire_lanes' decrement is
            # exact; synthetic out-of-range ids pass through unrefcounted
            # (and are never evicted).
            sel = assigned[(assigned >= 0) & (assigned < len(self._owners))]
            np.add.at(self._gid_refs, sel, 1)
        lanes[rem] = np.where(valid, lane_uniq, -1)[inverse].astype(np.int32)
        return lanes

    def fresh_lanes_grouped(
        self,
        s_sorted: np.ndarray,
        gid_idx_sorted: np.ndarray,
        col_sorted: np.ndarray,
        uniq: np.ndarray,
        counts: np.ndarray,
    ) -> np.ndarray | None:
        """Fast-path lane assignment for a slot-grouped batch (sorted by
        slot, arrival order within slot) targeting ALL-FRESH slots with no
        repeated (slot, voter) pair in the batch: each item's lane is then
        simply its within-slot arrival index (``col_sorted``). Returns
        int32 lanes in sorted-domain order (-1 = capacity exhausted), or
        None when the preconditions don't hold and the caller must fall
        back to :meth:`lanes_for_batch`. One nearly-sorted dup-check sort
        replaces lanes_for_batch's unique+lexsort passes — the difference
        is ~4x host time on the multi-million-row columnar batches.

        ``gid_idx_sorted`` are registry *indices* (generation tag already
        stripped) the caller has validated live via :meth:`gids_live`.
        """
        if len(s_sorted) == 0:
            return np.empty(0, np.int32)
        if self._lane_count[uniq].any():
            return None
        keys = (s_sorted << 32) | gid_idx_sorted
        # Plain introsort: numpy's "stable" on int64 is radix sort, which
        # measures ~4x SLOWER here and cannot exploit the slot-major runs.
        ks = np.sort(keys)
        if (ks[1:] == ks[:-1]).any():
            return None  # same voter twice on one slot: general path resolves
        ok = col_sorted < self.voter_capacity
        lanes = np.where(ok, col_sorted, -1).astype(np.int32)
        sl = s_sorted[ok] if not ok.all() else s_sorted
        gi = gid_idx_sorted[ok] if not ok.all() else gid_idx_sorted
        co = col_sorted[ok] if not ok.all() else col_sorted
        self._lane_gids[sl, co] = gi.astype(np.int32)
        self._lane_count[uniq] = np.minimum(
            counts, self.voter_capacity
        ).astype(np.int32)
        # bincount + add is one O(B) pass; np.add.at's unbuffered scatter
        # is ~10x slower per element on multi-million-row batches. (An
        # out-of-range index still fails loudly: the longer bincount
        # result refuses to broadcast.)
        self._gid_refs += np.bincount(gi, minlength=len(self._gid_refs))
        return lanes

    def state_of(self, slot: int) -> int:
        """Host-mirrored lifecycle state (no device traffic)."""
        return int(self._state_host[slot])

    def states_of(self, slots) -> np.ndarray:
        """Vectorized :meth:`state_of` (host mirror gather, no device
        traffic) — the bulk demotion/sweep paths read one array instead
        of N accessor calls."""
        return self._state_host[np.asarray(slots, np.int64)]

    def state_counts(self) -> dict[int, int]:
        """Histogram of slot states from the host mirror (stats path,
        reference: src/service_stats.rs:32-59)."""
        values, counts = np.unique(self._state_host, return_counts=True)
        return {int(v): int(c) for v, c in zip(values, counts)}

    # ── Allocation ─────────────────────────────────────────────────────

    @contextmanager
    def gather_activations(self):
        """Gather the slot activations made inside the scope into one dispatch.

        Inside the scope :meth:`allocate_batch` does its host work at once
        (the same slots from the free list, the state and expiry mirrors,
        :class:`SlotMeta`) and holds back its device write. The held writes
        go to the device as ONE activation dispatch before any other device
        operation of the pool (ingest, load, release, timeout, reads) and
        when the scope closes, so the device sees its writes in the same
        order as when each call dispatches at once. An inner scope defers
        to the outer one."""
        if self._gathered is not None:
            yield
            return
        self._gathered = []
        try:
            yield
        finally:
            try:
                self._flush_activations()
            finally:
                self._gathered = None

    def _flush_activations(self) -> None:
        """Dispatch the activations gathered so far, if any, as one."""
        if self._gathered:
            parts = [np.concatenate(col) for col in zip(*self._gathered)]
            self._gathered.clear()
            self._activate(*parts)

    def _activate(self, slots, n, req, cap, gossip, liveness) -> None:
        with observed_span(self.tracer, "pool.activate", None, slots=len(slots)):
            self._dispatch_activate(slots, n, req, cap, gossip, liveness)
        self._m_activated.inc(len(slots))
        self._m_activate_dispatches.inc()

    def allocate_batch(
        self,
        keys: list[Hashable],
        n: np.ndarray,
        req: np.ndarray,
        cap: np.ndarray,
        gossip: np.ndarray,
        liveness: np.ndarray,
        expiry: np.ndarray,
        created_at: np.ndarray,
    ) -> list[int]:
        """Claim one slot per key and initialise its on-device config
        (held for one dispatch inside :meth:`gather_activations`).

        ``req``/``cap`` are host-precomputed (exact integer threshold math,
        reference: src/utils.rs:307-313 — see ops.decide.required_votes_np).
        Raises PoolFullError (allocating nothing) if fewer than len(keys)
        slots are free.
        """
        count = len(keys)
        if count == 0:
            return []
        self._check_no_inflight("allocate_batch")
        n = np.array(n, np.int32)
        if int(n.max()) > self.voter_capacity:
            raise ValueError(
                f"expected_voters_count {int(n.max())} exceeds pool "
                f"voter_capacity {self.voter_capacity}"
            )
        if count > len(self._free):
            raise PoolFullError(
                f"need {count} slots, {len(self._free)} free of {self.capacity}"
            )
        # Claim the tail of the free list in one slice (same slots, same
        # order as count pop() calls would yield).
        slots = self._free[-count:][::-1]
        del self._free[-count:]
        slots_arr = np.asarray(slots, np.int32)
        # Copies: a held write must not alias arrays the caller may reuse.
        write = (
            slots_arr,
            n,
            np.array(req, np.int32),
            np.array(cap, np.int32),
            np.array(gossip, bool),
            np.array(liveness, bool),
        )
        if self._gathered is None:
            self._activate(*write)
        else:
            self._gathered.append(write)

        expiry = np.asarray(expiry, np.int64)
        created_at = np.asarray(created_at, np.int64)
        # Lane rows need no clearing here: free slots always have cleared
        # rows (initialised at construction, retired on release).
        self._state_host[slots_arr] = STATE_ACTIVE
        self._expiry_host[slots_arr] = expiry
        meta = self._meta
        for slot, key, exp, cre in zip(
            slots, keys, expiry.tolist(), created_at.tolist()
        ):
            meta[slot] = SlotMeta(key=key, expiry=exp, created_at=cre)
        return slots

    def load_rows(
        self,
        slots: list[int],
        state: np.ndarray,
        yes: np.ndarray,
        tot: np.ndarray,
        mask_rows: np.ndarray,
        val_rows: np.ndarray,
    ) -> None:
        """Overwrite tallies of already-allocated slots (snapshot restore)."""
        if not slots:
            return
        self._check_no_inflight("load_rows")
        self._flush_activations()
        self._dispatch_load(
            np.asarray(slots, np.int32),
            np.asarray(state, np.int32),
            np.asarray(yes, np.int32),
            np.asarray(tot, np.int32),
            np.asarray(mask_rows, bool),
            np.asarray(val_rows, bool),
        )
        self._state_host[np.asarray(slots)] = np.asarray(state, np.int32)

    def release(self, slots: list[int]) -> None:
        """Return slots to the free list (eviction / delete_scope). Tallies
        are lazily cleared on the next allocation of the slot; lane tables
        are retired now so fully-released voter identities leave the
        registry (the id is recycled by a later intern)."""
        if not slots:
            return
        self._check_no_inflight("release")
        self._flush_activations()
        self._dispatch_release(np.asarray(slots, np.int32))
        self._retire_lanes(np.asarray(slots, np.int64))
        for slot in slots:
            self._state_host[slot] = STATE_FREE
            self._expiry_host[slot] = 0
            del self._meta[slot]
            self._free.append(slot)

    def _retire_lanes(self, slot_arr: np.ndarray) -> None:
        """Drop the released slots' lane references; evict gids that no live
        slot references anymore."""
        slot_arr = np.unique(slot_arr)  # a duplicated slot must not double-deref
        rows = self._lane_gids[slot_arr]
        referenced = rows[rows >= 0].astype(np.int64)
        self._lane_gids[slot_arr] = -1
        self._lane_count[slot_arr] = 0
        if referenced.size == 0:
            return
        referenced = referenced[referenced < len(self._owners)]
        if referenced.size == 0:
            return
        gids, counts = np.unique(referenced, return_counts=True)
        self._gid_refs[gids] -= counts
        # _gid_live gates eviction so synthetic (never-interned) ids and
        # already-freed ids are skipped.
        for gid in gids[(self._gid_refs[gids] <= 0) & self._gid_live[gids]].tolist():
            del self._gid_of[self._owners[gid]]
            self._owners[gid] = b""
            self._gid_live[gid] = False
            # Bump the generation so every gid minted for this index before
            # the eviction is permanently distinguishable from the next
            # claimant's gid (stale use → typed rejection, never
            # misattribution).
            self._gid_gen[gid] += 1
            self._free_gids.append(gid)

    # ── Hot paths ──────────────────────────────────────────────────────

    def ingest(
        self,
        slots: np.ndarray,
        lanes: np.ndarray,
        values: np.ndarray,
        now: int,
    ) -> tuple[np.ndarray, list[tuple[int, int]]]:
        """Apply a flat, arrival-ordered vote batch (synchronous).

        Args:
          slots: int64[B] target slot per vote.
          lanes: int32[B] voter lane per vote (from SlotMeta.lane_for).
          values: bool[B] the yes/no choices.
          now: caller clock, for the per-slot expiry check
            (reference: src/session.rs:226).

        Returns:
          (statuses int32[B] in batch order, transitions) where transitions
          lists (slot, new_state) for every slot whose lifecycle state
          changed — the engine turns these into ConsensusReached events.
        """
        pending = self.ingest_async(slots, lanes, values, now)
        if pending is None:
            return np.empty(0, np.int32), []
        return self.complete(pending)

    def ingest_async(
        self,
        slots: np.ndarray,
        lanes: np.ndarray,
        values: np.ndarray,
        now: int,
    ) -> PendingIngest | None:
        """Dispatch a vote batch without waiting for results.

        The pool arrays advance immediately (donated in-place on device), so
        subsequent dispatches chain correctly; statuses/transitions become
        visible when :meth:`complete` is called. Streaming callers keep
        several batches in flight to hide host↔device latency (the pipeline
        axis from SURVEY §2.3).
        """
        slots = np.asarray(slots, np.int64)
        if slots.size == 0:
            return None
        uniq, row, col, depth = group_batch(slots)
        return self.ingest_async_grouped(
            uniq, row, col, depth, lanes, values, now
        )

    # True where ingest_async_grouped(fresh=True) routes to the closed-form
    # kernel (single-device, sharded, and multi-host pools — the engine
    # additionally agrees the plan fleet-wide in multi-host mode). The
    # opt-in pallas configuration advertises False to keep its A/B
    # meaningful.
    supports_fresh_ingest = True

    def fresh_grid_within_budget(self, s_count: int, depth: int) -> bool:
        """Absolute cell budget for the [S, depth]-padded fresh grid —
        padding blows up when one huge chain sits amid many shallow slots,
        at which point the segmented scan wins. Multi-host callers check
        this against the FLEET-agreed max shapes (the dispatch pads every
        process to those)."""
        return _bucket(s_count) * _bucket(depth, floor=1) <= 33_554_432

    def fresh_ingest_viable(
        self, uniq: np.ndarray, depth: int, n_items: int
    ) -> bool:
        """Whether a slot-grouped batch may take the closed-form (scan-free)
        ingest dispatch. Owns the invariants next to the kernel they guard:
        the pool supports it, every touched slot is still ACTIVE on the
        host state mirror (rare non-ACTIVE fresh slots: empty sessions
        decided by timeout), and the padded grid stays within the cell
        budget (with a relative padding-factor guard on top). The caller
        must separately establish freshness + no duplicate voters
        (fresh_lanes_grouped does both)."""
        if not self.supports_fresh_ingest:
            return False
        cells = _bucket(len(uniq)) * _bucket(depth, floor=1)
        return (
            cells <= max(8 * n_items, 65_536)
            and self.fresh_grid_within_budget(len(uniq), depth)
            and bool((self._state_host[uniq] == STATE_ACTIVE).all())
        )

    def ingest_async_grouped(
        self,
        uniq: np.ndarray,
        row: np.ndarray,
        col: np.ndarray,
        depth: int,
        lanes: np.ndarray,
        values: np.ndarray,
        now: int,
        fresh: bool = False,
    ) -> PendingIngest:
        """Pre-grouped :meth:`ingest_async`: the caller already grouped the
        batch by slot (``uniq[S]`` touched slots, per-item grid coordinates
        ``row``/``col``, ``depth`` = max votes per slot). The engine's
        columnar path computes the grouping once for a whole multi-dispatch
        batch and slices it per segment — skipping one O(B log B) sort per
        dispatch that :func:`group_batch` would redo.

        ``fresh=True`` dispatches the closed-form kernel (no sequential
        scan) — ONLY valid when every touched slot is freshly ACTIVE with
        zero tallies and the batch has no repeated (slot, voter) pair; the
        engine's fast path establishes exactly that. On >64-lane pools the
        fresh grid additionally requires (and checks) that every lane is
        the within-slot arrival index — the fresh assignment rule — so the
        lane plane need not cross the link at all (laneless uint8 cells,
        half the uint16 upload)."""
        self._flush_activations()
        s_count = len(uniq)
        depth = max(int(depth), 1)
        laneless = fresh and self.voter_capacity > 64 and not self._use_pallas
        if laneless and len(row):
            if not np.array_equal(lanes, col):
                raise ValueError(
                    "fresh ingest on a >64-lane pool requires lanes == "
                    "within-slot arrival index (the fresh assignment rule)"
                )
            grid = np.zeros((s_count, depth), np.uint8)
            grid[row, col] = np.asarray(values, np.uint8) | 2  # value|valid
        elif laneless:
            grid = np.zeros((s_count, depth), np.uint8)
        else:
            voter_grid = np.zeros((s_count, depth), np.int32)
            valbit = np.zeros((s_count, depth), np.int32)
            if len(row):
                voter_grid[row, col] = np.asarray(lanes, np.int32)
                valbit[row, col] = np.asarray(values, np.int32) | 2
            # Narrow grid cells to the pool's lane range (uint8/uint16) —
            # the grid is the dominant upload of every dispatch. The Pallas
            # kernel keeps the fixed int32 layout it was written against.
            grid = pack_grid(
                voter_grid,
                valbit & 1,
                valbit >> 1,
                voter_capacity=None if self._use_pallas else self.voter_capacity,
            )

        expired = self._expiry_host[uniq] <= now
        slot_pack2 = pack_slots(uniq.astype(np.int32), expired)
        if fresh:
            out, row_select = self._dispatch_ingest_fresh(
                slot_pack2, grid, laneless=laneless
            )
        else:
            out, row_select = self._dispatch_ingest(slot_pack2, grid)
        pending = PendingIngest(
            out=out, uniq=uniq, row=row, col=col, row_select=row_select
        )
        self._inflight.append(pending)
        return pending

    def complete_all(
        self, pendings: list[PendingIngest]
    ) -> list[tuple[np.ndarray, list[tuple[int, int]]]]:
        """Block on many in-flight ingests with ONE host↔device round-trip.

        jax.device_get transfers each leaf array separately, so fetching N
        dispatch outputs pays N link round-trips — on a latency-bound
        link that dominates the whole ingest path. Same-shape
        outputs are therefore stacked ON DEVICE (one cheap concat) and
        fetched as a single array. Must be called in dispatch order
        (enforced)."""
        outs = [p.out for p in pendings]
        if len(outs) > 1:
            groups: dict[tuple, list[int]] = {}
            for i, o in enumerate(outs):
                groups.setdefault(tuple(o.shape), []).append(i)
            # Each same-shape group is stacked in power-of-two chunks:
            # _stack_kernel is jitted per (arity, shape), so pow2 chunking
            # bounds the compile set at log2(max group) programs ever, with
            # no padding waste — a varying-depth stream would otherwise
            # trace+compile a fresh program for every distinct segment
            # count it produces.
            chunks: list[list[int]] = []
            for idxs in groups.values():
                pos, n = 0, len(idxs)
                while n:
                    c = 1 << (n.bit_length() - 1)
                    chunks.append(idxs[pos : pos + c])
                    pos += c
                    n -= c
            fetched = jax.device_get(
                [
                    _stack_kernel(*(outs[i] for i in chunk))
                    if len(chunk) > 1
                    else outs[chunk[0]]
                    for chunk in chunks
                ]
            )
            host: list = [None] * len(outs)
            for arr, chunk in zip(fetched, chunks):
                if len(chunk) > 1:
                    for k, i in enumerate(chunk):
                        host[i] = arr[k]
                else:
                    host[chunk[0]] = arr
        else:
            host = jax.device_get(outs)
        return [
            self._finish(pending, out) for pending, out in zip(pendings, host)
        ]

    def complete(
        self, pending: PendingIngest
    ) -> tuple[np.ndarray, list[tuple[int, int]]]:
        """Block on an in-flight ingest; return (statuses[B], transitions)."""
        return self._finish(pending, np.asarray(pending.out))

    def _check_no_inflight(self, op: str) -> None:
        if self._inflight:
            raise RuntimeError(
                f"{op} while {len(self._inflight)} ingest dispatch(es) are "
                "in flight: complete() them first (the host state mirror "
                "must apply updates in dispatch order)"
            )

    def _finish(
        self, pending: PendingIngest, host_out: np.ndarray
    ) -> tuple[np.ndarray, list[tuple[int, int]]]:
        if not self._inflight or self._inflight[0] is not pending:
            raise RuntimeError(
                "ingest completions must happen in dispatch order"
            )
        self._inflight.pop(0)
        arr = host_out[pending.row_select]
        statuses = arr[:, :-1]
        row_state = arr[:, -1]
        prev = self._state_host[pending.uniq]
        changed = prev != row_state
        self._state_host[pending.uniq] = row_state
        transitions = list(
            zip(
                pending.uniq[changed].tolist(),
                row_state[changed].tolist(),
            )
        )
        return statuses[pending.row, pending.col], transitions

    def timeout(self, slots: list[int]) -> list[tuple[int, int]]:
        """Fire the timeout decision for the given slots.

        Returns (slot, new_state) for each *requested* slot after the sweep
        (including unchanged already-decided ones, so the caller can
        implement the reference's idempotent timeout return,
        src/service.rs:331-334).
        """
        if not slots:
            return []
        self._check_no_inflight("timeout")
        self._flush_activations()
        row_state = self._dispatch_timeout(np.asarray(slots, np.int32))
        out: list[tuple[int, int]] = []
        for i, slot in enumerate(slots):
            new_state = int(row_state[i])
            self._state_host[slot] = new_state
            out.append((int(slot), new_state))
        return out

    # ── Device dispatch (single-device; overridden by the sharded pool) ─

    def _dispatch_activate(self, slots, n, req, cap, gossip, liveness) -> None:
        bucket = _bucket(len(slots))
        slot_ids = jnp.asarray(_pad_slot_ids(slots, bucket, self.capacity))
        (
            self._state,
            self._yes,
            self._tot,
            self._vote_mask,
            self._vote_val,
            self._n,
            self._req,
            self._cap,
            self._gossip,
            self._liveness,
        ) = _activate_kernel(
            self._state,
            self._yes,
            self._tot,
            self._vote_mask,
            self._vote_val,
            self._n,
            self._req,
            self._cap,
            self._gossip,
            self._liveness,
            slot_ids,
            jnp.asarray(_pad1(n, bucket, np.int32)),
            jnp.asarray(_pad1(req, bucket, np.int32)),
            jnp.asarray(_pad1(cap, bucket, np.int32)),
            jnp.asarray(_pad1(gossip, bucket, bool)),
            jnp.asarray(_pad1(liveness, bucket, bool)),
        )

    def _dispatch_load(self, slots, state, yes, tot, mask_rows, val_rows) -> None:
        bucket = _bucket(len(slots))
        slot_ids = jnp.asarray(_pad_slot_ids(slots, bucket, self.capacity))
        (
            self._state,
            self._yes,
            self._tot,
            self._vote_mask,
            self._vote_val,
        ) = _load_kernel(
            self._state,
            self._yes,
            self._tot,
            self._vote_mask,
            self._vote_val,
            slot_ids,
            jnp.asarray(_pad1(state, bucket, np.int32)),
            jnp.asarray(_pad1(yes, bucket, np.int32)),
            jnp.asarray(_pad1(tot, bucket, np.int32)),
            jnp.asarray(_pad2(mask_rows, bucket, self.voter_capacity, bool)),
            jnp.asarray(_pad2(val_rows, bucket, self.voter_capacity, bool)),
        )

    def _dispatch_release(self, slots) -> None:
        self._state = _release_kernel(
            self._state,
            jnp.asarray(_pad_slot_ids(slots, _bucket(len(slots)), self.capacity)),
        )

    def _dispatch_ingest(self, slot_pack, grid_pack):
        """Dispatch the packed batch; returns (device out [B_s, L+1],
        row-select indexer recovering the S real rows). Does NOT block."""
        s_count, depth = grid_pack.shape
        bucket_s = _bucket(s_count)
        bucket_l = _bucket(depth, floor=1)
        (
            self._state,
            self._yes,
            self._tot,
            self._vote_mask,
            self._vote_val,
            out,
        ) = self._ingest_kernel(
            self._state,
            self._yes,
            self._tot,
            self._vote_mask,
            self._vote_val,
            self._n,
            self._req,
            self._cap,
            self._gossip,
            self._liveness,
            jnp.asarray(_pad_slot_ids(slot_pack, bucket_s, self.capacity)),
            jnp.asarray(_pad2(grid_pack, bucket_s, bucket_l, grid_pack.dtype)),
        )
        return out, np.arange(s_count)

    def _dispatch_ingest_fresh(self, slot_pack, grid_pack, laneless=False):
        """Closed-form (scan-free) ingest dispatch for fresh-slot batches —
        same transfer contract as :meth:`_dispatch_ingest`. ``laneless``
        grids carry value/valid only (uint8); the kernel reconstructs
        lanes as the within-slot arrival index."""
        s_count, depth = grid_pack.shape
        bucket_s = _bucket(s_count)
        bucket_l = _bucket(depth, floor=1)
        kernel = fresh_ingest_laneless_kernel if laneless else fresh_ingest_kernel
        (
            self._state,
            self._yes,
            self._tot,
            self._vote_mask,
            self._vote_val,
            out,
        ) = kernel(
            self._state,
            self._yes,
            self._tot,
            self._vote_mask,
            self._vote_val,
            self._n,
            self._req,
            self._cap,
            self._gossip,
            self._liveness,
            jnp.asarray(_pad_slot_ids(slot_pack, bucket_s, self.capacity)),
            jnp.asarray(_pad2(grid_pack, bucket_s, bucket_l, grid_pack.dtype)),
        )
        return out, np.arange(s_count)

    def _dispatch_timeout(self, slots) -> np.ndarray:
        """Returns new row states, one per requested slot."""
        bucket = _bucket(len(slots))
        self._state, row_state = timeout_kernel(
            self._state,
            self._yes,
            self._tot,
            self._n,
            self._req,
            self._liveness,
            jnp.asarray(_pad_slot_ids(slots, bucket, self.capacity)),
        )
        return np.asarray(row_state)[: len(slots)]

    # ── Cold query path ────────────────────────────────────────────────

    def read_slot(self, slot: int) -> dict[str, np.ndarray]:
        """Gather one slot's full row back to host (debug / session export)."""
        self._flush_activations()
        state, yes, tot, mask, vals = _read_kernel(
            self._state, self._yes, self._tot, self._vote_mask, self._vote_val,
            jnp.asarray(slot, jnp.int32),
        )
        return dict(
            state=np.asarray(state),
            yes=np.asarray(yes),
            tot=np.asarray(tot),
            vote_mask=np.asarray(mask),
            vote_val=np.asarray(vals),
        )

    def read_slots(self, slots) -> dict[str, np.ndarray]:
        """Batched :meth:`read_slot`: ONE gather dispatch + transfer for
        many slots (arrays indexed [k] in ``slots`` order). The bulk-export
        path session demotion rides on — per-slot dispatches would make
        tier churn O(sessions) device round-trips."""
        self._flush_activations()
        state, yes, tot, mask, vals = _read_kernel(
            self._state, self._yes, self._tot, self._vote_mask, self._vote_val,
            jnp.asarray(np.asarray(slots, np.int32)),
        )
        return dict(
            state=np.asarray(state),
            yes=np.asarray(yes),
            tot=np.asarray(tot),
            vote_mask=np.asarray(mask),
            vote_val=np.asarray(vals),
        )
