"""Batched vote ingest over the dense proposal pool.

Applies a batch of (already host-validated) votes to the device-resident pool
with semantics bit-identical to repeated ``ConsensusSession::add_vote``
(reference: src/session.rs:225-249): per-proposal votes apply in arrival
order with the exact precedence chain — already-reached (no-op success) →
session-not-active → proposal-expired → round-cap (fails the session) →
duplicate-owner → accept, then the consensus check runs on the updated tally.

Layout: the host groups the batch by proposal slot into an ``[S, L]`` grid
(S touched slots, L = max votes per slot in this batch, padded). The kernel
gathers each touched slot's state, runs a ``lax.scan`` of length L — one vote
per slot per step, vectorized across all S slots — and scatters results back.
Wall-clock scales with the *deepest* per-proposal vote chain in the batch,
not the batch size: breadth-heavy workloads (many proposals, few votes each)
are nearly fully parallel; depth-heavy replays serialize only within a
proposal, exactly like the protocol itself does.

Transfer format (the host↔device link is latency-bound — every round trip
costs, so the batch crosses in TWO packed arrays and returns in ONE):
- ``slot_pack`` int32[S]: slot id in bits 0-29, ``expired`` flag in bit 30.
  Pad rows carry slot id == P (out of range): gathers clip (values unused),
  scatters drop — so pad rows can never corrupt slot 0.
- ``grid_pack`` [S, L]: voter lane in the low bits, vote value and
  cell-valid above them. The dtype is the narrowest that fits the pool's
  lane range (uint8 for voter_capacity <= 64, uint16 <= 16384, else int32
  with lane bits 0-15 / value bit 16 / valid bit 17 — see
  :func:`grid_layout`); the grid is the dominant upload, so narrowing it
  cuts the per-dispatch wire bytes 4x/2x. Pad cells have valid == 0.
- output int8[S, L+1]: per-vote statuses in columns [0, L), the row's final
  lifecycle state in column L.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..errors import StatusCode
from .decide import (
    STATE_ACTIVE,
    STATE_FAILED,
    STATE_REACHED_NO,
    STATE_REACHED_YES,
    decide_kernel,
)

# Status emitted for padding cells (no vote present).
PAD_STATUS = -1

_SLOT_MASK = (1 << 30) - 1
_EXPIRED_BIT = 30
_LANE_MASK = (1 << 16) - 1
_VAL_BIT = 16
_VALID_BIT = 17


def pack_slots(slot_ids: np.ndarray, expired: np.ndarray) -> np.ndarray:
    """Host-side: fuse slot ids + expiry flags into one int32 transfer."""
    return (
        np.asarray(slot_ids, np.int32) | (np.asarray(expired, np.int32) << _EXPIRED_BIT)
    ).astype(np.int32)


def unpack_slots(slot_pack: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host-side inverse of :func:`pack_slots` (used by shard routing)."""
    packed = np.asarray(slot_pack, np.int32)
    return packed & _SLOT_MASK, ((packed >> _EXPIRED_BIT) & 1).astype(bool)


def grid_dtype(voter_capacity: int):
    """Narrowest packed-grid dtype that fits lane + value + valid bits.
    The grid is the big host->device transfer of every ingest dispatch
    (uploads dominate on a latency-bound link), so capacity <= 64 pools ship
    uint8 cells and capacity <= 16384 ship uint16 — 4x / 2x less wire
    than the general int32 layout."""
    if voter_capacity <= 64:
        return np.uint8
    if voter_capacity <= 16384:
        return np.uint16
    return np.int32


def grid_layout(dtype) -> tuple[int, int, int]:
    """(lane_mask, val_bit, valid_bit) for a packed-grid dtype. Kernels
    derive the layout from the traced array's dtype, so host pack and
    device unpack can never disagree."""
    dt = np.dtype(dtype)
    if dt == np.uint8:
        return (1 << 6) - 1, 6, 7
    if dt == np.uint16:
        return (1 << 14) - 1, 14, 15
    return _LANE_MASK, _VAL_BIT, _VALID_BIT


def pack_grid(
    voter_grid: np.ndarray,
    val_grid: np.ndarray,
    valid_grid: np.ndarray,
    voter_capacity: int | None = None,
) -> np.ndarray:
    """Host-side: fuse lane/value/valid grids into one packed transfer.
    ``voter_capacity`` (when given) selects the narrowest dtype whose lane
    field still holds capacity-1; None keeps the original int32 layout
    (direct callers, and the Pallas kernel's fixed int32 unpack)."""
    dt = np.int32 if voter_capacity is None else grid_dtype(voter_capacity)
    _, val_bit, valid_bit = grid_layout(dt)
    return (
        np.asarray(voter_grid, dt)
        | (np.asarray(val_grid, dt) << val_bit)
        | (np.asarray(valid_grid, dt) << valid_bit)
    ).astype(dt)


def group_batch(slot_idx: np.ndarray):
    """Host-side: group a flat vote batch by proposal slot into grid
    coordinates, preserving arrival order within each slot.

    Returns ``(uniq_slots[S], row[B], col[B], L)`` where batch item ``b``
    lands at grid cell ``(row[b], col[b])`` and ``L`` is the deepest
    per-slot chain. Stable sort keeps the protocol's order-sensitivity
    (round caps, mid-batch consensus cuts) intact.
    """
    b_count = len(slot_idx)
    if b_count == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64), np.empty(0, np.int64), 0
    order = np.argsort(slot_idx, kind="stable")
    sorted_slots = slot_idx[order]
    # Group boundaries straight from the sorted run (np.unique would sort a
    # second time — this path sits on the ingest hot loop).
    is_start = np.empty(b_count, bool)
    is_start[0] = True
    np.not_equal(sorted_slots[1:], sorted_slots[:-1], out=is_start[1:])
    starts_idx = np.nonzero(is_start)[0]
    uniq = sorted_slots[starts_idx]
    inverse_sorted = np.cumsum(is_start) - 1
    starts = starts_idx[inverse_sorted]
    pos_sorted = np.arange(b_count) - starts
    counts_max = int(np.max(np.diff(np.append(starts_idx, b_count))))
    row = np.empty(b_count, dtype=np.int64)
    col = np.empty(b_count, dtype=np.int64)
    row[order] = inverse_sorted
    col[order] = pos_sorted
    return uniq, row, col, counts_max


_OK = int(StatusCode.OK)
_ALREADY_REACHED = int(StatusCode.ALREADY_REACHED)
_SESSION_NOT_ACTIVE = int(StatusCode.SESSION_NOT_ACTIVE)
_PROPOSAL_EXPIRED = int(StatusCode.PROPOSAL_EXPIRED)
_MAX_ROUNDS_EXCEEDED = int(StatusCode.MAX_ROUNDS_EXCEEDED)
_DUPLICATE_VOTE = int(StatusCode.DUPLICATE_VOTE)


def ingest_body(
    state,  # int32[P] slot lifecycle
    yes,  # int32[P] YES tally
    tot,  # int32[P] total tally
    vote_mask,  # bool[P, V] who has voted
    vote_val,  # bool[P, V] their choice
    n,  # int32[P] expected voters
    req,  # int32[P] precomputed required votes
    cap,  # int32[P] max round limit (max_round_limit semantics)
    gossipsub,  # bool[P] gossipsub round semantics flag
    liveness,  # bool[P] silent-peers-as-YES flag
    slot_pack,  # int32[S] packed slot ids + expired flags (see module doc)
    grid_pack,  # int32[S, L] packed voter/value/valid cells
):
    """Returns (updated pool arrays..., out int32[S, L+1]) where out carries
    per-vote statuses plus the final row state in the last column."""
    s_count = slot_pack.shape[0]
    rows = jnp.arange(s_count)

    slot_ids = slot_pack & _SLOT_MASK
    expired = ((slot_pack >> _EXPIRED_BIT) & 1).astype(bool)
    lane_mask, val_bit, valid_bit = grid_layout(grid_pack.dtype)
    voter_grid = (grid_pack & lane_mask).astype(jnp.int32)
    val_grid = ((grid_pack >> val_bit) & 1).astype(bool)
    valid_grid = ((grid_pack >> valid_bit) & 1).astype(bool)

    gather = lambda arr: jnp.take(arr, slot_ids, axis=0, mode="clip")
    row_state = gather(state)
    row_yes = gather(yes)
    row_tot = gather(tot)
    row_mask = gather(vote_mask)
    row_val = gather(vote_val)
    row_n = gather(n)
    row_req = gather(req)
    row_cap = gather(cap)
    row_gossip = gather(gossipsub)
    row_live = gather(liveness)

    def step(carry, xs):
        st, ys, tt, mask, vals = carry
        voter, val, valid = xs

        reached = (st == STATE_REACHED_YES) | (st == STATE_REACHED_NO)
        active = st == STATE_ACTIVE
        # Round projection (reference: src/session.rs:306-344): gossipsub
        # always projects round 2 when adding a vote; P2P projects
        # accepted-votes + 1 (round == tot + 1 invariant).
        projected = jnp.where(row_gossip, 2, tt + 1)
        exceeded = projected > row_cap
        dup = mask[rows, voter]

        ok = valid & active & ~expired & ~exceeded & ~dup
        status = jnp.where(
            ~valid,
            PAD_STATUS,
            jnp.where(
                reached,
                _ALREADY_REACHED,
                jnp.where(
                    ~active,
                    _SESSION_NOT_ACTIVE,
                    jnp.where(
                        expired,
                        _PROPOSAL_EXPIRED,
                        jnp.where(
                            exceeded,
                            _MAX_ROUNDS_EXCEEDED,
                            jnp.where(dup, _DUPLICATE_VOTE, _OK),
                        ),
                    ),
                ),
            ),
        ).astype(jnp.int32)

        # A cap violation moves the session to Failed even though the vote is
        # rejected (reference: src/session.rs:334-341).
        st = jnp.where(valid & active & ~expired & exceeded, STATE_FAILED, st)

        tt = tt + ok.astype(tt.dtype)
        ys = ys + (ok & val).astype(ys.dtype)
        mask = mask.at[rows, voter].set(dup | ok)
        vals = vals.at[rows, voter].set(jnp.where(ok, val, vals[rows, voter]))

        # Consensus check on the updated tally (is_timeout=False).
        decided, result = decide_kernel(ys, tt, row_n, row_req, row_live, False)
        newly = ok & decided
        reached_state = jnp.where(result, STATE_REACHED_YES, STATE_REACHED_NO)
        st = jnp.where(newly, reached_state.astype(st.dtype), st)

        return (st, ys, tt, mask, vals), status

    carry0 = (row_state, row_yes, row_tot, row_mask, row_val)
    # Scan over vote positions: xs steps through columns of the [S, L] grids.
    (row_state, row_yes, row_tot, row_mask, row_val), statuses = lax.scan(
        step,
        carry0,
        (voter_grid.T, val_grid.T, valid_grid.T),
    )
    statuses = statuses.T  # [L, S] -> [S, L]

    scatter = lambda arr, rows_val: arr.at[slot_ids].set(rows_val, mode="drop")
    state = scatter(state, row_state)
    yes = scatter(yes, row_yes)
    tot = scatter(tot, row_tot)
    vote_mask = scatter(vote_mask, row_mask)
    vote_val = scatter(vote_val, row_val)

    # int8 readback: status codes fit a byte, and the device->host link is
    # the bottleneck — 4x less transfer than int32.
    out = jnp.concatenate([statuses, row_state[:, None]], axis=1).astype(jnp.int8)
    return state, yes, tot, vote_mask, vote_val, out


# Jitted single-device entry point; the raw body is reused inside shard_map
# blocks by the multi-device pool (hashgraph_tpu.parallel).
ingest_kernel = partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))(ingest_body)


def fresh_ingest_body(
    state,
    yes,
    tot,
    vote_mask,
    vote_val,
    n,
    req,
    cap,
    gossipsub,
    liveness,
    slot_pack,  # int32[S] packed slot ids + expired flags
    grid_pack,  # packed cells: see `laneless` below
    *,
    laneless: bool = False,
):
    """Closed-form ingest for FRESH slots: the whole per-slot vote chain in
    one dispatch with NO sequential scan.

    ``laneless=True``: the grid carries only value (bit 0) and valid
    (bit 1) per cell (uint8); voter lanes are reconstructed on device as
    the within-slot arrival index — which is exactly what the fresh-path
    lane assignment rule produces — halving the dominant upload for pools
    whose lane range doesn't fit uint8 anyway (voter_capacity > 64).

    The serial scan in :func:`ingest_body` exists because a vote's fate
    depends on the running state. For a batch the engine has already
    resolved on its fast path — every touched slot freshly ACTIVE with zero
    prior tallies, and no repeated (slot, voter) pair — that dependency has
    a closed form: every valid vote before the terminal event is accepted,
    so the running tallies are prefix sums (XLA's log-depth parallel
    cumsum, not an L-step scan), the round-cap violation index and the
    decision index are first-true reductions over elementwise
    :func:`decide_kernel`, and statuses fall out of index-vs-terminal
    comparisons. Semantics are bit-identical to replaying the scan on a
    fresh slot (randomized parity-tested); per-slot wall clock drops from
    O(depth) scan steps to O(log depth), which is the difference between
    ~16 ms and ~1 ms for a 683-deep P2P quorum chain.

    PRECONDITIONS (engine-enforced): touched slots are ACTIVE with
    tot == yes == 0 and cleared mask/val rows; the batch has no duplicate
    (slot, voter) pair. Pad rows/cells follow the scan kernel's contract.
    Returns the same (updated arrays..., out int8[S, L+1]) shape.
    """
    s_count, depth = grid_pack.shape

    slot_ids = slot_pack & _SLOT_MASK
    expired = ((slot_pack >> _EXPIRED_BIT) & 1).astype(bool)
    if laneless:
        val_grid = (grid_pack & 1).astype(bool)
        valid = ((grid_pack >> 1) & 1).astype(bool)
        voter_grid = jnp.broadcast_to(
            jnp.arange(depth, dtype=jnp.int32), (s_count, depth)
        )
    else:
        lane_mask, val_bit, valid_bit = grid_layout(grid_pack.dtype)
        voter_grid = (grid_pack & lane_mask).astype(jnp.int32)
        val_grid = ((grid_pack >> val_bit) & 1).astype(bool)
        valid = ((grid_pack >> valid_bit) & 1).astype(bool)

    gather = lambda arr: jnp.take(arr, slot_ids, axis=0, mode="clip")
    row_n = gather(n)[:, None]
    row_req = gather(req)[:, None]
    row_cap = gather(cap)[:, None]
    row_gossip = gather(gossipsub)[:, None]
    row_live = gather(liveness)[:, None]

    live = valid & ~expired[:, None]
    T = jnp.cumsum(live.astype(jnp.int32), axis=1)
    Y = jnp.cumsum((live & val_grid).astype(jnp.int32), axis=1)

    # Round-cap check per vote, pre-accept (reference: src/session.rs:306-344):
    # gossipsub projects round 2; P2P projects accepted-before + 1 == T_i for
    # a valid vote on a fresh slot.
    projected = jnp.where(row_gossip, 2, T)
    exceeded = live & (projected > row_cap)
    decided_i, result_i = decide_kernel(Y, T, row_n, row_req, row_live, False)
    dec = live & decided_i

    idxs = jnp.arange(depth, dtype=jnp.int32)[None, :]
    c_has = dec.any(axis=1)
    c = jnp.where(c_has, jnp.argmax(dec, axis=1).astype(jnp.int32), depth)
    f_has = exceeded.any(axis=1)
    f = jnp.where(f_has, jnp.argmax(exceeded, axis=1).astype(jnp.int32), depth)
    # A vote that violates the cap is rejected before it could decide, so
    # the cap-fail terminal wins ties.
    dec_term = c < f
    fail_term = f_has & ~dec_term
    t_idx = jnp.where(dec_term, c, f)[:, None]

    # Statuses by region relative to the terminal index (the innermost
    # else-branch is the post-terminal region; with no terminal, t == depth
    # and every cell is "pre").
    pre = idxs < t_idx
    at = idxs == t_idx
    status = jnp.where(
        pre,
        _OK,
        jnp.where(
            at,
            jnp.where(dec_term[:, None], _OK, _MAX_ROUNDS_EXCEEDED),
            jnp.where(
                dec_term[:, None], _ALREADY_REACHED, _SESSION_NOT_ACTIVE
            ),
        ),
    )
    status = jnp.where(expired[:, None], _PROPOSAL_EXPIRED, status)
    status = jnp.where(valid, status, PAD_STATUS).astype(jnp.int32)

    # Accepted set: valid live votes up to the terminal (inclusive for a
    # decision — the deciding vote is accepted; exclusive for a cap fail).
    acc = live & (pre | (at & dec_term[:, None]))

    # Final per-row tallies/state.
    take_at = lambda M, i: jnp.take_along_axis(M, i[:, None], axis=1)[:, 0]
    last_T = T[:, -1] if depth else jnp.zeros(s_count, jnp.int32)
    last_Y = Y[:, -1] if depth else jnp.zeros(s_count, jnp.int32)
    cc = jnp.minimum(c, depth - 1)
    ff = jnp.minimum(f, depth - 1)
    tot_new = jnp.where(
        dec_term,
        take_at(T, cc),
        jnp.where(fail_term, take_at(T, ff) - 1, last_T),
    )
    yes_new = jnp.where(
        dec_term,
        take_at(Y, cc),
        jnp.where(
            fail_term,
            take_at(Y, ff) - (take_at(val_grid, ff) & take_at(live, ff)),
            last_Y,
        ),
    )
    result_c = take_at(result_i, cc)
    prev_state = gather(state)
    row_state = jnp.where(
        dec_term,
        jnp.where(result_c, STATE_REACHED_YES, STATE_REACHED_NO),
        jnp.where(fail_term, STATE_FAILED, prev_state),
    ).astype(prev_state.dtype)

    scatter = lambda arr, rows_val: arr.at[slot_ids].set(rows_val, mode="drop")
    state = scatter(state, row_state)
    yes = scatter(yes, yes_new.astype(yes.dtype))
    tot = scatter(tot, tot_new.astype(tot.dtype))
    rows_flat = jnp.repeat(slot_ids, depth)
    lanes_flat = voter_grid.reshape(-1)
    # Fresh rows start all-False, and each (slot, lane) cell is touched at
    # most once (no duplicate voters on this path), so scatter-max writes
    # exactly the accepted cells.
    vote_mask = vote_mask.at[rows_flat, lanes_flat].max(
        acc.reshape(-1), mode="drop"
    )
    vote_val = vote_val.at[rows_flat, lanes_flat].max(
        (acc & val_grid).reshape(-1), mode="drop"
    )

    out = jnp.concatenate(
        [status, row_state[:, None].astype(jnp.int32)], axis=1
    ).astype(jnp.int8)
    return state, yes, tot, vote_mask, vote_val, out


fresh_ingest_kernel = partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))(
    fresh_ingest_body
)
fresh_ingest_laneless_kernel = partial(
    jax.jit, donate_argnums=(0, 1, 2, 3, 4)
)(partial(fresh_ingest_body, laneless=True))
