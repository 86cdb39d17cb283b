"""Multi-device proposal pool: slot axis sharded over a device mesh.

SPMD layout (the scaling-book recipe: pick a mesh, annotate shardings, let
XLA place the collectives):

- every ``[P]`` / ``[P, V]`` pool array is sharded on the slot axis across
  the 1-D ``consensus_mesh``; device ``d`` owns the contiguous slot range
  ``[d·P_local, (d+1)·P_local)``;
- batched mutations are routed on host: each device receives only its own
  slots' work as one ``[D·B, ...]`` array sharded on axis 0, with local slot
  ids — inside ``shard_map`` every device runs the *same single-device
  kernel body* (:mod:`hashgraph_tpu.ops`) on its block, embarrassingly
  parallel, zero collectives on the hot path;
- the only cross-device communication is ``psum`` for global stats
  (:meth:`ShardedPool.global_state_counts`), riding ICI;
- slot allocation round-robins across devices so load stays balanced.

The reference has no distributed runtime (deliberate no-I/O design,
src/lib.rs:15-27); this layer is the TPU-native equivalent of scaling the
embedder horizontally, with sessions partitioned exactly like the
scope-partitioned storage maps (src/storage.rs:192-193).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.decide import (
    STATE_ACTIVE,
    STATE_FAILED,
    STATE_FREE,
    STATE_REACHED_NO,
    STATE_REACHED_YES,
    timeout_body,
)
from ..ops.ingest import (
    fresh_ingest_body,
    ingest_body,
    pack_slots,
    unpack_slots,
)
from ..obs import (
    POOL_SHARD_GRID_ROWS_TOTAL,
    POOL_SHARD_ROWS_TOTAL,
    observed_span,
    registry,
)
from .mesh import PROPOSAL_AXIS, consensus_mesh
from ..engine.pool import (
    ProposalPool,
    activate_body,
    load_body,
    release_body,
    _bucket,
    _pad1,
    _pad2,
    _pad_slot_ids,
)

__all__ = ["ShardedPool"]

_STATE_CODES = (
    STATE_FREE,
    STATE_ACTIVE,
    STATE_FAILED,
    STATE_REACHED_NO,
    STATE_REACHED_YES,
)


class ShardedPool(ProposalPool):
    """ProposalPool with its slot axis sharded over a device mesh.

    ``capacity_per_device`` slots live on each of the mesh's D devices
    (total capacity = D × capacity_per_device). The public API — and all
    host bookkeeping inherited from ProposalPool — is unchanged; only the
    ``_dispatch_*`` device hooks are replaced with shard_map versions.
    """


    def __init__(
        self,
        capacity_per_device: int,
        voter_capacity: int,
        mesh: Mesh | None = None,
    ):
        self.mesh = mesh if mesh is not None else consensus_mesh()
        self.axis = self.mesh.axis_names[0]
        self.n_devices = self.mesh.devices.size
        self.local_capacity = capacity_per_device
        self._m_shard_rows = registry.counter(POOL_SHARD_ROWS_TOTAL)
        self._m_shard_grid_rows = registry.counter(POOL_SHARD_GRID_ROWS_TOTAL)
        self._build_sharded_kernels()
        super().__init__(capacity_per_device * self.n_devices, voter_capacity)
        # Round-robin free list across devices: pops yield device 0, 1, ...,
        # D-1, then wrap — keeps per-device load balanced as slots fill.
        order = [
            d * self.local_capacity + k
            for k in range(self.local_capacity)
            for d in range(self.n_devices)
        ]
        self._free = order[::-1]

    # ── Sharded array construction ─────────────────────────────────────

    def _init_device_arrays(self) -> None:
        p, v = self.capacity, self.voter_capacity
        s1 = NamedSharding(self.mesh, P(self.axis))
        s2 = NamedSharding(self.mesh, P(self.axis, None))
        self._state = jax.device_put(
            np.full(p, STATE_FREE, np.int32), s1
        )
        self._yes = jax.device_put(np.zeros(p, np.int32), s1)
        self._tot = jax.device_put(np.zeros(p, np.int32), s1)
        self._vote_mask = jax.device_put(np.zeros((p, v), bool), s2)
        self._vote_val = jax.device_put(np.zeros((p, v), bool), s2)
        self._n = jax.device_put(np.zeros(p, np.int32), s1)
        self._req = jax.device_put(np.zeros(p, np.int32), s1)
        self._cap = jax.device_put(np.zeros(p, np.int32), s1)
        self._gossip = jax.device_put(np.zeros(p, bool), s1)
        self._liveness = jax.device_put(np.zeros(p, bool), s1)

    def _build_sharded_kernels(self) -> None:
        mesh, axis = self.mesh, self.axis
        v1 = P(axis)  # [P] pool arrays and [D*B] routed batches
        v2 = P(axis, None)  # [P, V] pool arrays and [D*B, L] grids

        sm = partial(jax.shard_map, mesh=mesh)

        self._sharded_activate = jax.jit(
            sm(
                activate_body,
                in_specs=(v1, v1, v1, v2, v2, v1, v1, v1, v1, v1,
                          v1, v1, v1, v1, v1, v1),
                out_specs=(v1, v1, v1, v2, v2, v1, v1, v1, v1, v1),
            ),
            donate_argnums=tuple(range(10)),
        )
        self._sharded_load = jax.jit(
            sm(
                load_body,
                in_specs=(v1, v1, v1, v2, v2, v1, v1, v1, v1, v2, v2),
                out_specs=(v1, v1, v1, v2, v2),
            ),
            donate_argnums=(0, 1, 2, 3, 4),
        )
        self._sharded_release = jax.jit(
            sm(release_body, in_specs=(v1, v1), out_specs=v1),
            donate_argnums=(0,),
        )
        self._sharded_ingest = jax.jit(
            sm(
                ingest_body,
                in_specs=(v1, v1, v1, v2, v2, v1, v1, v1, v1, v1, v1, v2),
                out_specs=(v1, v1, v1, v2, v2, v2),
            ),
            donate_argnums=(0, 1, 2, 3, 4),
        )
        # Closed-form (scan-free) fresh ingest: pure per-shard elementwise
        # + cumsum work, zero collectives — shards exactly like the scan.
        self._sharded_fresh_ingest = jax.jit(
            sm(
                fresh_ingest_body,
                in_specs=(v1, v1, v1, v2, v2, v1, v1, v1, v1, v1, v1, v2),
                out_specs=(v1, v1, v1, v2, v2, v2),
            ),
            donate_argnums=(0, 1, 2, 3, 4),
        )
        self._sharded_fresh_ingest_laneless = jax.jit(
            sm(
                partial(fresh_ingest_body, laneless=True),
                in_specs=(v1, v1, v1, v2, v2, v1, v1, v1, v1, v1, v1, v2),
                out_specs=(v1, v1, v1, v2, v2, v2),
            ),
            donate_argnums=(0, 1, 2, 3, 4),
        )
        self._sharded_timeout = jax.jit(
            sm(
                timeout_body,
                in_specs=(v1, v1, v1, v1, v1, v1, v1),
                out_specs=(v1, v1),
            ),
            donate_argnums=(0,),
        )

        def _counts_block(state):
            local = jnp.stack(
                [jnp.sum(state == code) for code in _STATE_CODES]
            )
            return jax.lax.psum(local, axis)

        self._sharded_counts = jax.jit(
            sm(_counts_block, in_specs=(v1,), out_specs=P())
        )

    # ── Host-side routing ──────────────────────────────────────────────

    def _route(
        self,
        slots: np.ndarray,
        payloads: list[tuple[np.ndarray, object]],
        bucket: int | None = None,
    ) -> tuple[np.ndarray, list[np.ndarray], np.ndarray, int]:
        """Distribute per-slot work to the owning devices.

        Returns (slot_grid [D*B] of local ids with per-device sentinel,
        routed payload arrays [D*B, ...], flat positions [K] mapping input
        order -> routed row, bucket B). ``bucket`` overrides the local
        per-device row bucket (multi-host callers pass the fleet-agreed
        value so every process compiles the same shapes).
        """
        dev = slots // self.local_capacity
        local = (slots % self.local_capacity).astype(np.int32)
        counts = np.bincount(dev, minlength=self.n_devices)
        if bucket is None:
            bucket = _bucket(int(counts.max()) if len(slots) else 0)
        order = np.argsort(dev, kind="stable")
        within = np.empty(len(slots), np.int64)
        starts = np.cumsum(counts) - counts
        within[order] = np.arange(len(slots)) - starts[dev[order]]
        rows = dev * bucket + within  # [K] flat routed position

        slot_grid = np.full(self.n_devices * bucket, self.local_capacity, np.int32)
        slot_grid[rows] = local
        routed = []
        for payload, fill in payloads:
            shape = (self.n_devices * bucket,) + payload.shape[1:]
            out = np.full(shape, fill, payload.dtype)
            out[rows] = payload
            routed.append(out)
        return slot_grid, routed, rows, bucket

    def _put_batch(self, arr: np.ndarray) -> jax.Array:
        """Place a routed [D*B, ...] host array with its axis-0 sharding."""
        spec = P(self.axis) if arr.ndim == 1 else P(self.axis, None)
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _local_ids(self, slots: np.ndarray) -> tuple[np.ndarray, int]:
        """A payload of the slots' device-local ids, padded with the
        per-device sentinel: the slot grid as :meth:`_place` routes it."""
        return (slots % self.local_capacity).astype(np.int32), self.local_capacity

    def _place(
        self,
        slots: np.ndarray,
        payloads: list[tuple[np.ndarray, object]],
        bucket: int | None = None,
    ) -> tuple[list[jax.Array], np.ndarray, int]:
        """:meth:`_route` the payloads, then :meth:`_put_batch` each, under
        one ``pool.shard_route`` span: (device arrays [D*B, ...], flat
        positions, bucket B). Counts the real rows routed and the D×B grid
        rows placed for them."""
        with observed_span(self.tracer, "pool.shard_route", None) as span:
            _, routed, rows, bucket = self._route(slots, payloads, bucket)
            placed = [self._put_batch(a) for a in routed]
            grid_rows = self.n_devices * bucket
            span.note(rows=len(slots), grid_rows=grid_rows)
        self._m_shard_rows.inc(len(slots))
        self._m_shard_grid_rows.inc(grid_rows)
        return placed, rows, bucket

    # ── Dispatch overrides ─────────────────────────────────────────────

    def _dispatch_activate(self, slots, n, req, cap, gossip, liveness) -> None:
        slots = slots.astype(np.int64)
        routed, _, _ = self._place(
            slots,
            [self._local_ids(slots), (n, 0), (req, 0), (cap, 0),
             (gossip, False), (liveness, False)],
        )
        (
            self._state, self._yes, self._tot, self._vote_mask,
            self._vote_val, self._n, self._req, self._cap,
            self._gossip, self._liveness,
        ) = self._sharded_activate(
            self._state, self._yes, self._tot, self._vote_mask,
            self._vote_val, self._n, self._req, self._cap,
            self._gossip, self._liveness,
            *routed,
        )

    def _dispatch_load(self, slots, state, yes, tot, mask_rows, val_rows) -> None:
        slots = slots.astype(np.int64)
        routed, _, _ = self._place(
            slots,
            [
                self._local_ids(slots),
                (state, 0),
                (yes, 0),
                (tot, 0),
                (mask_rows, False),
                (val_rows, False),
            ],
        )
        (
            self._state, self._yes, self._tot, self._vote_mask, self._vote_val,
        ) = self._sharded_load(
            self._state, self._yes, self._tot, self._vote_mask, self._vote_val,
            *routed,
        )

    def _dispatch_release(self, slots) -> None:
        slots = slots.astype(np.int64)
        (slot_grid,), _, _ = self._place(slots, [self._local_ids(slots)])
        self._state = self._sharded_release(self._state, slot_grid)

    def _dispatch_ingest(self, slot_pack, grid_pack):
        """Route the packed batch to owning devices; non-blocking. Returns
        (device out [D*B, L+1], row indexer recovering the S input rows)."""
        return self._routed_ingest(slot_pack, grid_pack, self._sharded_ingest)

    def _routed_ingest(
        self,
        slot_pack,
        grid_pack,
        kernel,
        bucket_s=None,
        bucket_l=None,
        row_offset=0,
    ):
        """Shared routing/repack body for the scan and closed-form ingest
        dispatches — one place owns the pad-sentinel/bucket contract.
        Multi-host callers pass fleet-agreed ``bucket_s``/``bucket_l`` (so
        every process compiles the same global program) and their device
        offset for block-local row positions."""
        s_count, depth = grid_pack.shape
        if bucket_l is None:
            bucket_l = _bucket(depth, floor=1)
        slots_g, expired = unpack_slots(slot_pack)
        local_pack = pack_slots(
            (slots_g % self.local_capacity).astype(np.int32), expired
        )
        (pack_g, grid_g), rows, bucket = self._place(
            slots_g.astype(np.int64),
            [
                (local_pack, self.local_capacity),
                (_pad2(grid_pack, s_count, bucket_l, grid_pack.dtype), 0),
            ],
            bucket=bucket_s,
        )
        (
            self._state, self._yes, self._tot, self._vote_mask,
            self._vote_val, out,
        ) = kernel(
            self._state, self._yes, self._tot, self._vote_mask,
            self._vote_val, self._n, self._req, self._cap,
            self._gossip, self._liveness,
            pack_g,
            grid_g,
        )
        return out, rows - row_offset * bucket

    def _dispatch_ingest_fresh(self, slot_pack, grid_pack, laneless=False):
        """Sharded closed-form ingest; same routing contract as
        :meth:`_dispatch_ingest`."""
        return self._routed_ingest(
            slot_pack,
            grid_pack,
            self._sharded_fresh_ingest_laneless
            if laneless
            else self._sharded_fresh_ingest,
        )

    def _dispatch_timeout(self, slots) -> np.ndarray:
        slots = slots.astype(np.int64)
        (slot_grid,), rows, _ = self._place(slots, [self._local_ids(slots)])
        self._state, row_state = self._sharded_timeout(
            self._state, self._yes, self._tot, self._n, self._req,
            self._liveness, slot_grid,
        )
        return np.asarray(row_state)[rows]

    # ── Collectives ────────────────────────────────────────────────────

    def per_device_occupancy(self) -> list[int]:
        """Occupied (non-FREE) slots per mesh device, from the host state
        mirror — the per-device view the fleet's per-shard breakdown
        reports. Device ``d`` owns the
        contiguous block ``[d·local_capacity, (d+1)·local_capacity)``."""
        blocks = self._state_host.reshape(self.n_devices, self.local_capacity)
        return (blocks != STATE_FREE).sum(axis=1).astype(int).tolist()

    def global_state_counts(self) -> dict[int, int]:
        """Device-side global histogram of slot states via psum over ICI
        (the all-reduce the host mirror makes redundant for small pools, but
        the scalable path for multi-host deployments where no single host
        sees every shard)."""
        self._flush_activations()
        counts = np.asarray(self._sharded_counts(self._state))
        return {code: int(c) for code, c in zip(_STATE_CODES, counts)}
