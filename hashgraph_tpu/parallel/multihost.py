"""Multi-host pool: the slot axis sharded across the devices of many
processes, with process-local data feeding.

Execution model (the scaling-book recipe applied to consensus):

- **Slot ownership follows device ownership.** The global pool's slot axis
  shards over the full mesh; each process owns the contiguous slot ranges
  of its addressable devices (`local_slot_range`).
- **Control plane is replicated.** Allocation, release, snapshot loads, and
  timeout sweeps must be invoked with identical arguments on every process
  (standard jax SPMD: same program, same global shapes). Host bookkeeping
  stays consistent because these ops are deterministic.
- **Data plane is process-local.** Each process ingests only votes for its
  own slots (the embedder's shard-aware relay forwards votes to the owning
  host — consensus state itself never crosses DCN). The routed batch is
  materialized per process via ``jax.make_array_from_process_local_data``:
  nobody ever holds the global batch, and readbacks pull only addressable
  shards. Per-dispatch grid shapes are agreed with one tiny allgather so
  every process compiles the same program.
- **Events are emitted by the owning process only** (ingest statuses and
  timeout transitions are returned for local slots), so a fleet of engine
  front-ends never double-publishes — asserted end-to-end by the 2-process
  ``TpuConsensusEngine``-on-``MultiHostPool`` test
  (tests/test_multihost.py::test_two_process_engine_on_multihost_pool),
  which drives the full engine surface: replicated control plane,
  local-only scalar + columnar ingest with agreed dispatch cadence,
  misrouted-vote rejection, collective timeouts and sweeps.
- **Signatures verify where votes arrive** (host CPU, native runtime), so
  adding hosts scales verification linearly with the fleet, independent of
  the TPU topology.

The 2-process CPU integration test (tests/test_multihost.py) spawns real
``jax.distributed`` processes and drives allocation, cross-process ingest,
psum stats, and the timeout sweep end-to-end.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.experimental import multihost_utils

from .mesh import PROPOSAL_AXIS, consensus_mesh
from .sharded import ShardedPool

__all__ = [
    "initialize_distributed",
    "distributed_consensus_mesh",
    "local_slot_range",
    "agree_trace_context",
    "collectives_available",
    "is_collectives_gap",
    "COLLECTIVES_GAP_SIGNATURE",
    "MultiHostPool",
]


# The exact backend-gap signature raised by jaxlib CPU backends that
# implement no multi-process collectives (sharded computations across
# jax.distributed processes fail at dispatch with this message). It is
# BOTH the runtime capability probe's discriminator (see
# collectives_available) and the only failure the two subprocess
# integration tests in tests/test_multihost.py may skip on — anything
# else still fails them.
COLLECTIVES_GAP_SIGNATURE = (
    "Multiprocess computations aren't implemented on the CPU backend"
)


def is_collectives_gap(exc: "BaseException | str") -> bool:
    """Whether an exception (or its message) is the known CPU-backend
    multi-process collectives gap — the one condition under which the
    fleet demotes cross-host tallies from psum to fabric frames."""
    return COLLECTIVES_GAP_SIGNATURE in str(exc)


_collectives_probe: "bool | None" = None


def collectives_available(refresh: bool = False) -> bool:
    """Runtime capability probe: can this process run cross-process
    collectives?

    Single-process (no ``jax.distributed`` fleet): trivially True — every
    collective is an in-process reduction, which all backends implement.
    Multi-process: run ONE tiny allgather and catch the CPU-backend gap
    signature (:data:`COLLECTIVES_GAP_SIGNATURE`). This is the runtime
    analogue of what used to be a test-only skip-guard: the federation
    tally path consults it to pick real psum collectives where the
    backend supports them and the gossip fabric's ``OP_FLEET_TALLY``
    frames where it doesn't. Any OTHER failure re-raises — a real bug
    must not silently demote the tally path.

    Memoized (a backend cannot gain the capability mid-process);
    ``refresh=True`` re-probes."""
    global _collectives_probe
    if _collectives_probe is not None and not refresh:
        return _collectives_probe
    if jax.process_count() <= 1:
        _collectives_probe = True
        return True
    try:
        multihost_utils.process_allgather(np.ones(1, np.int32))
    except Exception as exc:
        if is_collectives_gap(exc):
            _collectives_probe = False
            return False
        raise
    _collectives_probe = True
    return True


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Bring up jax.distributed for a multi-host deployment.

    On TPU pods the arguments auto-detect from the environment; pass them
    explicitly elsewhere. Call once per process before any jax computation.
    """
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def distributed_consensus_mesh(axis_name: str = PROPOSAL_AXIS):
    """The 1-D consensus mesh spanning every device of every process."""
    return consensus_mesh(axis_name=axis_name)


def agree_trace_context(ctx=None):
    """Fleet-wide distributed-trace agreement: every process adopts
    process 0's trace context so the replicated control plane's spans
    (allocation, timeout sweeps) stitch into ONE causal trace instead of
    N disjoint ones.

    Collective — call with identical cadence on every process (like the
    pool's control-plane ops), typically right after minting a root
    context on process 0::

        ctx = agree_trace_context(TraceContext.generate())
        with use_context(ctx):
            engine.sweep_timeouts(now)   # spans share one trace_id fleet-wide

    ``ctx`` defaults to this process's ambient context
    (:func:`~hashgraph_tpu.obs.trace.current_context`); processes other
    than 0 may pass anything (or nothing) — process 0's value wins.
    Returns the agreed context, or None when process 0 had none.
    """
    from ..obs.trace import TRACE_WIRE_BYTES, TraceContext, current_context

    local = ctx if ctx is not None else current_context()
    wire = np.frombuffer(
        local.to_wire() if local is not None else bytes(TRACE_WIRE_BYTES),
        np.uint8,
    )
    gathered = np.asarray(multihost_utils.process_allgather(wire)).reshape(
        -1, TRACE_WIRE_BYTES
    )
    agreed = gathered[0].tobytes()
    if not any(agreed):
        return None
    return TraceContext.from_wire(agreed)


def local_slot_range(
    capacity_per_device: int, mesh=None
) -> tuple[int, int]:
    """The global slot interval owned by this process: [start, stop).

    With slots laid out contiguously per device in mesh order, a process
    owns the union of its addressable devices' ranges (contiguous on
    standard TPU topologies where local devices are consecutive in the
    mesh).
    """
    mesh = mesh if mesh is not None else distributed_consensus_mesh()
    start, stop = _local_device_span(mesh)
    return (start * capacity_per_device, stop * capacity_per_device)


def _local_device_span(mesh) -> tuple[int, int]:
    """[start, stop) positions of this process's devices in mesh order."""
    devices = list(mesh.devices.flat)
    local = [
        i for i, d in enumerate(devices) if d.process_index == jax.process_index()
    ]
    if not local:
        return (0, 0)
    start, stop = min(local), max(local) + 1
    if local != list(range(start, stop)):
        raise RuntimeError(
            "this process's devices are not contiguous in the mesh; "
            "reorder the mesh so slot ranges stay process-local"
        )
    return (start, stop)


class MultiHostPool(ShardedPool):
    """ShardedPool across the devices of many ``jax.distributed`` processes.

    Contract (module docstring has the full model):
    - control-plane calls (``allocate_batch``, ``release``, ``load_rows``,
      ``timeout``) are collective with IDENTICAL arguments on every process;
    - ``ingest_async``/``complete_all`` are collective in *cadence* (every
      process dispatches the same number of batches, empty ones included)
      but each process passes only votes for its own slots
      (``local_slot_range``); statuses/transitions come back for local
      votes/slots only, so each process emits events for what it owns;
    - per-dispatch grid shapes are agreed via one small allgather.
    """

    def __init__(self, capacity_per_device, voter_capacity, mesh=None):
        mesh = mesh if mesh is not None else distributed_consensus_mesh()
        # Span first: _init_device_arrays (called from the base ctor) needs
        # it to materialize process-local sections.
        self._dev_lo, self._dev_hi = _local_device_span(mesh)
        super().__init__(capacity_per_device, voter_capacity, mesh)

    def local_slots(self) -> tuple[int, int]:
        """The global slot interval [start, stop) this process owns."""
        return (
            self._dev_lo * self.local_capacity,
            self._dev_hi * self.local_capacity,
        )

    # ── Process-local materialization ─────────────────────────────────

    def _init_device_arrays(self) -> None:
        """Initial pool arrays built from process-local sections (a plain
        device_put cannot target other hosts' devices)."""
        from ..ops.decide import STATE_FREE

        p, v = self.capacity, self.voter_capacity
        self._state = self._put_batch(np.full(p, STATE_FREE, np.int32))
        self._yes = self._put_batch(np.zeros(p, np.int32))
        self._tot = self._put_batch(np.zeros(p, np.int32))
        self._vote_mask = self._put_batch(np.zeros((p, v), bool))
        self._vote_val = self._put_batch(np.zeros((p, v), bool))
        self._n = self._put_batch(np.zeros(p, np.int32))
        self._req = self._put_batch(np.zeros(p, np.int32))
        self._cap = self._put_batch(np.zeros(p, np.int32))
        self._gossip = self._put_batch(np.zeros(p, bool))
        self._liveness = self._put_batch(np.zeros(p, bool))

    def _put_batch(self, arr: np.ndarray) -> jax.Array:
        """Build the global [D*B, ...] device array from this process's
        section only — no host ever materializes another host's rows on
        device (`jax.make_array_from_process_local_data`)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(self.axis) if arr.ndim == 1 else P(self.axis, None)
        sharding = NamedSharding(self.mesh, spec)
        rows_per_dev = arr.shape[0] // self.n_devices
        lo = self._dev_lo * rows_per_dev
        hi = self._dev_hi * rows_per_dev
        return jax.make_array_from_process_local_data(
            sharding, np.ascontiguousarray(arr[lo:hi]), arr.shape
        )

    @staticmethod
    def _local_block(garr) -> np.ndarray:
        """Assemble this process's contiguous section of a 1-D-sharded
        global array from its addressable shards (device order)."""
        shards = sorted(
            garr.addressable_shards,
            key=lambda s: s.index[0].start if s.index[0].start is not None else 0,
        )
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)

    # ── Data plane ─────────────────────────────────────────────────────

    def ingest_async(self, slots, lanes, values, now):
        """Collective dispatch; ``slots`` must all be process-local. Unlike
        the single-host pools an EMPTY batch still dispatches (the other
        processes' batches are part of the same global program) — the
        inherited grouped path dispatches unconditionally, preserving that.
        """
        from ..ops.ingest import group_batch

        slots = np.asarray(slots, np.int64)
        lo, hi = self.local_slots()
        if slots.size and not ((slots >= lo) & (slots < hi)).all():
            raise ValueError(
                f"ingest batch contains non-local slots (this process owns "
                f"[{lo}, {hi})); route votes to the owning host first"
            )
        uniq, row, col, depth = group_batch(slots)
        return self.ingest_async_grouped(
            uniq, row, col, depth, lanes, values, now
        )

    def _dispatch_ingest(self, slot_pack, grid_pack):
        return self._fleet_routed_ingest(
            slot_pack, grid_pack, self._sharded_ingest
        )

    def _dispatch_ingest_fresh(self, slot_pack, grid_pack, laneless=False):
        """Fleet closed-form ingest: same shape-agreement + routing as the
        scan dispatch (the caller — the engine — has already agreed
        fleet-wide that this call takes the fresh path; the laneless flag
        derives from voter_capacity, identical on every process)."""
        return self._fleet_routed_ingest(
            slot_pack,
            grid_pack,
            self._sharded_fresh_ingest_laneless
            if laneless
            else self._sharded_fresh_ingest,
        )

    def _fleet_routed_ingest(self, slot_pack, grid_pack, kernel):
        """Agree padded shapes across processes (every process must compile
        and run the same global program), then reuse the shared routing
        body with the agreed buckets and block-local row positions."""
        from ..engine.pool import _bucket

        s_count, depth = grid_pack.shape
        local_shape = np.array(
            [_bucket(s_count), _bucket(depth, floor=1)], np.int64
        )
        agreed = multihost_utils.process_allgather(local_shape)
        return self._routed_ingest(
            slot_pack,
            grid_pack,
            kernel,
            bucket_s=int(agreed[..., 0].max()),
            bucket_l=int(agreed[..., 1].max()),
            row_offset=self._dev_lo,
        )

    def complete_all(self, pendings):
        """Block on in-flight ingests, pulling only addressable shards
        (one device_get for all of them)."""
        shard_lists = []
        for pending in pendings:
            shards = sorted(
                pending.out.addressable_shards,
                key=lambda s: s.index[0].start
                if s.index[0].start is not None
                else 0,
            )
            shard_lists.append([s.data for s in shards])
        flat = jax.device_get([d for lst in shard_lists for d in lst])
        outs = []
        pos = 0
        for lst in shard_lists:
            outs.append(np.concatenate(flat[pos : pos + len(lst)], axis=0))
            pos += len(lst)
        return [
            self._finish(pending, out) for pending, out in zip(pendings, outs)
        ]

    def complete(self, pending):
        return self.complete_all([pending])[0]

    # ── Control plane ──────────────────────────────────────────────────

    def timeout(self, slots):
        """Collective (identical ``slots`` everywhere); returns only this
        process's slots — the owner emits the events. The host state mirror
        is synced for ALL requested slots (one small allgather), so
        ``state_of``/``state_counts`` — and any engine layered on top — stay
        truthful for non-local slots after a sweep."""
        if not slots:
            return []
        self._check_no_inflight("timeout")
        self._flush_activations()
        slot_arr = np.asarray(slots, np.int64)
        slot_grid, _, rows, bucket = self._route(slot_arr, [])
        self._state, row_state = self._sharded_timeout(
            self._state, self._yes, self._tot, self._n, self._req,
            self._liveness, self._put_batch(slot_grid),
        )
        local_block = self._local_block(row_state)
        lo_rows = self._dev_lo * bucket
        hi_rows = self._dev_hi * bucket
        local_states = np.full(len(slots), -1, np.int64)
        out = []
        for i, slot in enumerate(slots):
            r = int(rows[i])
            if lo_rows <= r < hi_rows:
                new_state = int(local_block[r - lo_rows])
                local_states[i] = new_state
                out.append((int(slot), new_state))
        # Every slot is local to exactly one process; max over the gathered
        # per-process vectors (-1 where non-local) recovers each slot's
        # owner-observed state on every process.
        gathered = multihost_utils.process_allgather(local_states)
        global_states = np.asarray(gathered).reshape(-1, len(slots)).max(axis=0)
        self._state_host[slot_arr] = global_states.astype(np.int32)
        return out

    def sync_states(self) -> None:
        """Refresh the host state mirror for non-local slots.

        Ingest transitions are observed owner-locally by design (zero DCN on
        the hot path), so remote slots' mirrored states lag until the next
        collective touch. This collective (identical cadence on every
        process; requires homogeneous per-process device counts) allgathers
        each process's local mirror block so ``state_of``/``state_counts``
        are globally exact at a quiesce/stats point."""
        self._check_no_inflight("sync_states")
        lo, hi = self.local_slots()
        gathered = np.asarray(
            multihost_utils.process_allgather(
                np.concatenate(
                    [np.array([lo], np.int64), self._state_host[lo:hi].astype(np.int64)]
                )
            )
        ).reshape(jax.process_count(), -1)
        for row in gathered:
            start = int(row[0])
            block = row[1:].astype(np.int32)
            self._state_host[start : start + len(block)] = block
