"""TpuConsensusEngine: the batch-first consensus service backed by the pool.

This is the framework's flagship execution path (SURVEY §7, BASELINE north
star): the same observable semantics as :class:`~hashgraph_tpu.service.
ConsensusService` — scalar entry points included — but with all tally/round/
decision state dense on device and a native batch API (:meth:`ingest_votes`)
the scalar calls funnel into. Host work per vote is limited to what XLA
cannot do: signature/hash validation (pluggable scheme, CPU), owner→lane
dictionary lookups, and event emission.

Division of labor:
- device (ProposalPool): tallies, vote masks, round-cap projection, the
  decision kernel, timeout sweeps — everything order-sensitive is replayed
  arrival-ordered by the scan inside the ingest kernel;
- host (this class): vote build/validation (reference: src/utils.rs:55-171),
  scope configs and their resolution precedence (src/service.rs:440-484),
  per-scope session registries with LRU eviction (src/service.rs:512-522),
  proposal reconstruction for gossip, and the event bus.
"""

from __future__ import annotations

import functools
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Generic, Hashable, TypeVar

import numpy as np

from ..errors import (
    ConsensusError,
    ConsensusFailed,
    InsufficientVotesAtTimeout,
    ProposalAlreadyExist,
    SessionNotFound,
    StatusCode,
    UserAlreadyVoted,
    error_for_code,
)
from ..events import BroadcastEventBus, ConsensusEventBus
from ..obs import (
    CHAIN_KERNEL_SECONDS,
    CHAIN_SUFFIX_LENGTH,
    DECISION_LATENCY,
    DECISIONS_TOTAL,
    DEFAULT_SIZE_BUCKETS,
    DEVICE_INGEST_SECONDS,
    INGEST_BATCH_SIZE,
    LIVE_PROPOSALS,
    PROPOSALS_CREATED_TOTAL,
    TIER_BYTES,
    TIER_DEMOTED_SESSIONS,
    TIER_DEMOTIONS_TOTAL,
    TIER_GC_TOTAL,
    TIER_PROMOTIONS_TOTAL,
    TIMEOUTS_FIRED_TOTAL,
    VERIFIED_SIGNATURES_TOTAL,
    VERIFY_BATCH_SECONDS,
    VOTE_TABLE_OCCUPANCY,
    VOTES_ACCEPTED_TOTAL,
    VOTES_TOTAL,
    WIRE_APPLY_ROWS_TOTAL,
    WIRE_DEVICE_DISPATCHES_TOTAL,
    TimelineStore,
    flight_recorder,
    observed_span,
    slo_engine,
)
from ..obs import (
    ENGINE_EQUIVOCATION_PROBE_FALLBACKS_TOTAL,
    ENGINE_EQUIVOCATION_PROBE_ROWS_TOTAL,
    ENGINE_HEALTH_SECONDS,
    ENGINE_LOCK_WAIT_SECONDS,
    ENGINE_PLAN_SECONDS,
    ENGINE_POST_APPLY_SECONDS,
    ENGINE_RESOLVE_SECONDS,
    ENGINE_VALIDATE_SECONDS,
)
from ..obs.prometheus import _escape_label
from ..obs import health_monitor as default_health_monitor
from ..obs import install_jax_telemetry
from ..obs import registry as default_registry
from ..obs.health import HealthMonitor
from ..obs.registry import Counter
from ..obs.timeline import OUTCOME_FAILED, OUTCOME_NO, OUTCOME_YES
from ..obs.trace import TraceContext, current_context, trace_store
from ..ops.decide import (
    STATE_ACTIVE,
    STATE_FAILED,
    STATE_REACHED_NO,
    STATE_REACHED_YES,
)
from ..protocol import (
    _F64_EPSILON,
    _TWO_THIRDS,
    COMPUTE_CHAIN,
    build_vote,
    calculate_required_votes,
    calculate_threshold_based_value,
    compute_vote_hash,
    regenerate_until_unique,
    validate_proposal_timestamp,
    validate_vote,
    validate_vote_chain,
)
from ..scope_config import (
    DEFAULT_TIMEOUT_SECONDS,
    ScopeConfig,
    ScopeConfigBuilder,
)
from .adaptive import AdaptiveTimeoutBook
from ..service import DEFAULT_MAX_SESSIONS_PER_SCOPE, ConsensusStats
from ..session import ConsensusConfig, ConsensusSession, ConsensusState
from ..signing import ConsensusSignatureScheme
from ..tracing import tracer as default_tracer
from ..types import (
    ConsensusEvent,
    ConsensusFailedEvent,
    ConsensusReached,
    CreateProposalRequest,
)
from ..wire import Proposal, Vote, normalize_wire_votes
from .pool import ProposalPool
from .session_sync import allocate_slot, load_session_rows, state_code_of
from .verify_cache import MISS, VerifiedVoteCache

Scope = TypeVar("Scope", bound=Hashable)

_U32_MAX = 0xFFFFFFFF


def hashlib_sha256_8(data: bytes) -> bytes:
    """First 8 bytes of SHA-256 — the admission-cache scheme tag (a
    stable, collision-negligible namespace for a handful of scheme
    types; full digests would fatten every cache key for nothing)."""
    import hashlib

    return hashlib.sha256(data).digest()[:8]


def _canonical_scope_bytes(scope) -> bytes:
    """Process-independent byte encoding of a scope for the multi-host
    deterministic pid derivation. repr() is NOT safe here: the default
    object repr embeds a memory address, which would silently de-sync the
    replicated control plane — the exact failure deterministic pids exist
    to prevent — so non-canonical scope types are a hard error in
    multi-host mode."""
    if isinstance(scope, str):
        return b"s:" + scope.encode()
    if isinstance(scope, (bytes, bytearray)):
        return b"b:" + bytes(scope)
    if isinstance(scope, int):
        # int(scope) so bool encodes identically to the int it equals
        # (True and 1 are the same dict key, so they are the same scope).
        return b"i:" + str(int(scope)).encode()
    raise TypeError(
        f"multi-host mode requires str/bytes/int scopes (canonical "
        f"cross-process encoding); got {type(scope).__name__}"
    )

_STATE_TO_SCALAR = {
    STATE_ACTIVE: ConsensusState.active(),
    STATE_FAILED: ConsensusState.failed(),
    STATE_REACHED_YES: ConsensusState.reached(True),
    STATE_REACHED_NO: ConsensusState.reached(False),
}

# Timeline outcome labels per dense lifecycle state (ACTIVE maps to None:
# a transition list never carries it, but the .get guard is cheap).
_OUTCOME_OF_STATE = {
    STATE_REACHED_YES: OUTCOME_YES,
    STATE_REACHED_NO: OUTCOME_NO,
    STATE_FAILED: OUTCOME_FAILED,
}


class _CreationCols:
    """Per-proposal scalar columns accumulated while batch creation mints
    its Proposal objects. The allocator turns these plain int/float/bool
    lists into device config arrays with np.asarray — several times cheaper
    than re-walking the freshly-built objects with fromiter generators."""

    __slots__ = ("n", "expiry", "liveness", "thr", "gossip", "maxr")

    def __init__(self):
        self.n: list[int] = []
        self.expiry: list[int] = []
        self.liveness: list[bool] = []
        self.thr: list[float] = []
        self.gossip: list[bool] = []
        self.maxr: list[int] = []


@dataclass(slots=True)
class SessionRecord(Generic[Scope]):
    """Host-side view of one session (scalar bookkeeping the device doesn't
    need; vote bytes kept for gossip reconstruction and chain linking,
    reference: src/utils.rs:62-77).

    Two substrates share this record type: pooled sessions (``slot`` >= 0,
    tallies live in device HBM) and host-spilled sessions (``session`` set,
    ``slot`` a negative synthetic id) — the graceful-degrade path for
    proposals the fixed pool geometry cannot hold. The reference service has
    no capacity limits at all (src/service.rs:86-97), so spilling keeps the
    public API's envelope unbounded even though the device pool is not."""

    scope: Scope
    slot: int
    proposal: Proposal  # votes list appended in acceptance order
    config: ConsensusConfig
    created_at: int
    votes: dict[bytes, Vote] = field(default_factory=dict)  # accepted only
    session: ConsensusSession | None = None  # host fallback substrate
    # Opt-in columnar retention: verbatim wire bytes of accepted votes as
    # (arrival seq, packed blob, local offsets) chunks. Decoded lazily on
    # proposal export so a columnar-ingested session can be re-gossiped
    # with a chain-valid vote list; empty unless the caller passed
    # wire_votes to ingest_columnar. ``retained_cache`` memoizes the decode
    # (chunk-count keyed: retained_wire only grows by append).
    retained_wire: list[tuple[int, bytes, np.ndarray]] = field(default_factory=list)
    retained_cache: tuple[int, list[tuple[int, list[Vote]]]] | None = None
    # Per-record arrival clock: scalar accepted votes take one tick each,
    # every retained columnar chunk takes one tick, so exports can merge
    # the two paths back into true (call-granularity) arrival order.
    arrival_seq: int = 0
    scalar_seqs: list[int] = field(default_factory=list)
    # Wire-columnar chain continuity (ingest_wire_columnar): the
    # session's effective tail hash and accepted-owner set as tracked by
    # the validated wire path, plus a (retained chunks, scalar accepts)
    # sync stamp. While the stamp matches the record, the dangling-vote
    # guard keeps enforcing received-hash linkage across wire frames —
    # without this, any frame after the first would be permissive and a
    # dropped/reordered gossip stream could diverge peers. A mismatched
    # stamp (legacy pre-validated columnar ingest, interleaved paths)
    # falls back to the documented permissive behavior.
    wire_tail: bytes | None = None
    wire_seen: "set[bytes] | None" = None
    wire_sync: "tuple[int, int] | None" = None
    # True while EVERY retained chunk on this record came from the
    # validated wire path (ingest_wire_columnar): its accepts are
    # guard-ordered, so the merged retained+scalar chain stays
    # positionally comparable — the anti-entropy watermark and the fork
    # probe keep working on wire-fed sessions. The legacy pre-validated
    # columnar ingest flips it False (arbitrary order; the documented
    # permissive behavior).
    wire_only: bool = True
    # Distributed trace identity bound at create/process time (None when
    # the trace store is disabled or the session arrived via an untraced
    # batch path): every later span/instant for this session joins this
    # trace, and the wire layers serialize it alongside the proposal.
    trace: "TraceContext | None" = None
    # Tiered-lifecycle bookkeeping: logical timestamp of the session's
    # last accepted activity (registration, accepted vote, fired timeout
    # — the idle clock the per-scope ``demote_after`` / GC TTLs measure
    # against), and the per-scope registration sequence number that keeps
    # LRU tie-order identical across demote/promote round-trips.
    last_activity: int = 0
    seq: int = 0

    @classmethod
    def fresh_pooled(
        cls, scope, slot: int, proposal, config, created_at: int
    ) -> "SessionRecord":
        """Fast constructor for a just-allocated pooled session (no spill
        substrate, empty collections). Batch registration creates one record
        per proposal, and the dataclass __init__'s keyword dispatch is ~2x
        the cost of direct slot stores at that volume."""
        rec = cls.__new__(cls)
        rec.scope = scope
        rec.slot = slot
        rec.proposal = proposal
        rec.config = config
        rec.created_at = created_at
        rec.votes = {}
        rec.session = None
        rec.retained_wire = []
        rec.retained_cache = None
        rec.arrival_seq = 0
        rec.scalar_seqs = []
        rec.wire_tail = None
        rec.wire_seen = None
        rec.wire_sync = None
        rec.wire_only = True
        rec.trace = None
        rec.last_activity = created_at
        rec.seq = 0
        return rec

    def next_arrival_seq(self) -> int:
        seq = self.arrival_seq
        self.arrival_seq += 1
        return seq

    def bump_round(self, accepted: int) -> None:
        """Host mirror of the device round update
        (reference: src/session.rs:351-366)."""
        if accepted <= 0:
            return
        if self.config.use_gossipsub_rounds:
            if self.proposal.round == 1:
                self.proposal.round = 2
        else:
            self.proposal.round = min(self.proposal.round + accepted, _U32_MAX)


class PendingVoteVerdicts:
    """Handle for an in-flight admission-verify prepass
    (:meth:`TpuConsensusEngine.verify_votes_async`): ``collect()`` blocks
    until the signature batch resolves and returns ``(verdicts,
    computed_hashes)`` aligned with the submitted votes. Idempotent —
    the first collect does the waiting. While uncollected, the crypto
    runs on the native verify pool with no GIL involvement, so the
    interpreter is free to drive device ingest of an earlier batch."""

    __slots__ = ("_collect_fn", "_result")

    def __init__(self, collect_fn):
        self._collect_fn = collect_fn
        self._result = None

    def collect(self) -> "tuple[list, list[bytes]]":
        if self._collect_fn is not None:
            self._result = self._collect_fn()
            self._collect_fn = None
        return self._result


class WireVotePrepass:
    """Handle for an in-flight wire-columnar validation prepass
    (:meth:`TpuConsensusEngine.wire_verify_begin`): ``pre_status`` holds
    the structural/hash verdicts already decided (0 = still live),
    ``crypto_rows`` the row indices whose signatures were submitted, and
    ``collect()`` blocks for their verdicts (idempotent). While
    uncollected, the crypto runs on the native verify pool with no GIL
    involvement — the bridge's reader thread starts the prepass for
    frame k+1 while frame k's apply runs on the serial lane.

    ``buf`` caches the frame's vote region as ``bytes`` when the prepass
    sliced it for crypto, so the apply stage (and a durable wrapper's
    WAL blob) reuse ONE copy instead of re-running ``tobytes()`` per
    stage — the prepass and apply always see the same ``data`` array."""

    __slots__ = ("pre_status", "crypto_rows", "buf", "_collect_fn", "_result")

    def __init__(self, pre_status, crypto_rows, collect_fn, buf=None):
        self.pre_status = pre_status
        self.crypto_rows = crypto_rows
        self.buf = buf
        self._collect_fn = collect_fn
        self._result = None

    def collect(self) -> list:
        if self._collect_fn is not None:
            self._result = self._collect_fn()
            self._collect_fn = None
        return self._result


class _TierEntry:
    """One demoted session: the exact PR-8 snapshot ITEM_SESSION payload
    bytes (:func:`hashgraph_tpu.sync.snapshot.encode_session_item` — the
    canonical serialized session, signed vote wire included, so promotion
    needs no re-signing and ``state_fingerprint`` hashes the same item
    bytes whether a session is live or demoted) plus the scalar metadata
    reads need WITHOUT decoding: lifecycle state for stats, created_at +
    seq for LRU ranking, expiry for the timeout sweep, last_activity for
    the GC TTL."""

    __slots__ = (
        "item",
        "state",  # snapshot state code: 0 active / 1 reached / 2 failed
        "result",  # meaningful iff state == 1
        "created_at",
        "seq",
        "expiry",
        "last_activity",
    )

    def __init__(self, item, state, result, created_at, seq, expiry, last_activity):
        self.item = item
        self.state = state
        self.result = result
        self.created_at = created_at
        self.seq = seq
        self.expiry = expiry
        self.last_activity = last_activity


# Sentinel: "compute the signature prepass inside ingest_votes" (the
# non-pipelined default) as opposed to an explicit None / prepass handle
# handed in by ingest_votes_pipelined.
_PREPASS_INLINE = object()


class TpuConsensusEngine(Generic[Scope]):
    """Batch consensus engine with the ConsensusService API surface.

    Capacity is fixed at construction (XLA static shapes): ``capacity``
    concurrent sessions across all scopes, ``voter_capacity`` voter lanes per
    proposal. Scalar and batch entry points share one code path: every
    mutation flows through :meth:`ingest_votes`.
    """

    def __init__(
        self,
        signer: ConsensusSignatureScheme,
        event_bus: ConsensusEventBus[Scope] | None = None,
        capacity: int | None = None,
        voter_capacity: int | None = None,
        max_sessions_per_scope: int = DEFAULT_MAX_SESSIONS_PER_SCOPE,
        pool: ProposalPool | None = None,
        verify_cache: "VerifiedVoteCache | None | str" = "default",
        health_monitor: "HealthMonitor | None" = None,
    ):
        self._signer = signer
        # Per-peer health accounting (scorecards, equivocation/fork
        # evidence, liveness watchdog — obs.health). Engines default to
        # the process-wide monitor so co-hosted peers accumulate one
        # fleet view; pass a private HealthMonitor for isolation. Gated
        # off during WAL replay (_health_live): replayed anomalies were
        # recorded before the crash and must not double-count.
        self.health: HealthMonitor = (
            health_monitor if health_monitor is not None else default_health_monitor
        )
        self._health_live = True
        # Memoized vote-admission verdicts (verify each unique vote once —
        # the redelivery/incremental-chain amortization, see verify_cache
        # module docstring). "default" builds a per-engine cache; pass a
        # shared instance to pool verdicts across engines (BridgeServer
        # does, one cache per server process), or None to disable —
        # disabled restores the pre-cache verification flow byte for byte.
        if isinstance(verify_cache, str) and verify_cache != "default":
            # Any other string (e.g. BridgeServer's "shared" sentinel, or
            # a typo) would be stored as the cache object and crash at the
            # first ingest — fail at the call site instead.
            raise ValueError(
                'verify_cache must be "default", a VerifiedVoteCache, or None'
            )
        self._verify_cache: VerifiedVoteCache | None = (
            VerifiedVoteCache() if verify_cache == "default" else verify_cache
        )
        # Scheme-identity namespace for admission keys: a shared cache
        # serving engines with different signature schemes must never
        # cross-serve verdicts (scheme A's True is not scheme B's).
        scheme = type(signer)
        self._verify_scheme_tag = hashlib_sha256_8(
            f"{scheme.__module__}.{scheme.__qualname__}".encode()
        )
        self._event_bus: ConsensusEventBus[Scope] = (
            event_bus if event_bus is not None else BroadcastEventBus()
        )
        # An injected pool (e.g. parallel.ShardedPool over a device mesh)
        # swaps the execution substrate without touching engine semantics.
        if pool is not None:
            if capacity is not None or voter_capacity is not None:
                raise ValueError(
                    "pass capacity/voter_capacity OR an explicit pool, not "
                    "both (the pool's own geometry wins)"
                )
            self._pool = pool
        else:
            self._pool = ProposalPool(
                capacity if capacity is not None else 4096,
                voter_capacity if voter_capacity is not None else 64,
            )
        self._max_sessions_per_scope = max_sessions_per_scope
        # Multi-host awareness: a pool exposing local_slots() shards the
        # slot axis across jax.distributed processes (parallel.MultiHostPool).
        # The engine then runs SPMD: control-plane calls (create/process
        # proposal, delete_scope, timeouts) replicated with IDENTICAL
        # arguments on every process, vote ingest process-local, and every
        # event emitted by exactly one owning process (see _owns_slot).
        self._multihost = hasattr(self._pool, "local_slots")
        if self._multihost:
            import jax

            # process_index is immutable for the process lifetime; cache it
            # off the event-gating paths.
            self._process_zero = jax.process_index() == 0
        else:
            self._process_zero = True
        self.tracer = default_tracer
        # Distributed-trace peer label: spans this engine records are
        # attributed to its signer identity, so one process hosting many
        # bridge peers still yields per-peer stitched timelines.
        self._trace_peer = "peer:" + signer.identity().hex()[:12]
        # Always-on metrics (process-wide registry). Instruments are
        # resolved once here so the per-batch hot paths pay attribute
        # loads, not registry dict probes.
        self.metrics = default_registry
        self._m_votes_total = self.metrics.counter(VOTES_TOTAL)
        self._m_votes_accepted = self.metrics.counter(VOTES_ACCEPTED_TOTAL)
        self._m_decisions = self.metrics.counter(DECISIONS_TOTAL)
        self._m_proposals = self.metrics.counter(PROPOSALS_CREATED_TOTAL)
        self._m_timeouts = self.metrics.counter(TIMEOUTS_FIRED_TOTAL)
        self._m_batch_size = self.metrics.histogram(
            INGEST_BATCH_SIZE, DEFAULT_SIZE_BUCKETS
        )
        self._m_verify = self.metrics.histogram(VERIFY_BATCH_SECONDS)
        # Signatures actually handed to the scheme (cache hits excluded):
        # the base family plus a per-scheme labelled variant, so a mixed
        # fleet's dashboards can split Ed25519 batch traffic from ECDSA.
        self._m_verified_sigs = self.metrics.counter(VERIFIED_SIGNATURES_TOTAL)
        self._m_verified_sigs_scheme = self.metrics.counter(
            f'{VERIFIED_SIGNATURES_TOTAL}{{scheme="{_escape_label(scheme.__name__)}"}}'
        )
        # Dispatch amortization (the apply reactor's measured claim):
        # every ingest_wire_columnar call is one fused device dispatch;
        # rows ride along so votes_per_dispatch = rows / dispatches.
        self._m_wire_dispatches = self.metrics.counter(
            WIRE_DEVICE_DISPATCHES_TOTAL
        )
        self._m_wire_apply_rows = self.metrics.counter(WIRE_APPLY_ROWS_TOTAL)
        self._m_chain = self.metrics.histogram(CHAIN_KERNEL_SECONDS)
        self._m_device = self.metrics.histogram(DEVICE_INGEST_SECONDS)
        # The frame-level path's waits and the sub-stages that tile the
        # wire apply stage (one observed_span each, per frame).
        self._m_lock_wait = self.metrics.histogram(ENGINE_LOCK_WAIT_SECONDS)
        self._m_resolve = self.metrics.histogram(ENGINE_RESOLVE_SECONDS)
        self._m_validate = self.metrics.histogram(ENGINE_VALIDATE_SECONDS)
        self._m_health = self.metrics.histogram(ENGINE_HEALTH_SECONDS)
        self._m_plan = self.metrics.histogram(ENGINE_PLAN_SECONDS)
        self._m_post_apply = self.metrics.histogram(ENGINE_POST_APPLY_SECONDS)
        self._m_probe_rows = self.metrics.counter(
            ENGINE_EQUIVOCATION_PROBE_ROWS_TOTAL
        )
        self._m_probe_fallbacks = self.metrics.counter(
            ENGINE_EQUIVOCATION_PROBE_FALLBACKS_TOTAL
        )
        self._m_suffix_len = self.metrics.histogram(
            CHAIN_SUFFIX_LENGTH, DEFAULT_SIZE_BUCKETS
        )
        # Per-proposal lifecycle timelines (created → first_vote → decided /
        # timed_out), feeding the decision-latency histogram.
        self._timelines = TimelineStore(
            self.metrics.histogram(DECISION_LATENCY)
        )
        # SLO plane: every observed decision latency also lands in the
        # process SLO engine's sliding windows, carrying the scope's
        # declared objective (ScopeConfig.decide_p99_ms) and the bound
        # trace id so a breach's incident dump can link the causal trace.
        # The shard label is stamped by the fleet router at shard build
        # time; a standalone engine reports unlabelled.
        self._slo_shard: str | None = None
        self._timelines.slo_sink = self._slo_observe
        # Adaptive consensus-timeout learner (engine/adaptive.py):
        # advisory per-scope values the embedder polls via
        # adaptive_timeout(). Learning shares the _health_live gate with
        # the watchdog — WAL replay re-fires nothing and must not teach.
        self._adaptive = AdaptiveTimeoutBook()
        # Engine-state gauges sampled at scrape time, weakly bound: a
        # collected engine's contribution vanishes instead of freezing.
        ref = weakref.ref(self)

        def _live_proposals() -> int:
            engine = ref()
            return len(engine._records) if engine is not None else 0

        def _pool_occupancy() -> int:
            engine = ref()
            if engine is None:
                return 0
            # Claimed device slots (host-spilled sessions use negative
            # synthetic ids and hold no pool row). list() snapshots the
            # keys in one atomic C call — the scrape thread runs without
            # the engine lock, and iterating the live dict would race
            # with a concurrent insert/evict resize.
            return sum(1 for s in list(engine._records) if s >= 0)

        self.metrics.register_gauge(LIVE_PROPOSALS, _live_proposals, owner=self)
        self.metrics.register_gauge(
            VOTE_TABLE_OCCUPANCY, _pool_occupancy, owner=self
        )

        def _tier_sessions() -> int:
            engine = ref()
            return engine._tier_count if engine is not None else 0

        def _tier_bytes() -> int:
            engine = ref()
            return engine._tier_bytes if engine is not None else 0

        self.metrics.register_gauge(
            TIER_DEMOTED_SESSIONS, _tier_sessions, owner=self
        )
        self.metrics.register_gauge(TIER_BYTES, _tier_bytes, owner=self)
        self._m_tier_demotions = self.metrics.counter(TIER_DEMOTIONS_TOTAL)
        self._m_tier_promotions = self.metrics.counter(TIER_PROMOTIONS_TOTAL)
        self._m_tier_gc = self.metrics.counter(TIER_GC_TOTAL)
        # Device/XLA telemetry (live-buffer gauge provider is global;
        # this routes the persistent-compile-cache monitoring events onto
        # the registry). Idempotent, and this module already imports JAX
        # through the pool, so obs itself stays jax-free.
        install_jax_telemetry()
        # One engine-wide reentrant lock: the reference service is fully
        # thread-safe (whole-map RwLocks, src/storage.rs:192-193); the pool's
        # host mirrors and free lists need the same discipline. Coarse
        # locking is correct here — the device does the heavy lifting and
        # host sections are short.
        self._lock = threading.RLock()
        # This thread's nesting depth in the engine's public API (see
        # _synchronized): a reentrant call is told apart from a free lock
        # without RLock internals.
        self._lock_depth = threading.local()

        self._records: dict[int, SessionRecord[Scope]] = {}  # slot -> record
        self._index: dict[tuple[Scope, int], int] = {}  # (scope, pid) -> slot
        self._scopes: dict[Scope, list[int]] = {}  # scope -> slots (insertion order)
        self._scope_configs: dict[Scope, ScopeConfig] = {}
        self._next_host_slot = -1  # synthetic ids for host-spilled sessions
        # Columnar-path cache: per-scope sorted (pids, slots) arrays for
        # vectorized proposal-id resolution; dropped on any membership change.
        self._pid_tables: dict[Scope, tuple[np.ndarray, np.ndarray]] = {}
        self._pid_hashes: dict[Scope, _PidLookup] = {}
        # Fused multi-scope resolution cache: one composite-key hash per
        # distinct scope tuple of an ingest_columnar_multi call (small
        # bounded dict, so alternating scope orders don't thrash a single
        # slot). ANY scope's membership change clears the whole cache
        # outright (_drop_pid_cache) — cheaper than tracking which scopes
        # each tuple spans, and rebuilds are one vectorized pass.
        self._fused_pid_cache: dict[tuple, "_PidLookup"] = {}
        # ── Demoted session tier (storage tiering, ROADMAP item 5) ─────
        # scope -> {pid -> _TierEntry}: sessions moved out of their device
        # slot / host record into the compact serialized tier (the PR-8
        # snapshot item format). Insertion order per scope = demotion
        # order. Demoted sessions stay fully addressable — every public
        # read/mutation either pages them back in (_promote_key) or reads
        # through the tier without promoting (stats, enumerations,
        # save_to_storage), so callers observe an untier'd engine.
        self._tier: dict[Scope, dict[int, _TierEntry]] = {}
        self._tier_count = 0
        self._tier_bytes = 0
        # ACTIVE demoted sessions only, (scope, pid) -> expiry: the
        # timeout sweep must page an expired idle session back in to fire
        # its timeout; keeping this tiny side map means the sweep never
        # scans the (potentially huge) decided-session tier.
        self._tier_active: dict[tuple[Scope, int], int] = {}
        # Per-scope demoted-pid arrays for batch id draws (invalidated on
        # tier membership change; rebuilt lazily by _taken_pids).
        self._tier_pid_arrays: dict[Scope, np.ndarray] = {}
        # Scopes excluded from the lifecycle sweep's demote/GC policies
        # (fleet/federation pin a scope while migrating its shard so the
        # routers never page state mid-flip).
        self._pinned_scopes: set[Scope] = set()
        # Per-scope registration sequence (LRU tie order across tiers).
        self._scope_seq: dict[Scope, int] = {}
        # Reentrancy flag: promotion re-registers a session through
        # _register, which must not count it as a fresh proposal.
        self._promoting = False
        # Lifecycle gate (set_replay_mode): False during WAL replay.
        self._lifecycle_live = True
        # Engine-local tier traffic counts (occupancy() is per-engine;
        # the hashgraph_tier_* counters are process-wide).
        self._tier_demotions = 0
        self._tier_promotions = 0
        self._tier_gc = 0

    # ── Accessors ──────────────────────────────────────────────────────

    def signer(self) -> ConsensusSignatureScheme:
        return self._signer

    def set_replay_mode(self, on: bool) -> None:
        """Metrics gate for WAL recovery (DurableEngine.recover): replayed
        traffic drives the live ingest paths, but the decisions it
        re-applies were made before the crash — with replay mode on,
        timelines stamp them ``pre_decided`` (outcome without latency) and
        the decisions/timeouts counters hold still, so a restart doesn't
        collapse the decision-latency quantiles or re-count pre-crash
        decisions. Vote/proposal counters keep counting: they measure work
        this process performed, and replay IS work."""
        self._timelines.replay_mode = on
        # Health accounting pauses with replay for the same reason:
        # replayed equivocations/forks were evidenced before the crash;
        # re-recording them would double-count scorecards (evidence
        # itself dedups, but counters do not).
        self._health_live = not on
        # The tier lifecycle pauses too: TTL decisions depend on idle
        # clocks a snapshot restore does not carry, so replay must not
        # re-derive them — the live run's GC outcome arrives as explicit
        # KIND_GC records (applied via gc_sessions), and demotion is
        # pure cache management recovery legitimately skips.
        self._lifecycle_live = not on
        if on:
            # Throwaway instruments: the ingest paths inc attributes
            # unconditionally, so swapping the targets is cheaper (and
            # less invasive) than flag checks on every site.
            self._m_decisions = Counter("replay.decisions.discard")
            self._m_timeouts = Counter("replay.timeouts.discard")
        else:
            self._m_decisions = self.metrics.counter(DECISIONS_TOTAL)
            self._m_timeouts = self.metrics.counter(TIMEOUTS_FIRED_TOTAL)

    def event_bus(self) -> ConsensusEventBus[Scope]:
        return self._event_bus

    def pool(self) -> ProposalPool:
        return self._pool

    def verify_cache(self) -> VerifiedVoteCache | None:
        """The memoized-admission cache (None when disabled)."""
        return self._verify_cache

    @property
    def _scheme(self) -> type[ConsensusSignatureScheme]:
        return type(self._signer)

    # ── Proposal lifecycle ─────────────────────────────────────────────

    def create_proposal(
        self,
        scope: Scope,
        request: CreateProposalRequest,
        now: int,
        config: ConsensusConfig | None = None,
    ) -> Proposal:
        """Create a local proposal and claim a pool slot
        (reference: src/service.rs:183-209)."""
        wall0 = time.time()
        proposal = request.into_proposal(now)
        self._ensure_unique_pid(scope, proposal)
        # Same gauntlet the scalar service runs via from_proposal ->
        # validate_proposal (trivial for a fresh, vote-free proposal but
        # keeps the error surface identical, reference: src/utils.rs:106-120).
        validate_proposal_timestamp(proposal.expiration_timestamp, now)
        resolved = self._resolve_config(scope, config, proposal)
        record = self._register(scope, proposal, resolved, now)
        if trace_store.enabled:
            self._bind_trace(
                record, "consensus.create_proposal", scope, wall0
            )
        return proposal.clone()

    def _bind_trace(
        self, record: "SessionRecord[Scope]", span_name: str, scope, wall0: float
    ) -> None:
        """Mint (or continue) the distributed trace for a freshly
        registered session: the ambient context — set by the bridge from a
        frame suffix, or by an embedder around a gossip delivery — is the
        causal parent; with none this engine is the trace root. The bound
        context's span is recorded so every peer contributes at least one
        span per proposal to the stitched timeline."""
        parent = current_context()
        ctx = parent.child() if parent is not None else TraceContext.generate()
        record.trace = ctx
        tl = self._timelines.get(record.slot)
        if tl is not None and tl.proposal_id == record.proposal.proposal_id:
            tl.trace_hex = ctx.trace_id.hex()
        trace_store.record(
            span_name,
            ctx,
            wall0,
            time.time() - wall0,
            parent=parent.span_id if parent is not None else None,
            peer=self._trace_peer,
            attrs={
                "scope": str(scope),
                "proposal_id": record.proposal.proposal_id,
            },
        )

    def _slo_observe(self, tl, latency: float) -> None:
        """TimelineStore slo_sink: one call per observed decision (same
        gating as the latency histogram). Resolves the scope's declared
        objective and forwards to the process SLO engine — a single
        short-lock windowed-sketch update, cheap enough to stay always-on
        (the <5% bound is held by bench.py's slo-overhead A/B)."""
        cfg = self._scope_configs.get(tl.scope)
        objective = None
        if cfg is not None and cfg.decide_p99_ms is not None:
            objective = cfg.decide_p99_ms * 1e-3
        slo_engine.observe(
            tl.scope,
            latency,
            shard=self._slo_shard,
            objective_s=objective,
            trace_hex=tl.trace_hex,
        )
        # Vote-driven decisions (never timeout outcomes — those feed the
        # backoff side) decay the scope's learned timeout toward the SLO
        # engine's observed tail.
        if (
            self._health_live
            and not tl.by_timeout
            and cfg is not None
            and cfg.adaptive_timeout_enabled()
        ):
            self._adaptive.on_decided(
                tl.scope, cfg, slo_engine.observed_p99(tl.scope)
            )

    def _ensure_unique_pid(
        self, scope: Scope, proposal: Proposal, taken: set[int] | None = None
    ) -> None:
        """Collision-proof a locally-generated proposal id against live
        sessions in this scope and (for batch creation) earlier proposals in
        the same batch. Policy and rationale: protocol.regenerate_until_unique.

        Multi-host: uuid-random ids would differ per process and silently
        de-sync the replicated control plane, so the id is derived
        deterministically from the proposal's content plus the (replicated)
        per-scope population — identical create_proposal calls then mint the
        identical pid on every process.
        """
        if self._multihost:
            import hashlib

            taken_set = taken or set()
            seq = len(self._scopes.get(scope, []))
            salt = 0
            while True:
                digest = hashlib.sha256(
                    b"|".join(
                        [
                            _canonical_scope_bytes(scope),
                            proposal.name.encode(),
                            proposal.payload,
                            proposal.proposal_owner,
                            str(
                                (
                                    proposal.expected_voters_count,
                                    proposal.timestamp,
                                    seq,
                                    salt,
                                )
                            ).encode(),
                        ]
                    )
                ).digest()
                pid = int.from_bytes(digest[:4], "little") ^ int.from_bytes(
                    digest[4:8], "little"
                )
                if (
                    pid
                    and (scope, pid) not in self._index
                    and not self._tier_has(scope, pid)
                    and pid not in taken_set
                ):
                    proposal.proposal_id = pid
                    return
                salt += 1
                self.tracer.count("engine.pid_collisions")
        collisions = regenerate_until_unique(
            proposal,
            lambda pid: (scope, pid) in self._index
            or self._tier_has(scope, pid)
            or (taken is not None and pid in taken),
        )
        if collisions:
            self.tracer.count("engine.pid_collisions", collisions)

    def _draw_unique_pids(
        self, existing: np.ndarray, count: int
    ) -> np.ndarray:
        """Batch id draw: one urandom read, vectorized collision rejection
        against ``existing`` live pids and within the batch itself. Multi-
        scope creation passes the union of all target scopes' pids and
        slices one draw per scope — global uniqueness is stronger than the
        per-scope requirement and costs one pass instead of one per scope."""
        import os as _os

        ids = np.frombuffer(_os.urandom(4 * count), dtype=np.uint32).astype(
            np.int64
        )
        for _ in range(64):
            # 0 is treated as a collision: the multi-host deterministic path
            # rejects it and proto3 drops zero fields from the wire encoding
            # — both creation paths must mint from the same id space.
            bad = np.isin(ids, existing) | (ids == 0)
            _, first_idx, inverse, counts = np.unique(
                ids, return_index=True, return_inverse=True, return_counts=True
            )
            is_first = np.zeros(count, bool)
            is_first[first_idx] = True
            bad |= (counts[inverse] > 1) & ~is_first
            n_bad = int(bad.sum())
            if n_bad == 0:
                return ids
            self.tracer.count("engine.pid_collisions", n_bad)
            ids[bad] = np.frombuffer(
                _os.urandom(4 * n_bad), dtype=np.uint32
            ).astype(np.int64)
        raise RuntimeError("could not draw unique proposal ids")  # pragma: no cover

    def create_proposals(
        self,
        scope: Scope,
        requests: list[CreateProposalRequest],
        now: int,
        config: ConsensusConfig | None = None,
    ) -> list[Proposal]:
        """Batch counterpart of create_proposal: one device dispatch claims
        and configures every slot (pool.allocate_batch), instead of one
        dispatch per proposal. No reference analogue (its creation path is a
        scalar call, src/service.rs:183-209) — this is the TPU-native bulk
        feed for large concurrent-proposal populations (BASELINE configs
        3-5). Success semantics match calling create_proposal in a loop;
        the error path is batch-atomic (any invalid request raises before
        anything registers, unlike the loop which keeps earlier items).
        """
        return self.create_proposals_multi([(scope, requests)], now, config)[0]

    def create_proposals_multi(
        self,
        items: "list[tuple[Scope, list[CreateProposalRequest]]]",
        now: int,
        config: ConsensusConfig | None = None,
    ) -> "list[list[Proposal]]":
        """Multi-scope batch creation (mirror of :meth:`ingest_columnar_multi`):
        ONE device dispatch claims slots for every scope's proposals instead
        of one dispatch per scope — the registration half of the config-5
        churn shape. Returns one Proposal list per input item, in order.
        Scopes must be distinct within one call (id uniqueness is checked
        against registered sessions, which a same-call sibling batch is
        not yet). A scope near its session cap falls back to the scalar
        path for that scope only (reference eviction semantics interleave
        with insertion there); fallback scopes run AFTER the batched
        allocation, so device-slot priority deterministically favors the
        batched population when the pool is nearly full."""
        seen: set = set()
        for scope, _ in items:
            if scope in seen:
                raise ValueError("create_proposals_multi: duplicate scope")
            seen.add(scope)
        out: list = [None] * len(items)
        entries: list = []
        spans: list = []
        fallbacks: list = []
        cols = _CreationCols()
        batched: list[int] = []
        for idx, (scope, requests) in enumerate(items):
            # Demoted sessions still count against the per-scope cap (the
            # reference trims on TOTAL population; a tier'd engine must
            # evict at the same points an untier'd one would).
            existing = len(self._scopes.get(scope, [])) + len(
                self._tier.get(scope, ())
            )
            if existing + len(requests) > self._max_sessions_per_scope:
                fallbacks.append(idx)
            else:
                batched.append(idx)
        # Single-host: ONE id draw for the whole call, collision-checked
        # against the union of every batched scope's live pids, sliced per
        # scope below (a per-scope draw pays the fixed numpy overhead
        # len(items) times).
        pre_ids: dict[int, np.ndarray] = {}
        if not self._multihost and batched:
            total = sum(len(items[i][1]) for i in batched)
            if total:
                parts = [self._taken_pids(items[i][0]) for i in batched]
                all_ids = self._draw_unique_pids(np.concatenate(parts), total)
                off = 0
                for i in batched:
                    k = len(items[i][1])
                    pre_ids[i] = all_ids[off : off + k]
                    off += k
        done = 0
        for idx, (scope, requests) in enumerate(items):
            if done < len(batched) and batched[done] == idx:
                done += 1
                proposals, configs = self._prepare_creation(
                    scope, requests, now, config, cols, pre_ids.get(idx)
                )
                spans.append((len(entries), len(proposals)))
                entries.extend(
                    (scope, p, c) for p, c in zip(proposals, configs)
                )
            else:
                spans.append(None)
        created = self._allocate_and_register(entries, now, cols)
        for idx, span in enumerate(spans):
            if span is not None:
                start, count = span
                out[idx] = created[start : start + count]
        for idx in fallbacks:
            scope, requests = items[idx]
            out[idx] = [
                self.create_proposal(scope, r, now, config) for r in requests
            ]
        return out

    def _prepare_creation(
        self,
        scope: Scope,
        requests: list[CreateProposalRequest],
        now: int,
        config: ConsensusConfig | None,
        cols: "_CreationCols",
        pre_ids: np.ndarray | None = None,
    ) -> tuple[list[Proposal], list[ConsensusConfig]]:
        """Python-side prep shared by the batch creators: mint proposals
        with batch-drawn ids (single-host) or deterministic ids (multi-host)
        and resolve configs with per-batch memoization. Per-proposal scalars
        the allocator needs (n, expiry, config fields) accumulate into
        ``cols`` during this loop — np.asarray over plain int lists later is
        several times cheaper than re-walking the objects with fromiter."""
        proposals: list[Proposal] = []
        configs: list[ConsensusConfig] = []
        # Single-host fast path: draw the whole batch's proposal ids in one
        # urandom read with vectorized collision checks (same id space and
        # uniqueness policy as generate_id/regenerate_until_unique, minus
        # the per-proposal uuid4 cost). Multi-host keeps the deterministic
        # per-proposal derivation (_ensure_unique_pid).
        if pre_ids is not None:
            batch_ids = pre_ids
        elif self._multihost:
            batch_ids = None
        else:
            batch_ids = self._draw_unique_pids(
                self._taken_pids(scope), len(requests)
            )
        # Config resolution is identical for requests sharing (expiration,
        # liveness) when no per-proposal override exists — memoize per batch.
        cfg_cache: dict = {}
        add_p = proposals.append
        add_c = configs.append
        c_n = cols.n.append
        c_exp = cols.expiry.append
        c_live = cols.liveness.append
        c_thr = cols.thr.append
        c_gos = cols.gossip.append
        c_maxr = cols.maxr.append
        batch_pids: set[int] | None = None if batch_ids is not None else set()
        pid_iter = (
            batch_ids.tolist() if batch_ids is not None else [None] * len(requests)
        )
        for request, pid in zip(requests, pid_iter):
            proposal = request.into_proposal(now, pid=pid)
            if batch_pids is not None:
                self._ensure_unique_pid(scope, proposal, taken=batch_pids)
                batch_pids.add(proposal.proposal_id)
            validate_proposal_timestamp(proposal.expiration_timestamp, now)
            add_p(proposal)
            key = (
                proposal.expiration_timestamp,
                proposal.liveness_criteria_yes,
            )
            entry = cfg_cache.get(key)
            if entry is None:
                resolved = self._resolve_config(scope, config, proposal)
                entry = (
                    resolved,
                    resolved.consensus_threshold,
                    resolved.use_gossipsub_rounds,
                    resolved.max_rounds,
                )
                cfg_cache[key] = entry
            add_c(entry[0])
            c_n(request.expected_voters_count)
            c_exp(proposal.expiration_timestamp)
            c_live(proposal.liveness_criteria_yes)
            c_thr(entry[1])
            c_gos(entry[2])
            c_maxr(entry[3])
        return proposals, configs

    def _allocate_and_register(
        self,
        entries: "list[tuple[Scope, Proposal, ConsensusConfig]]",
        now: int,
        cols: "_CreationCols",
    ) -> list[Proposal]:
        """One pool.allocate_batch for every (scope, proposal, config) entry
        (first-fit against the free budget; the rest host-spill), then host
        registration. Returns clones in entry order. ``cols`` carries the
        per-entry scalars collected during _prepare_creation, so the device
        config arrays build from plain int lists instead of re-walking the
        proposal/config objects."""
        from ..ops.decide import required_votes_np

        free = self._pool.free_slots
        n_all = np.asarray(cols.n, np.int64)
        # First-fit against the free budget, vectorized: rows small enough
        # for the lane grid claim slots in entry order until the budget is
        # spent (identical to the old per-entry scan).
        ok = n_all <= self._pool.voter_capacity
        fit_mask = ok & (np.cumsum(ok) <= free)
        fit_idx = np.nonzero(fit_mask)[0]
        all_fit = len(fit_idx) == len(entries)
        slots_by_item: dict[int, int] = {}
        slots: list[int] = []
        if len(fit_idx):
            count = len(fit_idx)
            if all_fit:
                n_arr = n_all
                thr_arr = np.asarray(cols.thr, np.float64)
                gossip_arr = np.asarray(cols.gossip, bool)
                maxr_arr = np.asarray(cols.maxr, np.int64)
                expiry_arr = np.asarray(cols.expiry, np.int64)
                liveness_arr = np.asarray(cols.liveness, bool)
                keys = [(s, p.proposal_id) for s, p, _ in entries]
            else:
                n_arr = n_all[fit_idx]
                thr_arr = np.asarray(cols.thr, np.float64)[fit_idx]
                gossip_arr = np.asarray(cols.gossip, bool)[fit_idx]
                maxr_arr = np.asarray(cols.maxr, np.int64)[fit_idx]
                expiry_arr = np.asarray(cols.expiry, np.int64)[fit_idx]
                liveness_arr = np.asarray(cols.liveness, bool)[fit_idx]
                keys = [
                    (entries[i][0], entries[i][1].proposal_id)
                    for i in fit_idx.tolist()
                ]
            req_arr = required_votes_np(n_arr, thr_arr)
            # max_round_limit semantics (reference: src/session.rs:120-128):
            # gossipsub -> max_rounds; P2P -> explicit override, else the
            # dynamic ceil(n*t) cap — which shares calculate_threshold_based_
            # value with required votes (src/utils.rs:292-304), so req_arr
            # doubles as the dynamic cap.
            cap_arr = np.where(
                gossip_arr,
                maxr_arr,
                np.where(maxr_arr == 0, req_arr, maxr_arr),
            )
            slots = self._pool.allocate_batch(
                keys=keys,
                n=n_arr,
                req=req_arr,
                cap=cap_arr,
                gossip=gossip_arr,
                liveness=liveness_arr,
                expiry=expiry_arr,
                created_at=np.full(count, now, np.int64),
            )
            if not all_fit:
                slots_by_item = dict(zip(fit_idx.tolist(), slots))

        # Entries arrive grouped by scope (one span per input item), so the
        # scope-keyed bookkeeping caches the current scope's slot list
        # instead of paying a setdefault + membership per proposal. The
        # all-fit case (the churn steady state) also skips the per-item
        # dict probe: fit_idx is then simply 0..len(entries).
        records = self._records
        index = self._index
        timelines = self._timelines
        wall = time.monotonic()
        touched: set = set()
        cur_scope: object = object()  # sentinel unequal to any real scope
        cur_list: list = []
        fresh = SessionRecord.fresh_pooled
        for i, (scope, proposal, cfg) in enumerate(entries):
            slot = slots[i] if all_fit else slots_by_item.get(i)
            if slot is None:  # host spill (oversized n or pool exhausted)
                host_session = ConsensusSession._new(proposal, cfg, now)
                slot = self._next_host_slot
                self._next_host_slot -= 1
                record = fresh(scope, slot, proposal, cfg, now)
                record.session = host_session
                record.votes = host_session.votes
                self.tracer.count("engine.host_spills")
            else:
                record = fresh(scope, slot, proposal, cfg, now)
            records[slot] = record
            index[(scope, proposal.proposal_id)] = slot
            timelines.created(slot, scope, proposal.proposal_id, now, wall)
            if scope is not cur_scope:
                cur_scope = scope
                cur_list = self._scopes.setdefault(scope, [])
                touched.add(scope)
            cur_list.append(slot)
        for scope in touched:
            self._drop_pid_cache(scope)
        if entries:
            self._m_proposals.inc(len(entries))
            flight_recorder.record("engine.create", proposals=len(entries))
        return [p.clone() for _, p, _ in entries]

    def process_incoming_proposal(
        self,
        scope: Scope,
        proposal: Proposal,
        now: int,
        config: ConsensusConfig | None = None,
    ) -> None:
        """Validate a network proposal (signatures, chain, expiry — the full
        scalar gauntlet, reference: src/session.rs:198-221) and load the
        replayed session into the pool as a dense row (resume-from-snapshot).
        ``config`` optionally overrides the scope-config resolution with the
        same precedence create_proposal gives its explicit override — WAL
        replay uses this to preserve a logged override across recovery.
        """
        if (scope, proposal.proposal_id) in self._index or self._tier_has(
            scope, proposal.proposal_id
        ):
            # Demoted sessions exist; the no-redelivery contract rejects
            # without paging them in.
            raise ProposalAlreadyExist()
        wall0 = time.time()
        config = self._resolve_config(scope, config, proposal)
        # Fail-fast BEFORE the signature prepass, preserving the scalar
        # path's zero-crypto rejection of expired gossip (validate_proposal
        # re-runs the same check first, so error precedence is unchanged —
        # an attacker redelivering expired chains must not be able to buy
        # ECDSA work or churn the shared cache's LRU).
        try:
            validate_proposal_timestamp(proposal.expiration_timestamp, now)
        except ConsensusError:
            self._note_expired_proposal(proposal, now)
            raise
        # Admission cache for the embedded chain: verdicts for known votes
        # come from the cache, the rest from one batched verify (None
        # disables the prepass entirely — from_proposal then verifies each
        # vote inline, the original scalar flow).
        sv = ch = None
        if proposal.votes and self._verify_cache is not None:
            sv, ch = self._cached_verify(proposal.votes)
        # The scalar oracle replays embedded votes with exact reference
        # semantics (chain validation, per-vote ECDSA, round caps); the dense
        # row is loaded from its final state.
        session, transition = ConsensusSession.from_proposal(
            proposal.clone(),
            self._scheme,
            config,
            now,
            sig_verdicts=sv,
            computed_hashes=ch,
        )
        # Event before save, as in the reference (src/service.rs:275-277).
        if transition.is_reached and self._owns_replicated_event():
            self._emit(
                scope,
                ConsensusReached(
                    proposal_id=proposal.proposal_id,
                    result=transition.reached,
                    timestamp=now,
                ),
            )
        self._register_session(scope, session, now)
        self._note_chain_admitted(proposal.votes, config, now)
        if trace_store.enabled:
            slot = self._index.get((scope, proposal.proposal_id))
            if slot is not None:
                # Continues the trace the proposal travelled with (ambient
                # context from the bridge frame / gossip envelope); roots a
                # fresh one for untraced senders.
                self._bind_trace(
                    self._records[slot],
                    "consensus.process_proposal",
                    scope,
                    wall0,
                )

    def _note_chain_admitted(
        self, votes: "list[Vote]", config: ConsensusConfig, now: int
    ) -> None:
        """Scorecard admissions for an embedded chain accepted whole
        (process_incoming_proposal / ingest_proposals): one dict pass per
        chain, one monitor call — O(L) dict stores against the O(L)
        SHA/ECDSA the chain already cost."""
        if not self._health_live or not votes:
            return
        counts: dict[bytes, int] = {}
        for vote in votes:
            counts[vote.vote_owner] = counts.get(vote.vote_owner, 0) + 1
        self.health.note_admitted(
            counts, now, timeout_hint=config.consensus_timeout
        )

    def _note_expired_proposal(self, proposal: Proposal, now: int) -> None:
        """Expired-gossip scorecard hit for a whole stale proposal,
        attributed to the chain's most recent signer (falling back to the
        proposal owner for vote-free proposals)."""
        if not self._health_live:
            return
        source = (
            proposal.votes[-1].vote_owner
            if proposal.votes
            else proposal.proposal_owner
        )
        if source:
            self.health.note_expired(source, now)

    def ingest_proposals(
        self,
        items: list[tuple[Scope, Proposal]],
        now: int,
        configs: "list[ConsensusConfig | None] | None" = None,
    ) -> list[int]:
        """Batch counterpart of process_incoming_proposal: validate and load
        many (possibly vote-carrying) proposals in bulk.

        The expensive per-vote work is batched — ALL embedded signatures go
        through one scheme.verify_batch call (native threaded path) and ALL
        chains with >1 votes through one vmapped device chain kernel — then
        each proposal replays the exact scalar check sequence with the
        precomputed verdicts injected, so error precedence is identical to
        the scalar path; the slots those items claim are activated on the
        device in one dispatch (``ProposalPool.gather_activations``).
        Returns one StatusCode per item (OK = registered; events emitted
        exactly as the scalar path would). ``configs``
        optionally supplies a per-item explicit config override (same
        precedence as create_proposal's; None entries resolve from the
        scope config) — WAL replay uses it to preserve logged overrides.
        """
        from ..ops.chain import chain_kernel_batch, first_chain_error, pack_chain

        if configs is not None and len(configs) != len(items):
            raise ValueError("configs must supply one entry per item")
        statuses = [int(StatusCode.OK)] * len(items)

        # Items that cannot pass — already registered at entry, or already
        # expired — are excluded from the verification prepass and the
        # chain kernel: under gossip redelivery the same vote-carrying
        # proposal arrives over and over, and re-verifying a chain that is
        # about to be dropped anyway was the per-delivery O(chain)
        # redelivery tax (expired chains are the same attack surface —
        # buying ECDSA work and churning the shared cache's LRU with a
        # stale proposal must not be possible on ANY entry point). Their
        # statuses come from the final loop's inline gauntlet, which
        # raises ProposalExpired / reports PROPOSAL_ALREADY_EXIST before
        # any signature work — exact scalar error precedence preserved.
        skip = [
            (scope, proposal.proposal_id) in self._index
            or self._tier_has(scope, proposal.proposal_id)
            or now >= proposal.expiration_timestamp
            for scope, proposal in items
        ]

        # Bulk signature verification across every embedded vote of the
        # surviving items, through the admission cache: identical votes
        # appearing across many chains collapse to one verify item, known
        # votes to none (plain one-shot verify_batch when the cache is
        # disabled — see _cached_verify).
        flat_votes: list[Vote] = []
        spans: list[tuple[int, int] | None] = []  # (start, count) per item
        for i, (scope, proposal) in enumerate(items):
            if skip[i]:
                spans.append(None)
                continue
            start = len(flat_votes)
            flat_votes.extend(proposal.votes)
            spans.append((start, len(proposal.votes)))
        # Crypto/device pipelining: the signature batch is SUBMITTED to
        # the verify pool here, the chain kernel below dispatches to the
        # device while the pool verifies, and the verdicts are collected
        # only when both are needed — host ECDSA/Ed25519 and device chain
        # validation for the same call overlap instead of serializing.
        pending_verify = (
            self._cached_verify_begin(flat_votes) if flat_votes else None
        )

        # Bulk chain validation on device (only chains that need it).
        chain_errors: dict[int, ConsensusError | None] = {}
        chain_idx = [
            i
            for i, (_, p) in enumerate(items)
            if not skip[i] and len(p.votes) > 1
        ]
        if chain_idx:
            pad = max(len(items[i][1].votes) for i in chain_idx)
            packs = [pack_chain(items[i][1].votes, pad_to=pad) for i in chain_idx]
            batchpack = {
                key: np.stack([p[key] for p in packs]) for key in packs[0]
            }
            with observed_span(
                self.tracer,
                "engine.chain_kernel",
                self._m_chain,
                chains=len(chain_idx),
            ):
                chain_statuses = np.asarray(
                    chain_kernel_batch(
                        batchpack["vote_hash"],
                        batchpack["received_hash"],
                        batchpack["parent_hash"],
                        batchpack["owner"],
                        batchpack["ts"],
                        batchpack["valid"],
                    )
                )
            for j, i in enumerate(chain_idx):
                code = first_chain_error(chain_statuses[j])
                exc_cls = error_for_code(code) if code else None
                chain_errors[i] = exc_cls() if exc_cls is not None else None

        verdicts: list = []
        vote_hashes: list = []
        if pending_verify is not None:
            verdicts, vote_hashes = pending_verify.collect()

        with self._pool.gather_activations():
            for i, (scope, proposal) in enumerate(items):
                if (scope, proposal.proposal_id) in self._index or self._tier_has(
                    scope, proposal.proposal_id
                ):
                    # Demoted sessions exist: this path's strict
                    # no-redelivery contract rejects without paging them in.
                    statuses[i] = int(StatusCode.PROPOSAL_ALREADY_EXIST)
                    continue
                if spans[i] is None:
                    # Nothing precomputed for this item: expired at entry
                    # (the inline gauntlet below raises ProposalExpired
                    # before any signature work), or registered at entry but
                    # freed mid-batch by an earlier item's per-scope-cap
                    # eviction — either way, run the full scalar gauntlet, as
                    # a sequential process_incoming_proposal would.
                    sv = ch = None
                    chain_error = COMPUTE_CHAIN
                else:
                    start, count = spans[i]
                    sv = verdicts[start : start + count] if count else None
                    ch = vote_hashes[start : start + count] if count else None
                    chain_error = chain_errors.get(i)
                try:
                    config = self._resolve_config(
                        scope, configs[i] if configs is not None else None, proposal
                    )
                    session, transition = ConsensusSession.from_proposal(
                        proposal.clone(),
                        self._scheme,
                        config,
                        now,
                        sig_verdicts=sv,
                        chain_error=chain_error,
                        computed_hashes=ch,
                    )
                    if transition.is_reached and self._owns_replicated_event():
                        self._emit(
                            scope,
                            ConsensusReached(
                                proposal_id=proposal.proposal_id,
                                result=transition.reached,
                                timestamp=now,
                            ),
                        )
                    self._register_session(scope, session, now)
                    self._note_chain_admitted(proposal.votes, config, now)
                except ConsensusError as exc:
                    statuses[i] = int(exc.code)
                    if exc.code == StatusCode.PROPOSAL_EXPIRED:
                        self._note_expired_proposal(proposal, now)
        return statuses

    # ── Gossip delivery: create-or-extend (chain-prefix watermark) ─────

    def deliver_proposal(
        self,
        scope: Scope,
        proposal: Proposal,
        now: int,
        config: ConsensusConfig | None = None,
    ) -> int:
        """Scalar :meth:`deliver_proposals` (one StatusCode int)."""
        return self.deliver_proposals(
            [(scope, proposal)], now,
            configs=[config] if config is not None else None,
        )[0]

    def deliver_proposals(
        self,
        items: "list[tuple[Scope, Proposal]]",
        now: int,
        configs: "list[ConsensusConfig | None] | None" = None,
    ) -> "list[int]":
        """Gossip-facing delivery of (possibly vote-carrying) proposals:
        create unknown sessions, EXTEND known ones along the validated-chain
        watermark, and absorb pure redeliveries for free.

        The reference protocol gossips growing vote chains; its
        ``process_incoming_proposal`` rejects any redelivery outright
        (ProposalAlreadyExist), forcing embedders to re-feed every embedded
        vote through the vote path — O(chain) signature checks per
        delivery, O(L²) for an incrementally grown chain. This entry point
        is the amortized alternative. Per item:

        - unknown ``(scope, proposal_id)``: the full
          :meth:`ingest_proposals` gauntlet (batched, cache-aware);
          status as that path reports it;
        - known, and the incoming chain strictly extends the accepted one
          (every accepted vote's hash matches positionally — the
          watermark): ONLY the suffix is hash/signature/chain-checked
          (cache-aware) and applied through the batch vote path. Status
          OK when every suffix vote landed (duplicates from concurrent
          vote gossip and post-decision extras are absorbed), else the
          first hard per-vote error. Admission failures apply nothing
          (checked up front); apply-stage rejections — capacity, round
          caps — leave earlier suffix votes applied, exactly as feeding
          the suffix through the per-vote gossip path would;
        - known otherwise — identical chain, shorter chain, fork before
          the watermark, or a session whose chain was retained through
          the columnar path (merged order not positionally comparable):
          PROPOSAL_ALREADY_EXIST with zero crypto, exactly what
          process_incoming_proposal reports for a redelivery.

        Items are processed STRICTLY in order, each against the state the
        previous items left: a batch call is definitionally equivalent to
        the same deliveries made one by one (so ``[create X, extend X]``
        extends, and a same-batch duplicate settles as a redelivery).
        That equivalence is load-bearing for durability — the WAL chunks
        oversized KIND_DELIVER records into consecutive smaller batches
        and replays them as separate calls. Consecutive UNKNOWN items
        with distinct pids are still dispatched as one
        :meth:`ingest_proposals` call (one verify batch, one chain-kernel
        dispatch) — safe because that path also processes in order — and
        repeated signatures across items cost one verify via the
        admission cache, so ordering does not forfeit the batch's
        amortization.

        Multi-host: a device-pooled session owned by another process
        reports SESSION_NOT_FOUND *before* any suffix validation — the
        relay routes on that status, and a misrouted-but-invalid delivery
        must look the same as a misrouted-valid one (the ingest_votes
        convention).

        Semantics with the verify cache disabled are identical (the
        watermark is structural, not cached); only the signature work
        changes. Events/decisions fire exactly as the underlying
        create/vote paths emit them.
        """
        if configs is not None and len(configs) != len(items):
            raise ValueError("configs must supply one entry per item")
        statuses: list[int] = [0] * len(items)
        run: list[int] = []  # consecutive unknown items, distinct pids
        run_keys: set = set()

        def flush_run() -> None:
            if not run:
                return
            sub = self.ingest_proposals(
                [items[j] for j in run],
                now,
                configs=(
                    [configs[j] for j in run] if configs is not None else None
                ),
            )
            for j, code in zip(run, sub):
                statuses[j] = int(code)
            run.clear()
            run_keys.clear()

        for k, (scope, proposal) in enumerate(items):
            key = (scope, proposal.proposal_id)
            # A known pid — or a pid this run is about to register — must
            # see the state all earlier items produced: flush first.
            # Demoted sessions are known: a redelivery that strictly
            # extends one pages it back in and applies the suffix.
            if key in self._index or key in run_keys or self._tier_has(*key):
                flush_run()
            slot = self._index.get(key)
            if slot is None:
                slot = self._tier_lookup_promote(*key)
            if slot is None:
                run.append(k)
                run_keys.add(key)
                continue
            record = self._records[slot]
            if (
                self._multihost
                and record.session is None
                and not self._owns_slot(slot)
            ):
                # Misrouted, rejected BEFORE validation (see docstring).
                statuses[k] = int(StatusCode.SESSION_NOT_FOUND)
                continue
            suffix = self._extension_suffix(record, proposal)
            if suffix:
                statuses[k] = self._apply_chain_suffix(record, suffix, now)
            else:
                statuses[k] = int(StatusCode.PROPOSAL_ALREADY_EXIST)
                # The settle is still crypto-free; the health probe only
                # re-walks the already-compared prefix to classify WHY the
                # redelivery failed to extend (fork evidence / truncation
                # lag) instead of discarding the signal.
                self._note_redelivery_health(record, proposal, now)
        flush_run()
        return statuses

    def _note_redelivery_health(
        self, record: SessionRecord[Scope], proposal: Proposal, now: int
    ) -> None:
        """Classify a non-extending redelivery for the health layer. A
        prefix mismatch before the validated watermark is a FORK: the
        accepted vote and the divergent incoming vote at that position are
        retained as a self-authenticating evidence pair, attributed to the
        divergent vote's signer (its signature is NOT verified here — the
        watermark path settles forks crypto-free; the bytes authenticate
        themselves offline). A matching-but-shorter chain is a TRUNCATION:
        the chain's most recent signer — the closest accountable identity
        to the gossip source — is scored with the lag. Identical
        redeliveries are benign and score nothing. Only LEGACY
        (pre-validated) columnar retention is skipped — merged order not
        positionally comparable, same reason _extension_suffix bails;
        wire-validated retention probes against the merged chain, so a
        forker cannot hide behind a victim's columnar ingest path."""
        if not self._health_live or (
            record.retained_wire and not record.wire_only
        ):
            return
        accepted = (
            self._accepted_vote_chain(record)
            if record.retained_wire
            else record.proposal.votes
        )
        incoming = proposal.votes
        n = len(incoming)
        if n and n <= len(accepted):
            # Benign fast path — identical redelivery (equal length) or a
            # lagging peer (shorter): ONE tail-hash compare, no prefix
            # walk, so the steady-state gossip settle stays O(1). The
            # accepted chain's received_hash links commit each vote to
            # its predecessor, so a matching tail at the same position
            # means a matching prefix for fully-linked chains; chains
            # with empty links could in principle share the tail while
            # diverging earlier — evidence capture is best-effort there
            # (the API status is PROPOSAL_ALREADY_EXIST either way).
            if incoming[-1].vote_hash == accepted[n - 1].vote_hash:
                if n < len(accepted):
                    self.health.note_truncation(
                        incoming[-1].vote_owner, len(accepted) - n, now
                    )
                return
        elif not n:
            if accepted and proposal.proposal_owner:
                self.health.note_truncation(
                    proposal.proposal_owner, len(accepted), now
                )
            return
        # Mismatch guaranteed somewhere in the shared prefix (a strict
        # extension would have taken the watermark path; a shorter/equal
        # chain with an agreeing prefix matched its tail above — its
        # differing vote at any position, tail included, IS a divergent
        # history). Conviction bar (chaos-harness refinement, PARITY.md):
        # a positional divergence alone is NOT evidence against the
        # divergent vote's signer — an honest vote can land at a
        # different position under loss/reorder (or a racing embedder),
        # and grading its signer suspect would defame an honest peer.
        # Fork evidence is retained only when the divergent vote's owner
        # ALSO has a different accepted vote in this session — two
        # validly-shaped distinct votes by one signer, the same
        # self-authenticating double-sign bar the equivocation probe
        # applies. Anything weaker is counted, not convicted.
        for ours, theirs in zip(accepted, incoming):
            if ours.vote_hash != theirs.vote_hash:
                prior = record.votes.get(theirs.vote_owner)
                if prior is None and record.session is not None:
                    prior = record.session.votes.get(theirs.vote_owner)
                if prior is None and record.retained_wire:
                    # Wire-retained accepts live in the merged chain, not
                    # the scalar vote map.
                    for vote in accepted:
                        if vote.vote_owner == theirs.vote_owner:
                            prior = vote
                            break
                if prior is not None and prior.vote_hash != theirs.vote_hash:
                    # The retained pair is (offender's accepted vote,
                    # offender's divergent vote) — both carry the
                    # offender's signature, verifiable offline.
                    self.health.note_fork(
                        record.scope,
                        proposal.proposal_id,
                        prior.encode(),
                        theirs.encode(),
                        theirs.vote_owner,
                        now,
                    )
                else:
                    self.tracer.count("engine.divergent_redeliveries")
                return

    def _extension_suffix(
        self, record: SessionRecord[Scope], proposal: Proposal
    ) -> "list[Vote] | None":
        """Suffix of ``proposal.votes`` beyond the session's accepted chain,
        or None when the incoming chain is not a strict extension of it
        (shorter, equal-length, forked before the watermark, or the
        accepted chain is partly columnar-retained wire whose merged order
        is not positionally comparable). Wire-validated retention
        (``record.wire_only`` — the bridge's zero-copy OP_VOTE_BATCH
        path) stays comparable: its accepts are guard-ordered, so the
        merged chain is positional and anti-entropy can extend a
        wire-fed session exactly as a scalar-fed one. The prefix compare
        is bytes equality over already-validated hashes — no crypto."""
        if record.retained_wire:
            if not record.wire_only:
                return None
            accepted = self._accepted_vote_chain(record)
        else:
            accepted = record.proposal.votes
        incoming = proposal.votes
        if len(incoming) <= len(accepted):
            return None
        for ours, theirs in zip(accepted, incoming):
            if ours.vote_hash != theirs.vote_hash:
                return None
        return [v.clone() for v in incoming[len(accepted) :]]

    def _apply_chain_suffix(
        self, record: SessionRecord[Scope], suffix: "list[Vote]", now: int
    ) -> int:
        """Validate and apply a watermark extension: hash/signature checks
        (admission cache) and chain-link checks cover ONLY the suffix — the
        accepted prefix was validated when it was accepted. Admission is
        all-or-nothing (the first bad suffix vote rejects the delivery
        before anything mutates); APPLY-stage rejections — capacity,
        round caps — mirror the per-vote gossip path this call amortizes:
        earlier suffix votes stay applied and the first hard code is
        returned, exactly the state feeding the suffix through
        process_incoming_vote one by one would leave. One documented
        boundary divergence (PARITY.md): the expiry fail-fast below uses
        the proposal-level ``now >= expiration`` check shared by every
        proposal entry point, while the per-vote path expires strictly
        after (``now > expiration``) — a delivery at exactly
        ``now == expiration_timestamp`` is rejected here but would apply
        through the per-vote fallback."""
        proposal = record.proposal
        # Fail-fast BEFORE the signature prepass, matching the expiry
        # guards in process_incoming_proposal / ingest_proposals: an
        # attacker redelivering extensions of an expired session must not
        # be able to buy ECDSA work or churn the shared cache's LRU.
        try:
            validate_proposal_timestamp(proposal.expiration_timestamp, now)
        except ConsensusError as exc:
            if self._health_live and suffix[-1].vote_owner:
                # Expired-gossip scorecard hit on the chain's most recent
                # signer (the closest accountable identity to the
                # redelivery source) — still zero crypto.
                self.health.note_expired(suffix[-1].vote_owner, now)
            return int(exc.code)
        verdicts, hashes = self._cached_verify(suffix)
        for i, vote in enumerate(suffix):
            if vote.proposal_id != proposal.proposal_id:
                return int(StatusCode.VOTE_PROPOSAL_ID_MISMATCH)
            try:
                validate_vote(
                    vote,
                    self._scheme,
                    proposal.expiration_timestamp,
                    proposal.timestamp,
                    now,
                    sig_verdict=verdicts[i],
                    computed_hash=hashes[i],
                )
            except ConsensusError as exc:
                self._note_reject_health(vote, int(exc.code), now)
                return int(exc.code)
        code = self._validate_suffix_chain(record, suffix)
        if code:
            return code
        sub = self.ingest_votes(
            [(record.scope, vote) for vote in suffix], now, pre_validated=True
        )
        # The histogram is documented as "votes applied per watermark
        # extension": observe what actually LANDED (apply-stage rejections
        # and already-voted absorptions excluded), so rejected deliveries
        # and partial applies never read as healthy extension traffic.
        applied = int(np.sum(np.asarray(sub) == int(StatusCode.OK)))
        if applied:
            self._m_suffix_len.observe(applied)
            self.tracer.count("engine.chain_extensions")
        # Soft codes a live session legitimately produces for chain votes
        # that raced concurrent gossip: the owner already voted via the
        # vote path, or the session decided mid-suffix. Anything else is a
        # hard error the caller must see.
        soft = (
            int(StatusCode.OK),
            int(StatusCode.ALREADY_REACHED),
            int(StatusCode.DUPLICATE_VOTE),
            int(StatusCode.USER_ALREADY_VOTED),
        )
        for code in sub:
            if int(code) not in soft:
                return int(code)
        return int(StatusCode.OK)

    def _validate_suffix_chain(
        self, record: SessionRecord[Scope], suffix: "list[Vote]"
    ) -> int:
        """protocol.validate_vote_chain over accepted + suffix, checked
        from the watermark onward (``start``): the accepted prefix's links
        were validated at acceptance, and the chain rules live in exactly
        one place. Wire-retained records supply the MERGED accepted chain
        (scalar votes alone would make a correctly-linked suffix look
        dangling — its received_hash names the retained tail). Returns a
        StatusCode int, 0 when valid."""
        accepted = (
            self._accepted_vote_chain(record)
            if record.retained_wire
            else record.proposal.votes
        )
        try:
            validate_vote_chain(accepted + suffix, start=len(accepted))
        except ConsensusError as exc:
            return int(exc.code)
        return 0

    def _register(
        self,
        scope: Scope,
        proposal: Proposal,
        config: ConsensusConfig,
        now: int,
        session: ConsensusSession | None = None,
    ) -> SessionRecord[Scope]:
        """Claim a pool slot for the proposal — or, when the pool geometry
        cannot hold it (expected_voters_count or embedded voters beyond the
        lane capacity, or no free slots), degrade to a host-backed scalar
        session. Registration therefore never fails on capacity, matching the
        reference service's unbounded envelope (src/service.rs:86-97) and its
        invariant that session save cannot fail (events may be emitted before
        registration, src/service.rs:275-277)."""
        # Per-scope LRU eviction runs BEFORE slot allocation so overflow
        # eviction can free a device slot for the incoming session (the
        # reference trims after save, src/service.rs:512-522 — the surviving
        # set is identical either way, but trimming first avoids stranding
        # the newcomer on the host path while a freed slot sits idle).
        if self._evict_for(scope, now):
            # The incoming session itself loses the LRU ranking (created_at
            # tie): never tracked, nothing allocated — same observable result
            # as insert-then-trim.
            host_session = (
                session
                if session is not None
                else ConsensusSession._new(proposal, config, now)
            )
            slot = self._next_host_slot
            self._next_host_slot -= 1
            record = SessionRecord(
                scope=scope,
                slot=slot,
                proposal=host_session.proposal,
                config=config,
                created_at=now,
                session=host_session,
            )
            record.votes = host_session.votes
            return record
        fits = (
            proposal.expected_voters_count <= self._pool.voter_capacity
            and (
                session is None
                or (
                    len(session.votes) <= self._pool.voter_capacity
                    # Tally-carrying sessions (columnar spill survivors) stay
                    # host-backed: pooling would bake tallies into the dense
                    # row but drop them from the exportable session, so a
                    # save->load->save round-trip would lose them.
                    and not session.tallies
                )
            )
            and self._pool.free_slots > 0
        )
        if fits:
            slot = allocate_slot(
                self._pool, (scope, proposal.proposal_id), proposal, config, now
            )
            host_session = None
        else:
            slot = self._next_host_slot
            self._next_host_slot -= 1
            host_session = (
                session
                if session is not None
                else ConsensusSession._new(proposal, config, now)
            )
            self.tracer.count("engine.host_spills")
        record = SessionRecord(
            scope=scope,
            slot=slot,
            proposal=proposal if host_session is None else host_session.proposal,
            config=config,
            created_at=now,
            session=host_session,
        )
        if host_session is not None:
            record.votes = host_session.votes  # shared dict: one source of truth
        record.last_activity = now
        seq = self._scope_seq.get(scope, 0)
        self._scope_seq[scope] = seq + 1
        record.seq = seq
        self._records[slot] = record
        self._index[(scope, record.proposal.proposal_id)] = slot
        self._scopes.setdefault(scope, []).append(slot)
        self._drop_pid_cache(scope)
        self._timelines.created(
            slot, scope, record.proposal.proposal_id, now, time.monotonic()
        )
        if not self._promoting:
            # Paging a demoted session back in is not a fresh proposal.
            self._m_proposals.inc()
        return record

    def _register_session(
        self, scope: Scope, session: ConsensusSession, created_at: int
    ) -> None:
        """Load a scalar session (possibly already decided) into a fresh
        slot — the shared path for validated network proposals and
        storage-backed restore (device tensors are a cache; the session is
        the source of truth, SURVEY §5 checkpoint row). Sessions the pool
        cannot hold stay host-backed (see _register)."""
        record = self._register(
            scope, session.proposal, session.config, created_at, session=session
        )
        if record.slot not in self._records:
            return  # evicted immediately by the per-scope cap (created_at tie)
        state = state_code_of(session.state)
        if state != STATE_ACTIVE:
            # Loaded already-decided (snapshot restore / vote-carrying
            # gossip): stamp the timeline's outcome but do NOT observe
            # decision latency — the decision wasn't made by this engine.
            self._timelines.decided(
                record.slot,
                _OUTCOME_OF_STATE[state],
                created_at,
                time.monotonic(),
                pre_decided=True,
            )
        if record.session is not None:
            return  # host-backed: the scalar session IS the state
        record.votes = {k: v.clone() for k, v in session.votes.items()}
        if session.votes or not session.state.is_active:
            loaded = load_session_rows(self._pool, record.slot, session)
            assert loaded  # capacity pre-checked in _register

    # ── Voting ─────────────────────────────────────────────────────────

    def _cached_verify(
        self, votes: "list[Vote]"
    ) -> "tuple[list, list[bytes]]":
        """Synchronous admission-verify prepass: exactly
        ``_cached_verify_begin(votes).collect()`` (see there)."""
        return self._cached_verify_begin(votes).collect()

    def verify_votes_async(self, votes: "list[Vote]") -> "PendingVoteVerdicts":
        """Public admission-verify prepass for pipelining embedders.

        Starts the full host validation front half NOW — vote-hash
        recompute, structural prechecks, verify-cache consult, and the
        signature batch submitted to the scheme (on the native worker
        pool, the crypto runs GIL-free in the background) — and returns a
        handle whose ``collect()`` yields ``(verdicts, computed_hashes)``
        aligned with ``votes``, exactly what the engine's own entry
        points consume. Embedders that drive :meth:`ingest_columnar`
        with pre-validated traffic use this to overlap batch k+1's
        crypto with batch k's device ingest (the `bench.py
        validated-sweep` cold path); verdicts must all be True and each
        ``computed_hash`` must equal the vote's ``vote_hash`` before the
        rows may be ingested as validated."""
        return self._cached_verify_begin(votes)

    def _cached_verify_begin(self, votes: "list[Vote]") -> "PendingVoteVerdicts":
        """Signature verdicts for ``votes`` through the admission cache,
        in two halves. This half: in-batch dedup (identical votes across
        many chains collapse to one verify item), cache consultation, and
        ONE scheme.verify_batch_submit over the surviving misses — the
        crypto is in flight on the verify pool when this returns. The
        ``collect()`` half: await verdicts, fan out, populate the cache,
        and return (verdicts, computed_hashes) aligned with ``votes`` —
        callers feed both into validate_vote so the SHA pass here is the
        only one. The verify-batch histogram observes the *collect* wait,
        so a well-overlapped pipeline shows near-zero residence while an
        unpipelined caller still sees the full verify cost (begin is
        immediately followed by collect).

        With the cache disabled this is a plain batched verify (identical
        to the pre-cache flow). Admission keys are derived from each
        vote's ``signing_payload()`` — the exact bytes the scheme
        verifies — so a key can never be shared by two different
        verification questions (see the verify_cache module docstring).
        Rows whose embedded ``vote_hash`` field does not match the
        recomputed digest — or with structurally empty
        owner/hash/signature — are neither verified nor cached:
        validate_vote rejects them before ever consulting the signature
        verdict, so caching them would only churn the LRU."""
        hashes = [compute_vote_hash(v) for v in votes]
        if self._verify_cache is None:
            if not votes:
                return PendingVoteVerdicts(lambda: ([], hashes))
            pending = self._scheme.verify_batch_submit(
                [v.vote_owner for v in votes],
                [v.signing_payload() for v in votes],
                [v.signature for v in votes],
            )

            def _finish_uncached():
                with observed_span(
                    self.tracer,
                    "engine.verify_batch",
                    self._m_verify,
                    votes=len(votes),
                ):
                    verdicts = pending.collect()
                self._note_verified(len(votes))
                return list(verdicts), hashes

            return PendingVoteVerdicts(_finish_uncached)
        cache = self._verify_cache
        verdicts: list = [False] * len(votes)
        rows: list[int] = []
        keys: list[bytes] = []
        payloads: list[bytes] = []
        for i, (vote, digest) in enumerate(zip(votes, hashes)):
            if (
                not vote.vote_owner
                or not vote.signature
                or vote.vote_hash != digest
            ):
                continue  # verdict unreachable in validate_vote's ordering
            payload = vote.signing_payload()
            rows.append(i)
            payloads.append(payload)
            keys.append(
                VerifiedVoteCache.key(
                    payload, vote.signature, self._verify_scheme_tag
                )
            )
        miss_rows: dict[bytes, list[int]] = {}
        miss_payloads: dict[bytes, bytes] = {}
        for i, key, payload, hit in zip(
            rows, keys, payloads, cache.get_many(keys)
        ):
            if hit is not MISS:
                verdicts[i] = hit
            else:
                miss_rows.setdefault(key, []).append(i)
                miss_payloads.setdefault(key, payload)
        if not miss_rows:
            return PendingVoteVerdicts(lambda: (verdicts, hashes))
        rep = [r[0] for r in miss_rows.values()]
        pending = self._scheme.verify_batch_submit(
            [votes[i].vote_owner for i in rep],
            list(miss_payloads.values()),
            [votes[i].signature for i in rep],
        )

        def _finish():
            with observed_span(
                self.tracer,
                "engine.verify_batch",
                self._m_verify,
                votes=len(rep),
            ):
                fresh = pending.collect()
            self._note_verified(len(rep))
            for (_, miss), verdict in zip(miss_rows.items(), fresh):
                for i in miss:
                    verdicts[i] = verdict
            cache.put_many(list(zip(miss_rows, fresh)))
            return verdicts, hashes

        return PendingVoteVerdicts(_finish)

    def _note_verified(self, count: int) -> None:
        self._m_verified_sigs.inc(count)
        self._m_verified_sigs_scheme.inc(count)

    def cast_vote(self, scope: Scope, proposal_id: int, choice: bool, now: int) -> Vote:
        """Sign, chain, and apply this peer's vote
        (reference: src/service.rs:216-237)."""
        record = self._get_record(scope, proposal_id)
        validate_proposal_timestamp(record.proposal.expiration_timestamp, now)
        identity = self._signer.identity()
        if identity in record.votes or (
            record.session is not None and identity in record.session.tallies
        ) or (
            record.retained_wire
            and any(
                identity == vote.vote_owner
                for vote in self._accepted_vote_chain(record)
            )
        ):
            raise UserAlreadyVoted()
        # Chain against the MERGED accepted chain: votes accepted through
        # the zero-copy wire path live in retained wire chunks, not
        # record.proposal.votes — linking against the scalar list alone
        # would mint a vote whose received_hash ignores the real tail,
        # and every other peer's dangling guard would (rightly) reject
        # it. The materialized view is decode-cached per growth.
        link_source = (
            self._materialized_proposal(record)
            if record.retained_wire
            else record.proposal
        )
        vote = build_vote(link_source, choice, self._signer, now)
        statuses = self.ingest_votes(
            [(scope, vote)], now, pre_validated=True
        )
        exc = error_for_code(int(statuses[0]))
        if exc is not None:
            raise exc()
        return vote

    def cast_vote_and_get_proposal(
        self, scope: Scope, proposal_id: int, choice: bool, now: int
    ) -> Proposal:
        """reference: src/service.rs:243-253"""
        self.cast_vote(scope, proposal_id, choice, now)
        return self._materialized_proposal(self._get_record(scope, proposal_id))

    def process_incoming_vote(self, scope: Scope, vote: Vote, now: int) -> None:
        """Scalar network-vote entry point (reference: src/service.rs:286-305):
        full host validation, then the batched device path."""
        statuses = self.ingest_votes([(scope, vote)], now)
        exc = error_for_code(int(statuses[0]))
        if exc is not None:
            raise exc()

    def _vote_prepass_begin(
        self, items: "list[tuple[Scope, Vote]]", pre_validated: bool
    ) -> "tuple[list[int], PendingVoteVerdicts] | None":
        """Start the batched signature prepass for an ingest_votes batch:
        resolve which rows have a locally-owned session (the same filter
        the apply loop uses), and submit their signatures through the
        admission cache to the verify pool. Returns (row indices, pending
        handle), or None when the batch takes no prepass (pre-validated,
        or a cacheless scalar call).

        Safe to call for batch k+1 BEFORE batch k applies — that is the
        double-buffered pipeline — because ingest_votes never evicts or
        unregisters sessions: everything the prepass RESOLVED stays
        resolved across vote applies. A batch may page a demoted session
        back in (tier promotion), but that only ADDS index entries — a
        row the prepass saw as session-less simply verifies inline at
        apply time, exactly like the cacheless path. (Interleaving
        proposal registration/eviction between begin and apply is NOT
        supported; ingest_votes_pipelined only chains vote batches, so
        the invariant holds by construction.)"""
        batch = len(items)
        if pre_validated or not (
            batch > 1 or (batch == 1 and self._verify_cache is not None)
        ):
            return None
        idxs = [
            i
            for i, (scope, vote) in enumerate(items)
            if (slot := self._index.get((scope, vote.proposal_id))) is not None
            and (slot < 0 or self._owns_slot(slot))  # skip misrouted rows
        ]
        if not idxs:
            return None
        return idxs, self._cached_verify_begin([items[i][1] for i in idxs])

    def ingest_votes_pipelined(
        self,
        batches: "list[list[tuple[Scope, Vote]]]",
        now: int,
        pre_validated: bool = False,
    ) -> "list[np.ndarray]":
        """Double-buffered :meth:`ingest_votes` over consecutive batches:
        batch k+1's signature prepass is submitted to the verify pool
        BEFORE batch k applies, so host crypto overlaps the previous
        batch's device dispatch and host bookkeeping. Result-identical to
        ``[ingest_votes(b, now, pre_validated) for b in batches]`` — the
        prepass is order-invariant across vote applies (see
        :meth:`_vote_prepass_begin`), statuses and events fire in the
        same per-batch order, and with the native pool absent the
        deferred-sync fallback restores today's sequential behavior byte
        for byte."""
        results: "list[np.ndarray]" = []
        prev: "tuple[list[tuple[Scope, Vote]], object] | None" = None
        for items in batches:
            items = list(items)
            prepass = self._vote_prepass_begin(items, pre_validated)
            if prev is not None:
                results.append(
                    self.ingest_votes(
                        prev[0], now, pre_validated, _prepass=prev[1]
                    )
                )
            prev = (items, prepass)
        if prev is not None:
            results.append(
                self.ingest_votes(prev[0], now, pre_validated, _prepass=prev[1])
            )
        return results

    def ingest_votes(
        self,
        items: list[tuple[Scope, Vote]],
        now: int,
        pre_validated: bool = False,
        _prepass=_PREPASS_INLINE,
    ) -> np.ndarray:
        """THE batch hot path: apply many votes across many sessions/scopes
        in one device dispatch.

        Per vote: resolve the session, host-validate (hash, signature,
        replay/expiry — skipped when ``pre_validated``, for locally built or
        already-verified replay traces), map owner→lane, then run the
        arrival-ordered ingest kernel. Emits ConsensusReached events for every
        session the batch decides. Returns int32 status codes in batch order
        (StatusCode.OK / ALREADY_REACHED are successes).

        ``_prepass`` (private) lets :meth:`ingest_votes_pipelined` hand in
        a signature prepass it already started for this batch; the
        default recomputes it inline, which is the same thing minus the
        overlap.
        """
        batch = len(items)
        self.tracer.count("engine.votes_in", batch)
        wall = time.monotonic()
        if batch:
            self._m_votes_total.inc(batch)
            self._m_batch_size.observe(batch)
            flight_recorder.record("engine.ingest_votes", votes=batch)
        statuses = np.zeros(batch, np.int32)
        dev_rows: list[int] = []  # indices into items that reach the device
        slots = np.empty(batch, np.int64)
        lanes = np.empty(batch, np.int32)
        values = np.empty(batch, bool)
        # Host-spilled sessions apply immediately but their events are queued
        # as (batch index, scope, event) and emitted interleaved with the
        # device path's, preserving per-vote arrival order across substrates.
        host_events: list[tuple[int, Scope, ConsensusEvent]] = []
        host_accepted = 0
        host_transitions = 0
        host_owned_transitions = 0
        # Per-signer health accounting, batched: admissions accumulate
        # into one dict flushed in a single monitor call (_flush_vote_
        # health), so the hot path pays dict stores, not per-vote locks.
        admit_counts: dict[bytes, int] = {}
        admit_timeout = 0.0
        # Chain-linkage tails per record for THIS batch: a same-batch
        # chained run (v2 extends tail, v3 extends v2) must see v2 as the
        # effective tail even on the device substrate, whose host-side
        # append happens after the dispatch. Optimistic — a mid-batch
        # apply-stage rejection (round cap) can let one dangling
        # follower through, matching the pre-guard behavior there.
        pending_tail: dict[int, bytes] = {}

        # Batched signature verification: one scheme call for the whole batch
        # (native runtime: one pool-fanned C batch, GIL-free). Verdicts are
        # injected into the per-vote check sequence, preserving exact scalar
        # error precedence. With the admission cache enabled the prepass
        # also covers batch == 1 (the process_incoming_vote / bridge scalar
        # path hits the cache too), dedups identical votes within the
        # batch, and only the cache misses reach the scheme. A pipelined
        # caller hands in the prepass it began before the PREVIOUS batch
        # applied; the crypto has been running in the background since.
        sig_verdicts: dict[int, object] = {}
        vote_hashes: dict[int, bytes] = {}
        if _prepass is _PREPASS_INLINE:
            _prepass = self._vote_prepass_begin(items, pre_validated)
        if _prepass is not None:
            idxs, pending = _prepass
            verdicts, hashes = pending.collect()
            sig_verdicts = dict(zip(idxs, verdicts))
            vote_hashes = dict(zip(idxs, hashes))

        for i, (scope, vote) in enumerate(items):
            slot = self._index.get((scope, vote.proposal_id))
            if slot is None:
                # Late vote on a demoted session: demand-page it back in
                # and apply exactly as if it had never left.
                slot = self._tier_lookup_promote(scope, vote.proposal_id)
                if slot is None:
                    statuses[i] = int(StatusCode.SESSION_NOT_FOUND)
                    continue
            record = self._records[slot]
            if (
                self._multihost
                and record.session is None
                and not self._owns_slot(slot)
            ):
                # Misrouted vote, rejected BEFORE validation: the relay
                # routes on this status, and a misrouted-but-invalid vote
                # must look the same as a misrouted-valid one.
                statuses[i] = int(StatusCode.SESSION_NOT_FOUND)
                continue
            if not pre_validated:
                try:
                    validate_vote(
                        vote,
                        self._scheme,
                        record.proposal.expiration_timestamp,
                        record.proposal.timestamp,
                        now,
                        sig_verdict=sig_verdicts.get(i),
                        computed_hash=vote_hashes.get(i),
                    )
                except ConsensusError as exc:
                    statuses[i] = int(exc.code)
                    self._note_reject_health(vote, int(exc.code), now)
                    continue
            # Dangling-vote guard (chaos-harness hardening, PARITY.md): a
            # FIRST-TIME voter whose received_hash names a vote this
            # session never accepted is rejected instead of appended. An
            # appended dangling vote makes the local chain positionally
            # incomparable to the sender's — the watermark can then never
            # extend it and anti-entropy can never repair the peer to
            # byte-identical state (and the divergence used to read as
            # fork "evidence" against an honest signer). Redeliveries and
            # equivocations (known owners) keep their duplicate-shaped
            # statuses; empty links and columnar-retained sessions keep
            # the reference's permissive behavior.
            # Wire-validated retention keeps the guard armed here too
            # (continuity state maintained by ingest_wire_columnar): a
            # session fed through the zero-copy bridge path and then hit
            # by a scalar/object-path vote must guard exactly as if every
            # vote had taken one path — otherwise the two paths' statuses
            # could diverge on the same byte stream. Only LEGACY
            # pre-validated columnar retention stays permissive.
            wire_guarded = record.retained_wire and record.wire_only
            if wire_guarded and (
                record.wire_seen is None
                or record.wire_sync
                != (len(record.retained_wire), len(record.scalar_seqs))
            ):
                self._resync_wire_chain(record)
            first_time_voter = not (
                record.retained_wire and not record.wire_only
            ) and (
                vote.vote_owner not in record.votes
                and not (wire_guarded and vote.vote_owner in record.wire_seen)
                and (
                    record.session is None
                    or (
                        vote.vote_owner not in record.session.tallies
                        and vote.vote_owner not in record.session.votes
                    )
                )
            )
            if first_time_voter:
                if vote.received_hash:
                    # An empty chain has no tail: a first vote claiming a
                    # received link is dangling by definition (the chain
                    # head always carries an empty link).
                    tail = pending_tail.get(
                        slot,
                        (record.wire_tail or b"")
                        if wire_guarded
                        else record.proposal.votes[-1].vote_hash
                        if record.proposal.votes
                        else b"",
                    )
                    if vote.received_hash != tail:
                        statuses[i] = int(StatusCode.RECEIVED_HASH_MISMATCH)
                        self.tracer.count("engine.dangling_votes_rejected")
                        continue
                # This vote is now the batch-effective tail for the
                # record (known-owner duplicates never move the tail).
                pending_tail[slot] = vote.vote_hash
            if record.session is not None:
                was_active = record.session.state.is_active
                code, event = self._host_add_vote(record, vote, now)
                statuses[i] = code
                if code == int(StatusCode.OK):
                    host_accepted += 1
                    record.last_activity = now
                    owner = vote.vote_owner
                    admit_counts[owner] = admit_counts.get(owner, 0) + 1
                    if record.config.consensus_timeout > admit_timeout:
                        admit_timeout = record.config.consensus_timeout
                    self._timelines.voted(slot, now, wall)
                    if trace_store.enabled and record.trace is not None:
                        trace_store.instant(
                            "consensus.vote_applied",
                            record.trace,
                            peer=self._trace_peer,
                            attrs={"owner": vote.vote_owner.hex()[:12]},
                        )
                if was_active and not record.session.state.is_active:
                    host_transitions += 1
                    # Host-spilled sessions are replicated on every
                    # process: decision metrics are ownership-gated like
                    # events so a fleet-wide sum counts each decision once.
                    owned = self._owns_slot(slot)
                    host_owned_transitions += owned
                    outcome = _OUTCOME_OF_STATE[state_code_of(record.session.state)]
                    self._timelines.decided(
                        slot, outcome, now, wall, observe=owned,
                    )
                    if trace_store.enabled and record.trace is not None:
                        trace_store.instant(
                            "consensus.decided",
                            record.trace,
                            peer=self._trace_peer,
                            attrs={"outcome": outcome},
                        )
                if event is not None and self._owns_slot(slot):
                    host_events.append((i, scope, event))
                continue
            lane = self._pool.lane_for(slot, vote.vote_owner)
            if lane is None:
                statuses[i] = int(StatusCode.VOTER_CAPACITY_EXCEEDED)
                continue
            slots[len(dev_rows)] = slot
            lanes[len(dev_rows)] = lane
            values[len(dev_rows)] = vote.vote
            dev_rows.append(i)

        if not dev_rows:
            if self._multihost:
                # Collective cadence: the other processes' batches are part
                # of the same global dispatch, so an empty one still joins.
                self._pool.ingest(
                    np.empty(0, np.int64), np.empty(0, np.int32),
                    np.empty(0, bool), now,
                )
            self.tracer.count("engine.votes_accepted", host_accepted)
            self.tracer.count("engine.transitions", host_transitions)
            self._m_votes_accepted.inc(host_accepted)
            self._m_decisions.inc(host_owned_transitions)
            for _, ev_scope, event in host_events:
                self._emit(ev_scope, event)
            self._flush_vote_health(
                items, statuses, admit_counts, admit_timeout, now,
                pre_validated,
            )
            return statuses

        k = len(dev_rows)
        with observed_span(
            self.tracer, "engine.device_ingest", self._m_device, votes=k
        ):
            dev_statuses, transitions = self._pool.ingest(
                slots[:k], lanes[:k], values[:k], now
            )
        statuses[np.asarray(dev_rows)] = dev_statuses
        # Re-stamp the wall clock AFTER the device dispatch completed: a
        # decision's latency must include the ingest that produced it (the
        # columnar path stamps at the same point), not the batch-entry time.
        wall = time.monotonic()
        accepted = int(np.sum(dev_statuses == int(StatusCode.OK))) + host_accepted
        self.tracer.count("engine.votes_accepted", accepted)
        self.tracer.count("engine.transitions", len(transitions) + host_transitions)
        self._m_votes_accepted.inc(accepted)
        # Device transitions are local by construction (misrouted votes
        # were rejected before the dispatch); host-spilled ones were
        # ownership-filtered above.
        self._m_decisions.inc(len(transitions) + host_owned_transitions)
        for slot, new_state in transitions:
            outcome = _OUTCOME_OF_STATE.get(new_state)
            if outcome is not None:
                self._timelines.decided(slot, outcome, now, wall)
                if trace_store.enabled:
                    tctx = self._records[slot].trace
                    if tctx is not None:
                        trace_store.instant(
                            "consensus.decided",
                            tctx,
                            peer=self._trace_peer,
                            attrs={"outcome": outcome},
                        )

        # Host bookkeeping for accepted votes, in arrival order; remember the
        # last accepted vote per slot — that is the vote that flipped a slot
        # that ended the batch decided (OK can never follow REACHED).
        last_ok: dict[int, int] = {}
        for j, i in enumerate(dev_rows):
            if dev_statuses[j] == int(StatusCode.OK):
                scope, vote = items[i]
                record = self._records[int(slots[j])]
                stored = vote.clone()  # as the scalar add_vote does
                record.votes[stored.vote_owner] = stored
                record.proposal.votes.append(stored)
                record.scalar_seqs.append(record.next_arrival_seq())
                record.bump_round(1)
                admit_counts[stored.vote_owner] = (
                    admit_counts.get(stored.vote_owner, 0) + 1
                )
                last_ok[int(slots[j])] = j
        for slot in last_ok:
            record = self._records[slot]
            record.last_activity = now
            cfg_timeout = record.config.consensus_timeout
            if cfg_timeout > admit_timeout:
                admit_timeout = cfg_timeout
            self._timelines.voted(slot, now, wall)
            if trace_store.enabled:
                tctx = self._records[slot].trace
                if tctx is not None:
                    trace_store.instant(
                        "consensus.vote_applied",
                        tctx,
                        peer=self._trace_peer,
                        attrs={"batch": int(batch)},
                    )

        # Event emission in per-vote arrival order, mirroring the scalar
        # path exactly: the deciding vote emits ConsensusReached, and every
        # later vote to the decided session re-emits it (the reference's
        # add_vote returns the existing result, which process_incoming_vote
        # turns into another event — src/session.rs:246, src/service.rs:303).
        # A STATE_FAILED transition (round-cap overrun) emits nothing,
        # matching the MaxRoundsExceeded error path (src/session.rs:334-343).
        newly_reached = {
            slot: new_state
            for slot, new_state in transitions
            if new_state in (STATE_REACHED_YES, STATE_REACHED_NO)
        }
        pending_events = host_events
        for j, i in enumerate(dev_rows):
            slot = int(slots[j])
            code = int(dev_statuses[j])
            emit_reached = (
                code == int(StatusCode.OK)
                and slot in newly_reached
                and last_ok.get(slot) == j
            ) or code == int(StatusCode.ALREADY_REACHED)
            if emit_reached:
                record = self._records[slot]
                state = self._pool.state_of(slot)
                pending_events.append(
                    (
                        i,
                        record.scope,
                        ConsensusReached(
                            proposal_id=record.proposal.proposal_id,
                            result=state == STATE_REACHED_YES,
                            timestamp=now,
                        ),
                    )
                )
        pending_events.sort(key=lambda t: t[0])
        for _, ev_scope, event in pending_events:
            self._emit(ev_scope, event)
        self._flush_vote_health(
            items, statuses, admit_counts, admit_timeout, now, pre_validated
        )
        return statuses

    # Duplicate-shaped statuses worth an equivocation probe: the session
    # already holds a vote by this owner (device DUPLICATE_VOTE, scalar
    # USER_ALREADY_VOTED) or absorbed a late vote post-decision
    # (ALREADY_REACHED) — all three reached the engine AFTER signature
    # admission, so a differing vote_hash means the owner validly signed
    # two distinct votes for one proposal.
    _EQUIVOCATION_PROBE_CODES = (
        int(StatusCode.DUPLICATE_VOTE),
        int(StatusCode.USER_ALREADY_VOTED),
        int(StatusCode.ALREADY_REACHED),
    )

    def _flush_vote_health(
        self,
        items: "list[tuple[Scope, Vote]]",
        statuses: np.ndarray,
        admit_counts: "dict[bytes, int]",
        admit_timeout: float,
        now: int,
        pre_validated: bool,
    ) -> None:
        """Per-batch health flush for ingest_votes: one batched admission
        update, then an equivocation probe over the (rare) duplicate-shaped
        rejections — two validly-signed votes with different hashes from
        one owner on one proposal become a retained evidence pair
        (obs.health module docstring)."""
        if not self._health_live or not len(items):
            return
        if admit_counts:
            self.health.note_admitted(
                admit_counts, now, timeout_hint=admit_timeout
            )
        if pre_validated:
            # No signature admission ran in THIS call (locally-built
            # votes, WAL replay, already-validated suffixes): a
            # duplicate-shaped rejection here must not mint a
            # verified-evidence record — an embedder bug or forged
            # replay row could otherwise fabricate "self-authenticating"
            # proof and 503 the node. The network-facing paths (the
            # only ones an attacker reaches) all validate, so coverage
            # is unchanged where it matters.
            return
        # Candidate selection must stay cheap on the clean path. Scalar
        # batches (the watermark/bridge shape) read one int; larger ones
        # take ONE vectorized any() pass (OK == 0, so any nonzero means
        # some rejection) before the per-code compares. np.isin is NOT
        # used — it costs ~250us per call at small batch sizes, which
        # alone would blow the redelivery budget.
        if len(items) == 1:
            if int(statuses[0]) not in self._EQUIVOCATION_PROBE_CODES:
                return
            rows = [0]
        else:
            if not statuses.any():
                return
            candidates = statuses == self._EQUIVOCATION_PROBE_CODES[0]
            for code in self._EQUIVOCATION_PROBE_CODES[1:]:
                candidates |= statuses == code
            if not candidates.any():
                return
            rows = np.nonzero(candidates)[0].tolist()
        last_key: "tuple | None" = None  # duplicates cluster per proposal
        record: "SessionRecord[Scope] | None" = None
        for i in rows:
            scope, vote = items[i]
            key = (scope, vote.proposal_id)
            if key != last_key:
                last_key = key
                slot = self._index.get(key)
                record = self._records[slot] if slot is not None else None
            if record is None:
                continue
            prior = record.votes.get(vote.vote_owner)
            if prior is not None and prior.vote_hash != vote.vote_hash:
                self.health.note_equivocation(
                    scope,
                    vote.proposal_id,
                    prior.encode(),
                    vote.encode(),
                    vote.vote_owner,
                    now,
                )

    def _note_reject_health(self, vote: Vote, code: int, now: int) -> None:
        """Scorecard attribution for per-vote admission rejections (the
        identity is the vote's *claimed* signer — see
        HealthMonitor.note_invalid_signature)."""
        if not self._health_live:
            return
        if code in (
            int(StatusCode.INVALID_VOTE_SIGNATURE),
            int(StatusCode.INVALID_VOTE_HASH),
            int(StatusCode.SIGNATURE_SCHEME),
        ):
            if vote.vote_owner:
                self.health.note_invalid_signature(vote.vote_owner, now)
        elif code == int(StatusCode.VOTE_EXPIRED):
            if vote.vote_owner:
                self.health.note_expired(vote.vote_owner, now)

    def voter_gid(self, owner: bytes) -> int:
        """Intern an owner identity for the columnar ingest path.

        Gids are generation-tagged (``generation << 32 | index``): a gid
        freed by any session-releasing call (delete_scope, per-scope-cap
        eviction inside create_proposal, spill) is rejected with
        EMPTY_VOTE_OWNER from then on — including after its index is
        recycled to a new owner, whose gid carries a newer generation.
        Stale use is therefore always a typed error, never silent
        misattribution; re-interning per batch (a dict hit) merely avoids
        the rejections for voters whose membership churns."""
        return self._pool.voter_gid(owner)

    def ingest_columnar(
        self,
        scope: Scope,
        proposal_ids: np.ndarray,
        voter_gids: np.ndarray,
        values: np.ndarray,
        now: int,
        max_depth: int = 8,
        wire_votes: "list[bytes] | tuple[bytes, np.ndarray] | None" = None,
    ) -> np.ndarray:
        """THE throughput path: apply an arrival-ordered vote batch given as
        dense columns (structure-of-arrays) — proposal ids, interned voter
        ids (:meth:`voter_gid`), yes/no values — with zero per-vote Python.

        Same observable semantics as :meth:`ingest_votes` with
        ``pre_validated=True`` (validation, when needed, happens upstream:
        wire decode + signature verification are batch host stages), with
        two deliberate trade-offs, both documented in PARITY.md:
        - by default no per-vote ``Vote`` objects are accumulated host-side,
          so gossip reconstruction/export sees tallies but not vote chains;
          pass ``wire_votes`` (the encoded Vote bytes per row, either a list
          or a ``(packed, offsets)`` pair) to retain accepted rows' verbatim
          bytes off the timing path — proposal exports then re-embed them,
          merged with any scalar-ingested votes in true (call-granularity)
          arrival order, so the proposal re-gossips with a chain-valid vote
          list even for sessions fed through both paths (reference:
          src/utils.rs:175-215);
        - event ordering is guaranteed per-session, not across sessions.

        Resolution is fully vectorized (open-addressing _PidLookup hash for
        proposal→slot, dense lane tables for voter→lane), and the device
        work is split into bounded-depth dispatches pipelined through
        ``ingest_async`` so scan depth never exceeds ``max_depth`` and
        transfers overlap device compute. Returns int32 statuses in batch
        order (reference semantics per code, as ingest_votes).
        """
        proposal_ids = np.asarray(proposal_ids, np.int64)
        voter_gids = np.asarray(voter_gids, np.int64)
        values = np.asarray(values, bool)
        wire_norm, statuses, done = self._columnar_preamble(
            len(proposal_ids), wire_votes
        )
        if done:
            return statuses
        found, slots = self._pid_lookup(scope).lookup(proposal_ids)
        if self._promote_columnar_misses([scope], None, proposal_ids, found):
            found, slots = self._pid_lookup(scope).lookup(proposal_ids)
        return self._columnar_finish(
            slots, found, voter_gids, values, now, max_depth, statuses,
            wire_norm,
        )

    def _columnar_preamble(
        self, batch: int, wire_votes
    ) -> "tuple[tuple[np.ndarray, np.ndarray] | None, np.ndarray, bool]":
        """Shared entry sequence of the columnar paths: normalize wire
        bytes BEFORE any state mutates, count the batch, init statuses.
        The returned ``done`` flag short-circuits empty single-host
        batches; multi-host must NOT shortcut — an empty local batch still
        joins the fleet's agreed dispatch cadence (allgather + padding in
        _columnar_apply)."""
        wire_norm = (
            self._normalize_wire(wire_votes, batch)
            if wire_votes is not None
            else None
        )
        self.tracer.count("engine.votes_in", batch)
        if batch:
            self._m_votes_total.inc(batch)
            self._m_batch_size.observe(batch)
            flight_recorder.record("engine.ingest_columnar", votes=batch)
        statuses = np.full(batch, int(StatusCode.SESSION_NOT_FOUND), np.int32)
        return wire_norm, statuses, batch == 0 and not self._multihost

    def _columnar_finish(
        self,
        slots: np.ndarray,
        found: np.ndarray,
        voter_gids: np.ndarray,
        values: np.ndarray,
        now: int,
        max_depth: int,
        statuses: np.ndarray,
        wire_norm: "tuple[np.ndarray, np.ndarray] | None",
        wire_validated: bool = False,
    ) -> np.ndarray:
        """Shared tail of the columnar paths: apply, then retain accepted
        rows' wire bytes keyed by the resolved slots. ``wire_validated``
        marks retention coming from the guard-ordered wire path — the
        only kind that keeps a record's chain positionally comparable
        (SessionRecord.wire_only)."""
        statuses = self._columnar_apply(
            slots, found, voter_gids, values, now, max_depth, statuses
        )
        if wire_norm is not None:
            with observed_span(
                self.tracer, "engine.post_apply", self._m_post_apply
            ):
                self._retain_wire_slots(
                    statuses, slots, wire_norm, wire_validated
                )
        return statuses

    @staticmethod
    def _normalize_wire(wire_votes, batch: int) -> tuple[np.ndarray, np.ndarray]:
        """Validate and normalize wire_votes to (u8 data, i64 offsets)
        BEFORE any state mutates — a malformed argument must fail the call,
        not strand already-applied votes without their retained bytes."""
        blob, offsets = normalize_wire_votes(wire_votes, batch)
        return np.frombuffer(blob, np.uint8), offsets

    def _retain_wire_slots(
        self,
        statuses: np.ndarray,
        slots: np.ndarray,
        wire_norm: tuple[np.ndarray, np.ndarray],
        wire_validated: bool = False,
    ) -> None:
        """Attach accepted rows' verbatim vote bytes to their session
        records, keyed by the already-resolved slots (vectorized gather;
        one Python iteration per touched session, not per vote). Shared by
        the single- and multi-scope columnar entry points — slots identify
        records directly, so retention is scope-agnostic."""
        ok_rows = np.nonzero(statuses == int(StatusCode.OK))[0]
        if ok_rows.size == 0:
            return
        data_arr, offsets = wire_norm
        ok_slots = slots[ok_rows]
        order = np.argsort(ok_slots, kind="stable")  # arrival order per slot
        rows = ok_rows[order]
        s_sorted = ok_slots[order]
        starts = offsets[rows]
        lens = offsets[rows + 1] - starts
        ends = starts + lens
        uniq, seg_start = np.unique(s_sorted, return_index=True)
        seg_bounds = np.append(seg_start, len(rows))

        # Fast path: every slot's accepted rows occupy one contiguous span
        # of the packed data (the common streaming layout — batch packed in
        # arrival order, slot-major). Each slot's blob is then ONE slice;
        # the general path below materializes a per-byte gather index,
        # which is ~len(data) int64 entries of host work.
        contig = np.ones(len(rows), bool)
        if len(rows) > 1:
            contig[1:] = starts[1:] == ends[:-1]
            contig[seg_start] = True  # span breaks at slot boundaries are fine
        if contig.all():
            # All per-slot offset arrays are built in ONE pass (each slot's
            # cells plus a trailing end cell), so the per-slot loop is just
            # two small slices — per-slot np.append/tobytes overhead was
            # ~15us x touched-slots, the retained-churn bench's biggest
            # line item.
            s_count = len(uniq)
            counts = np.diff(seg_bounds)
            base = starts[seg_start]  # [S] span base per slot
            k_of_row = np.repeat(np.arange(s_count), counts)
            all_off = np.empty(len(rows) + s_count, np.int64)
            all_off[np.arange(len(rows)) + k_of_row] = starts - base[k_of_row]
            end_pos = seg_bounds[1:] + np.arange(s_count)
            seg_ends = ends[seg_bounds[1:] - 1]
            all_off[end_pos] = seg_ends - base
            data_bytes = data_arr.tobytes()
            records = self._records
            base_l = base.tolist()
            ends_l = seg_ends.tolist()
            lo_l = (seg_bounds[:-1] + np.arange(s_count)).tolist()
            hi_l = end_pos.tolist()
            for k, slot in enumerate(uniq.tolist()):
                record = records[slot]
                record.wire_only = record.wire_only and wire_validated
                seq = record.arrival_seq
                record.arrival_seq = seq + 1
                record.retained_wire.append(
                    (
                        seq,
                        data_bytes[base_l[k] : ends_l[k]],
                        all_off[lo_l[k] : hi_l[k] + 1].copy(),
                    )
                )
            return

        out_off = np.zeros(len(rows) + 1, np.int64)
        np.cumsum(lens, out=out_off[1:])
        gather = (
            np.arange(int(out_off[-1]), dtype=np.int64)
            - np.repeat(out_off[:-1], lens)
            + np.repeat(starts, lens)
        )
        blob = data_arr[gather]
        for k, slot in enumerate(uniq.tolist()):
            lo, hi = int(seg_bounds[k]), int(seg_bounds[k + 1])
            seg_off = (out_off[lo : hi + 1] - out_off[lo]).copy()
            seg_blob = blob[int(out_off[lo]) : int(out_off[hi])].tobytes()
            record = self._records[int(slot)]
            record.wire_only = record.wire_only and wire_validated
            record.retained_wire.append(
                (record.next_arrival_seq(), seg_blob, seg_off)
            )

    def ingest_columnar_multi(
        self,
        scopes: list,
        scope_idx: np.ndarray,
        proposal_ids: np.ndarray,
        voter_gids: np.ndarray,
        values: np.ndarray,
        now: int,
        max_depth: int = 8,
        wire_votes: "list[bytes] | tuple[bytes, np.ndarray] | None" = None,
    ) -> np.ndarray:
        """Mixed-scope columnar ingest: one fused device pipeline across
        many scopes (BASELINE config-5 churn shape). ``scopes`` lists the
        distinct scopes; ``scope_idx`` (int32, per row) indexes into it.
        Per-scope work is only the proposal-id resolution — one _PidLookup
        hash probe pass per scope — so a 256-scope stream costs 256 cheap
        vectorized lookups, not 256 device dispatches; lanes, dispatch
        segmentation, statuses, events, and opt-in ``wire_votes`` retention
        (accepted rows' verbatim bytes, re-embedded chain-valid on export —
        reference: src/utils.rs:175-215) are shared with
        :meth:`ingest_columnar`."""
        proposal_ids = np.asarray(proposal_ids, np.int64)
        scope_idx = np.asarray(scope_idx, np.int64)
        voter_gids = np.asarray(voter_gids, np.int64)
        values = np.asarray(values, bool)
        batch = len(proposal_ids)
        wire_norm, statuses, done = self._columnar_preamble(batch, wire_votes)
        if done:
            return statuses
        found, slots = self._resolve_slots_multi(scopes, scope_idx, proposal_ids)
        return self._columnar_finish(
            slots, found, voter_gids, values, now, max_depth, statuses,
            wire_norm,
        )

    def _resolve_slots_multi(
        self, scopes: list, scope_idx: np.ndarray, proposal_ids: np.ndarray
    ) -> "tuple[np.ndarray, np.ndarray]":
        """Mixed-scope proposal-id resolution shared by the columnar entry
        points: (found bool[B], slots int64[B]). Rows that miss the live
        index but hit the demoted tier page their sessions back in and
        re-resolve — columnar late votes see an untier'd engine."""
        with observed_span(self.tracer, "engine.resolve_slots", self._m_resolve):
            found, slots = self._resolve_slots_multi_once(
                scopes, scope_idx, proposal_ids
            )
            if self._promote_columnar_misses(
                scopes, scope_idx, proposal_ids, found
            ):
                found, slots = self._resolve_slots_multi_once(
                    scopes, scope_idx, proposal_ids
                )
        return found, slots

    def _resolve_slots_multi_once(
        self, scopes: list, scope_idx: np.ndarray, proposal_ids: np.ndarray
    ) -> "tuple[np.ndarray, np.ndarray]":
        batch = len(proposal_ids)
        found = np.zeros(batch, bool)
        slots = np.zeros(batch, np.int64)
        fused = self._fused_pid_lookup(scopes)
        if fused is not None:
            # Composite (scope_ordinal << 32 | pid) probe: the whole
            # mixed-scope batch resolves in one vectorized pass. Rows whose
            # pid falls outside u32 can never match a registered id.
            rows = np.nonzero(
                (proposal_ids >= 0) & (proposal_ids >> np.int64(32) == 0)
            )[0]
            if rows.size:
                comp = (scope_idx[rows] << np.int64(32)) | proposal_ids[rows]
                hit, hit_slots = fused.lookup(comp)
                found[rows] = hit
                slots[rows] = hit_slots
        else:
            # Fallback: one stable sort groups the rows of every scope
            # (O(batch log batch) total, not one full scan per scope).
            order = np.argsort(scope_idx, kind="stable")
            bounds = np.searchsorted(
                scope_idx[order], np.arange(len(scopes) + 1)
            )
            for k, scope in enumerate(scopes):
                rows = order[bounds[k] : bounds[k + 1]]
                if rows.size == 0:
                    continue
                hit, hit_slots = self._pid_lookup(scope).lookup(
                    proposal_ids[rows]
                )
                found[rows] = hit
                slots[rows] = hit_slots
        return found, slots

    # ── Zero-copy wire ingest (OP_VOTE_BATCH columnar fast path) ───────

    def wire_verify_begin(
        self,
        data: np.ndarray,
        cols: np.ndarray,
        offsets: np.ndarray,
        buf: "bytes | None" = None,
    ) -> "WireVotePrepass":
        """Session-independent half of the wire-columnar validation:
        structural emptiness checks, the batched vote-hash pass, and ONE
        cache-aware signature batch submit over the survivors — all from
        parsed columns (:mod:`hashgraph_tpu.bridge.columnar`), no Vote
        objects anywhere. The crypto is in flight on the verify pool when
        this returns, so a pipelined bridge connection submits frame
        k+1's prepass while frame k applies (the 3-stage wire pipeline).
        Safe to run before earlier queued frames apply because nothing
        here reads session state — the same order-invariance contract as
        :meth:`_vote_prepass_begin`, extended across session
        registration (slot resolution happens at apply time, in receive
        order, inside :meth:`ingest_wire_columnar`).

        Check precedence mirrors ``validate_vote`` exactly: empty owner,
        empty hash, empty signature, hash mismatch, then signature.
        Replay/expiry need the session record and stay in
        :meth:`ingest_wire_columnar`. ``offsets`` are the per-row spans
        into ``data`` — the signing payload of a canonical row is the
        PREFIX ``data[offsets[i] : offsets[i] + sign_len]``, so no
        re-encode ever happens."""
        from ..bridge import columnar as C

        k = len(cols)
        pre = np.zeros(k, np.int32)
        owner_len = cols[:, C.COL_OWNER_LEN]
        hash_len = cols[:, C.COL_HASH_LEN]
        sig_len = cols[:, C.COL_SIG_LEN]
        pre[owner_len == 0] = int(StatusCode.EMPTY_VOTE_OWNER)
        pre[(pre == 0) & (hash_len == 0)] = int(StatusCode.EMPTY_VOTE_HASH)
        pre[(pre == 0) & (sig_len == 0)] = int(StatusCode.EMPTY_SIGNATURE)
        live = pre == 0
        if live.any():
            digests = C.vote_hash_columns(data, cols)
            rows32 = np.nonzero(live & (hash_len == 32))[0]
            if rows32.size:
                gather = (
                    cols[rows32, C.COL_HASH_OFF, None]
                    + np.arange(32, dtype=np.int64)
                )
                mismatch = (data[gather] != digests[rows32]).any(axis=1)
                pre[rows32[mismatch]] = int(StatusCode.INVALID_VOTE_HASH)
            pre[live & (hash_len != 32)] = int(StatusCode.INVALID_VOTE_HASH)
        crypto_rows = np.nonzero(pre == 0)[0]
        if crypto_rows.size == 0:
            return WireVotePrepass(pre, crypto_rows, lambda: [], buf=buf)
        # Byte slices only for rows that reach crypto: one slice each for
        # owner / payload / signature — no decode, no re-encode (the
        # signing payload is a prefix of the canonical wire bytes).
        if buf is None:
            buf = data.tobytes()
        base = np.asarray(offsets, np.int64)[crypto_rows].tolist()
        row_l = cols[crypto_rows].tolist()
        owners: list[bytes] = []
        payloads: list[bytes] = []
        sigs: list[bytes] = []
        for start, c in zip(base, row_l):
            owners.append(
                buf[c[C.COL_OWNER_OFF]:c[C.COL_OWNER_OFF] + c[C.COL_OWNER_LEN]]
            )
            payloads.append(buf[start:start + c[C.COL_SIGN_LEN]])
            sigs.append(
                buf[c[C.COL_SIG_OFF]:c[C.COL_SIG_OFF] + c[C.COL_SIG_LEN]]
            )
        return WireVotePrepass(
            pre,
            crypto_rows,
            self._wire_crypto_begin(owners, payloads, sigs),
            buf=buf,
        )

    def _wire_crypto_begin(self, owners, payloads, sigs):
        """Cache-aware batched signature verify over byte triples (the
        object path's :meth:`_cached_verify_begin` minus Vote objects):
        dedups identical (payload, signature) items, consults the
        admission cache, submits ONE scheme batch over the misses, and
        returns a zero-arg collect -> verdicts aligned with the input."""
        k = len(owners)
        if self._verify_cache is None:
            pending = self._scheme.verify_batch_submit(owners, payloads, sigs)

            def _finish_uncached():
                with observed_span(
                    self.tracer, "engine.verify_batch", self._m_verify, votes=k
                ):
                    verdicts = pending.collect()
                self._note_verified(k)
                return list(verdicts)

            return _finish_uncached
        cache = self._verify_cache
        verdicts: list = [False] * k
        keys = [
            VerifiedVoteCache.key(payload, sig, self._verify_scheme_tag)
            for payload, sig in zip(payloads, sigs)
        ]
        miss_rows: dict[bytes, list[int]] = {}
        for i, (key, hit) in enumerate(zip(keys, cache.get_many(keys))):
            if hit is not MISS:
                verdicts[i] = hit
            else:
                miss_rows.setdefault(key, []).append(i)
        if not miss_rows:
            return lambda: verdicts
        rep = [rows[0] for rows in miss_rows.values()]
        pending = self._scheme.verify_batch_submit(
            [owners[i] for i in rep],
            [payloads[i] for i in rep],
            [sigs[i] for i in rep],
        )

        def _finish():
            with observed_span(
                self.tracer, "engine.verify_batch", self._m_verify,
                votes=len(rep),
            ):
                fresh = pending.collect()
            self._note_verified(len(rep))
            for (_, rows), verdict in zip(miss_rows.items(), fresh):
                for i in rows:
                    verdicts[i] = verdict
            cache.put_many(list(zip(miss_rows, fresh)))
            return verdicts

        return _finish

    def ingest_wire_columnar(
        self,
        scopes: list,
        scope_idx: np.ndarray,
        cols: np.ndarray,
        data: np.ndarray,
        offsets: np.ndarray,
        now: int,
        max_depth: int = 8,
        stage_seconds: "dict | None" = None,
        _prepass: "WireVotePrepass | None" = None,
        _buf: "bytes | None" = None,
    ) -> np.ndarray:
        """THE wire throughput path: fully *validated* mixed-scope ingest
        straight from parsed ``OP_VOTE_BATCH`` columns — hash, signature
        (batched, admission-cached), replay/expiry, and the dangling-vote
        guard all run without constructing a single ``Vote`` object, then
        the surviving rows land on the shared columnar apply pipeline
        (:meth:`_columnar_finish`) with wire retention on.

        Status-identical to ``ingest_votes`` (``pre_validated=False``)
        over the same decoded rows — the bridge's object path remains the
        parity oracle, property-tested in tests/test_wire_columnar.py and
        fuzz-tested in tests/test_wire_fuzz.py; divergences that remain
        (health admission granularity) are documented in PARITY.md.

        ``cols``/``data``/``offsets`` come from
        :func:`hashgraph_tpu.bridge.columnar.parse_vote_columns` over
        canonical rows ONLY (callers fall back to the object path for
        anything else). ``stage_seconds`` (optional dict) accumulates
        ``"crypto"`` and ``"apply"`` wall seconds for the bench's stage
        attribution. ``_prepass`` accepts a
        :meth:`wire_verify_begin` started earlier (the pipelined bridge
        starts it on the reader thread); default recomputes it inline.
        ``_buf`` accepts the vote region already materialized as bytes
        (a durable wrapper shares its WAL blob; the prepass's copy is
        reused the same way) — one ``tobytes()`` per frame, not three."""
        from ..bridge import columnar as C

        scope_idx = np.asarray(scope_idx, np.int64)
        offsets = np.asarray(offsets, np.int64)
        batch = len(cols)
        self.tracer.count("engine.votes_in", batch)
        if batch:
            self._m_votes_total.inc(batch)
            self._m_batch_size.observe(batch)
            self._m_wire_dispatches.inc()
            self._m_wire_apply_rows.inc(batch)
            flight_recorder.record("engine.ingest_wire_columnar", votes=batch)
        statuses = np.full(batch, int(StatusCode.SESSION_NOT_FOUND), np.int32)
        if batch == 0 and not self._multihost:
            return statuses
        pids = np.ascontiguousarray(cols[:, C.COL_PID])
        found, slots = self._resolve_slots_multi(scopes, scope_idx, pids)
        if self._multihost:
            # Misrouted rows reject BEFORE validation (SESSION_NOT_FOUND),
            # mirroring ingest_votes' precedence: the relay routes on this
            # status and a misrouted-but-invalid vote must look the same
            # as a misrouted-valid one.
            lo, hi = self._pool.local_slots()
            non_local = found & (slots >= 0) & ((slots < lo) | (slots >= hi))
            found &= ~non_local
        t0 = time.monotonic()
        with observed_span(self.tracer, "engine.verify_collect", None):
            prepass = (
                _prepass
                if _prepass is not None
                else self.wire_verify_begin(data, cols, offsets, buf=_buf)
            )
            buf = _buf if _buf is not None else prepass.buf
            if buf is None:
                buf = data.tobytes()
            prepass.buf = buf
            verdicts = prepass.collect()
            pre = prepass.pre_status
            valid = found.copy()
            fail = found & (pre != 0)
            statuses[fail] = pre[fail]
            valid &= pre == 0
            # Signature verdicts (validate_vote's injection semantics: an
            # exception verdict carries its own status code).
            sig_reject: list[tuple[int, int]] = []
            for row, verdict in zip(prepass.crypto_rows.tolist(), verdicts):
                if verdict is True:
                    continue
                if isinstance(verdict, Exception):
                    code = int(
                        getattr(verdict, "code", StatusCode.SIGNATURE_SCHEME)
                    )
                else:
                    code = int(StatusCode.INVALID_VOTE_SIGNATURE)
                sig_reject.append((row, code))
            for row, code in sig_reject:
                if valid[row]:
                    statuses[row] = code
                    valid[row] = False
        if stage_seconds is not None:
            stage_seconds["crypto"] = (
                stage_seconds.get("crypto", 0.0) + time.monotonic() - t0
            )
        t1 = time.monotonic()
        # The apply stage [t1, end) is tiled by per-frame spans:
        # engine.wire_validate, engine.health (two passes),
        # engine.plan_dispatch, engine.device_ingest, engine.post_apply.
        tracer = self.tracer
        with observed_span(tracer, "engine.wire_validate", self._m_validate):
            # Replay/expiry checks need the session record: per-UNIQUE-slot
            # timestamp lookup, then one vectorized compare per rule.
            ts_u64 = np.ascontiguousarray(cols[:, C.COL_TS]).view(np.uint64)
            rows_v = np.nonzero(valid)[0]
            admit_timeout = 0.0
            if rows_v.size:
                uniq = np.unique(slots[rows_v])
                creation = np.empty(len(uniq), np.uint64)
                expiry = np.empty(len(uniq), np.uint64)
                for j, slot in enumerate(uniq.tolist()):
                    record = self._records[slot]
                    creation[j] = record.proposal.timestamp
                    expiry[j] = record.proposal.expiration_timestamp
                    if record.config.consensus_timeout > admit_timeout:
                        admit_timeout = record.config.consensus_timeout
                pos = np.searchsorted(uniq, slots[rows_v])
                ts_rows = ts_u64[rows_v]
                old = ts_rows < creation[pos]
                expired = ~old & (
                    (ts_rows > expiry[pos]) | (np.uint64(now) > expiry[pos])
                )
                statuses[rows_v[old]] = int(
                    StatusCode.TIMESTAMP_OLDER_THAN_CREATION_TIME
                )
                statuses[rows_v[expired]] = int(StatusCode.VOTE_EXPIRED)
                valid[rows_v[old | expired]] = False
        with observed_span(
            tracer, "engine.health", self._m_health, **{"pass": "reject"}
        ):
            self._wire_reject_health(buf, cols, found, statuses, now)
        with observed_span(tracer, "engine.wire_validate", self._m_validate):
            self._wire_dangling_guard(buf, cols, slots, valid, statuses)
            # Voter interning: one gid per UNIQUE owner (vectorized when
            # the scheme's identities are fixed-width — the common case),
            # then the shared columnar apply with wire retention on.
            gids = self._wire_intern_gids(buf, cols, valid)
            values = cols[:, C.COL_VALUE] != 0
        statuses = self._columnar_finish(
            slots, valid, gids, values, now, max_depth, statuses,
            (data, offsets), wire_validated=True,
        )
        with observed_span(tracer, "engine.post_apply", self._m_post_apply):
            self._wire_track_chain(buf, cols, slots, offsets, statuses)
        with observed_span(
            tracer, "engine.health", self._m_health, **{"pass": "admit"}
        ):
            self._wire_admit_health(
                buf, cols, scopes, scope_idx, slots, offsets, statuses,
                admit_timeout, now,
            )
        if stage_seconds is not None:
            stage_seconds["apply"] = (
                stage_seconds.get("apply", 0.0) + time.monotonic() - t1
            )
        return statuses

    def _wire_reject_health(self, buf, cols, found, statuses, now) -> None:
        """Scorecard attribution for wire-columnar validation rejects —
        the vectorized twin of the object path's per-vote
        ``_note_reject_health`` (same code set, same claimed-signer
        attribution), sliced from the frame only on the failure path."""
        if not self._health_live:
            return
        from ..bridge import columnar as C

        sig_codes = (
            int(StatusCode.INVALID_VOTE_SIGNATURE),
            int(StatusCode.INVALID_VOTE_HASH),
            int(StatusCode.SIGNATURE_SCHEME),
        )
        mask = found & (
            (statuses == sig_codes[0])
            | (statuses == sig_codes[1])
            | (statuses == sig_codes[2])
            | (statuses == int(StatusCode.VOTE_EXPIRED))
        )
        for row in np.nonzero(mask)[0].tolist():
            c = cols[row]
            owner = buf[c[C.COL_OWNER_OFF]:c[C.COL_OWNER_OFF] + c[C.COL_OWNER_LEN]]
            if not owner:
                continue
            if int(statuses[row]) == int(StatusCode.VOTE_EXPIRED):
                self.health.note_expired(owner, now)
            else:
                self.health.note_invalid_signature(owner, now)

    def _wire_dangling_guard(self, buf, cols, slots, valid, statuses) -> None:
        """The ingest_votes dangling-vote guard over columns: a
        first-time voter whose received_hash does not name the session's
        effective tail is rejected instead of appended — identical
        semantics (including the optimistic in-batch tail walk) to the
        object path on a fresh session. Unlike the legacy pre-validated
        columnar path, the guard STAYS armed across wire frames: the
        record's ``wire_tail``/``wire_seen`` continuity state (updated
        from ground-truth accepted rows in :meth:`_wire_track_chain`)
        carries the tail forward, so a dropped or reordered gossip frame
        rejects its dangling followers on every peer the same way —
        without this a storm could diverge peers into states anti-entropy
        cannot reconcile. Sessions whose retained wire came from the
        legacy permissive path (stale sync stamp) stay permissive, as
        documented in PARITY.md."""
        from ..bridge import columnar as C

        rows = np.nonzero(valid)[0]
        if rows.size == 0:
            return
        order = np.argsort(slots[rows], kind="stable")
        prev_slot = -1
        guard = False
        tail = b""
        seen: set = set()
        for i in rows[order].tolist():
            slot = int(slots[i])
            if slot != prev_slot:
                prev_slot = slot
                record = self._records[slot]
                if not record.retained_wire:
                    guard = True
                    tail = (
                        record.proposal.votes[-1].vote_hash
                        if record.proposal.votes
                        else b""
                    )
                    seen = set(record.votes)
                    if record.session is not None:
                        seen.update(record.session.tallies)
                        seen.update(record.session.votes)
                elif record.wire_only:
                    if record.wire_seen is None or record.wire_sync != (
                        len(record.retained_wire), len(record.scalar_seqs)
                    ):
                        # Scalar accepts or a watermark extension landed
                        # since the last wire frame: rebuild the
                        # continuity state from the merged accepted
                        # chain (decode is cached per growth).
                        self._resync_wire_chain(record)
                    guard = True
                    tail = record.wire_tail or b""
                    seen = set(record.wire_seen)
                    if record.session is not None:
                        seen.update(record.session.tallies)
                        seen.update(record.session.votes)
                else:
                    guard = False
            if not guard:
                continue
            c = cols[i]
            owner = buf[c[C.COL_OWNER_OFF]:c[C.COL_OWNER_OFF] + c[C.COL_OWNER_LEN]]
            if owner in seen:
                continue
            received = buf[c[C.COL_RECV_OFF]:c[C.COL_RECV_OFF] + c[C.COL_RECV_LEN]]
            if received and received != tail:
                statuses[i] = int(StatusCode.RECEIVED_HASH_MISMATCH)
                valid[i] = False
                self.tracer.count("engine.dangling_votes_rejected")
                continue
            tail = buf[c[C.COL_HASH_OFF]:c[C.COL_HASH_OFF] + c[C.COL_HASH_LEN]]
            seen.add(owner)

    def _accepted_vote_chain(self, record: "SessionRecord[Scope]") -> list:
        """The session's accepted votes in true arrival order, retained
        wire chunks and scalar accepts merged (no clones — callers read,
        never mutate). For wire_only records this IS the positional
        chain the watermark compares against."""
        retained = self._decoded_retained(record)
        scalar = record.proposal.votes
        if not retained:
            return scalar
        n_pre = len(scalar) - len(record.scalar_seqs)
        items: list[tuple[int, list]] = [(-1, scalar[:n_pre])] if n_pre else []
        items.extend(
            (seq, [vote])
            for seq, vote in zip(record.scalar_seqs, scalar[n_pre:])
        )
        items.extend(retained)
        items.sort(key=lambda t: t[0])
        return [vote for _, votes in items for vote in votes]

    def _resync_wire_chain(self, record: "SessionRecord[Scope]") -> None:
        """Rebuild the wire-guard continuity state from the merged
        accepted chain (after scalar accepts or a watermark extension
        touched a wire_only record)."""
        chain = self._accepted_vote_chain(record)
        record.wire_seen = {vote.vote_owner for vote in chain}
        record.wire_tail = chain[-1].vote_hash if chain else b""
        record.wire_sync = (
            len(record.retained_wire), len(record.scalar_seqs)
        )

    def _wire_track_chain(self, buf, cols, slots, offsets, statuses) -> None:
        """Post-apply continuity update: fold each slot's ACCEPTED rows
        (frame order) into the record's wire chain state — effective
        tail hash, accepted-owner set, and the sync stamp that proves no
        other path touched the record since."""
        from ..bridge import columnar as C

        ok_rows = np.nonzero(statuses == int(StatusCode.OK))[0]
        if ok_rows.size == 0:
            return
        order = np.argsort(slots[ok_rows], kind="stable")
        for i in ok_rows[order].tolist():
            record = self._records[int(slots[i])]
            if record.wire_seen is None:
                record.wire_seen = set(record.votes)
            c = cols[i]
            record.wire_seen.add(
                buf[c[C.COL_OWNER_OFF]:c[C.COL_OWNER_OFF] + c[C.COL_OWNER_LEN]]
            )
            record.wire_tail = (
                buf[c[C.COL_HASH_OFF]:c[C.COL_HASH_OFF] + c[C.COL_HASH_LEN]]
            )
            record.wire_sync = (
                len(record.retained_wire), len(record.scalar_seqs)
            )

    def _wire_intern_gids(self, buf, cols, valid) -> np.ndarray:
        """gid column for the apply stage: unique owners interned once
        each. Fixed-width identities (every real scheme) dedupe in one
        vectorized np.unique over an [N, L] byte matrix; mixed widths
        fall back to a memo dict."""
        from ..bridge import columnar as C

        batch = len(cols)
        gids = np.zeros(batch, np.int64)
        rows = np.nonzero(valid)[0]
        if rows.size == 0:
            return gids
        lens = cols[rows, C.COL_OWNER_LEN]
        width = int(lens[0])
        if (lens == width).all():
            data_arr = np.frombuffer(buf, np.uint8)
            gather = (
                cols[rows, C.COL_OWNER_OFF, None]
                + np.arange(width, dtype=np.int64)
            )
            matrix = data_arr[gather]
            uniq, inverse = np.unique(matrix, axis=0, return_inverse=True)
            uniq_gids = np.array(
                [self._pool.voter_gid(row.tobytes()) for row in uniq],
                np.int64,
            )
            gids[rows] = uniq_gids[inverse.reshape(-1)]
        else:
            memo: dict[bytes, int] = {}
            for i in rows.tolist():
                c = cols[i]
                owner = buf[c[C.COL_OWNER_OFF]:c[C.COL_OWNER_OFF] + c[C.COL_OWNER_LEN]]
                gid = memo.get(owner)
                if gid is None:
                    gid = memo[owner] = self._pool.voter_gid(owner)
                gids[i] = gid
        return gids

    def _wire_admit_health(
        self, buf, cols, scopes, scope_idx, slots, offsets, statuses,
        admit_timeout, now,
    ) -> None:
        """Post-apply health flush for the wire path: batched admission
        counts for accepted rows (the object path's ``note_admitted``)
        plus the equivocation probe over duplicate-shaped rejections —
        a differing vote_hash from an owner the session already tallied
        becomes a retained evidence pair, with the prior vote recovered
        from the session's scalar votes or its retained wire chunks.

        Whether the retained chunks hold a vote by the row's owner is
        answered from the record's chain continuity state, not by
        decoding them: on a ``wire_only`` record whose sync stamp is
        current, ``wire_seen`` holds every owner of the accepted chain
        (the dangling guard resyncs it, :meth:`_wire_track_chain` folds
        in each frame's accepts), so an owner outside it has no prior
        vote and its row is done — the common late first-time voter on
        a decided session decodes nothing. The chain is still decoded
        (once per growth) and walked for an owner inside ``wire_seen``
        (a redelivery or a real equivocation), on a record the legacy
        permissive path retained into, and under a stale stamp."""
        if not self._health_live:
            return
        from ..bridge import columnar as C

        ok = statuses == int(StatusCode.OK)
        if ok.any():
            admit_counts: dict[bytes, int] = {}
            for row in np.nonzero(ok)[0].tolist():
                c = cols[row]
                owner = buf[c[C.COL_OWNER_OFF]:c[C.COL_OWNER_OFF] + c[C.COL_OWNER_LEN]]
                admit_counts[owner] = admit_counts.get(owner, 0) + 1
            self.health.note_admitted(
                admit_counts, now, timeout_hint=admit_timeout
            )
        cand = statuses == self._EQUIVOCATION_PROBE_CODES[0]
        for code in self._EQUIVOCATION_PROBE_CODES[1:]:
            cand |= statuses == code
        probe_rows = np.nonzero(cand)[0].tolist()
        fallbacks = 0
        for row in probe_rows:
            record = self._records[int(slots[row])]
            c = cols[row]
            owner = buf[c[C.COL_OWNER_OFF]:c[C.COL_OWNER_OFF] + c[C.COL_OWNER_LEN]]
            prior = record.votes.get(owner)
            if prior is None and record.retained_wire and not (
                record.wire_only
                and record.wire_sync
                == (len(record.retained_wire), len(record.scalar_seqs))
                and owner not in record.wire_seen
            ):
                fallbacks += 1
                prior = next(
                    (
                        v
                        for _seq, chunk in self._decoded_retained(record)
                        for v in chunk
                        if v.vote_owner == owner
                    ),
                    None,
                )
            if prior is None:
                continue
            vote_hash = buf[c[C.COL_HASH_OFF]:c[C.COL_HASH_OFF] + c[C.COL_HASH_LEN]]
            if prior.vote_hash != vote_hash:
                self.health.note_equivocation(
                    scopes[int(scope_idx[row])],
                    int(c[C.COL_PID]),
                    prior.encode(),
                    buf[int(offsets[row]):int(offsets[row + 1])],
                    owner,
                    now,
                )
        self._m_probe_rows.inc(len(probe_rows))
        self._m_probe_fallbacks.inc(fallbacks)
        self.tracer.count("engine.equivocation_probe_rows", len(probe_rows))
        self.tracer.count("engine.equivocation_probe_fallbacks", fallbacks)

    def _columnar_apply(
        self,
        slots: np.ndarray,
        found: np.ndarray,
        voter_gids: np.ndarray,
        values: np.ndarray,
        now: int,
        max_depth: int,
        statuses: np.ndarray,
    ) -> np.ndarray:
        """Slot-resolved columnar pipeline shared by the single- and
        multi-scope entry points: gid/locality filters, host-spill tallies,
        lane resolution, bounded-depth pipelined device dispatches, round
        bookkeeping, and event emission. Three per-batch spans tile it:
        ``engine.plan_dispatch``, ``engine.device_ingest`` and
        ``engine.post_apply``."""
        with observed_span(self.tracer, "engine.plan_dispatch", self._m_plan):
            plan = self._columnar_plan(
                slots, found, voter_gids, values, now, max_depth, statuses
            )
        if plan is None:
            return statuses
        pendings, orig_of, order, sel, uniq, grp_sorted = plan
        with observed_span(
            self.tracer,
            "engine.device_ingest",
            self._m_device,
            votes=int(len(order)),
        ):
            results = self._pool.complete_all(pendings)
        with observed_span(self.tracer, "engine.post_apply", self._m_post_apply):
            self._columnar_bookkeeping(
                results, orig_of, order, sel, uniq, grp_sorted, now, statuses
            )
        return statuses

    def _columnar_plan(
        self,
        slots: np.ndarray,
        found: np.ndarray,
        voter_gids: np.ndarray,
        values: np.ndarray,
        now: int,
        max_depth: int,
        statuses: np.ndarray,
    ):
        """Dispatch planning for :meth:`_columnar_apply`: the filters and
        host-spill tallies (their statuses written into ``statuses``), the
        slot sort, lanes and depth segments, and the device calls enqueued.
        Returns ``(pendings, orig_of, order, sel, uniq, grp_sorted)``, or
        None when nothing is dispatched."""
        # Gids must be LIVE current-generation identities (voter_gid):
        # out-of-range, freed, and stale-generation ids (held across a
        # release, even after the index was recycled to a new owner) all get
        # a typed per-row status on BOTH substrates — previously the spill
        # path raised IndexError mid-batch while the device path silently
        # accepted any integer as a fresh voter.
        if self._multihost:
            # Misrouted rows (device slots another process owns) report the
            # session as not found on this host; the relay routes by
            # is_local(). Host-spilled rows (slots < 0) are replicated
            # control-plane state and apply everywhere. This runs BEFORE the
            # gid check: a misrouted voter is typically not interned here,
            # and the relay must see the routing status, not an identity one.
            lo, hi = self._pool.local_slots()
            non_local = found & (slots >= 0) & ((slots < lo) | (slots >= hi))
            if non_local.any():
                statuses[non_local] = int(StatusCode.SESSION_NOT_FOUND)
                found = found & ~non_local

        bad_gid = ~self._pool.gids_live(voter_gids)
        if bad_gid.any():
            statuses[found & bad_gid] = int(StatusCode.EMPTY_VOTE_OWNER)
            found = found & ~bad_gid

        # Host-spilled sessions (negative slots): rare scalar fallback,
        # applied tally-only — fabricating unsigned Vote objects here would
        # poison the session's exportable chain (advisor r2 medium).
        wall = time.monotonic()
        host_rows = np.nonzero(found & (slots < 0))[0]
        for i in host_rows:
            slot = int(slots[i])
            record = self._records[slot]
            owner = self._pool.owner_of_gid(int(voter_gids[i]))
            was_active = record.session.state.is_active
            code, event = self._host_add_tally(
                record, owner, bool(values[i]), now
            )
            statuses[i] = code
            if code == int(StatusCode.OK):
                record.last_activity = now
                self._timelines.voted(slot, now, wall)
                self._m_votes_accepted.inc()
            self.tracer.count(
                "engine.votes_accepted", int(code == int(StatusCode.OK))
            )
            if was_active and not record.session.state.is_active:
                # Ownership-gated like events: host-spilled sessions are
                # replicated fleet-wide, decision metrics must not be.
                owned = self._owns_slot(slot)
                self._timelines.decided(
                    slot,
                    _OUTCOME_OF_STATE[state_code_of(record.session.state)],
                    now,
                    wall,
                    observe=owned,
                )
                if owned:
                    self._m_decisions.inc()
            self.tracer.count(
                "engine.transitions",
                int(was_active and not record.session.state.is_active),
            )
            if event is not None and self._owns_slot(slot):
                self._emit(record.scope, event)

        dev_mask = found & (slots >= 0)
        # Identity fast path: when EVERY row reaches the device (the
        # streaming steady state — no unknown sessions, no stale gids, no
        # spills), skip materializing the row-index array and the gathers
        # through it; ``sel`` below is then just ``order``.
        dev_rows = (
            None
            if len(dev_mask) and dev_mask.all()
            else np.nonzero(dev_mask)[0]
        )

        # ── Fused sorted-domain pipeline ───────────────────────────────
        # ONE stable slot-sort of the batch; grouping, lane assignment,
        # depth segmentation, and round bookkeeping all derive from the
        # sorted domain. (Previously each stage re-sorted: lanes_for_batch
        # unique+lexsort, group_batch argsort, one more argsort per depth
        # segment — ~3x the host time on multi-million-row batches.)
        def _group(s_sorted: np.ndarray):
            b = len(s_sorted)
            is_start = np.empty(b, bool)
            is_start[0] = True
            np.not_equal(s_sorted[1:], s_sorted[:-1], out=is_start[1:])
            starts_idx = np.nonzero(is_start)[0]
            grp = np.cumsum(is_start) - 1
            col = np.arange(b) - starts_idx[grp]
            counts = np.diff(np.append(starts_idx, b))
            return s_sorted[starts_idx], starts_idx, grp, col, counts

        order = np.empty(0, np.int64)
        sel = order  # statuses-row index per sorted item (= dev_rows[order])
        lanes_sorted = np.empty(0, np.int32)
        vals_sorted = np.empty(0, bool)
        uniq = starts_idx = grp_sorted = col_sorted = counts = None
        fast_lanes = False
        if dev_rows is None or dev_rows.size:
            dslots = slots if dev_rows is None else slots[dev_rows]
            dgids = voter_gids if dev_rows is None else voter_gids[dev_rows]
            # Grouped-stream fast path: a proposal-major batch (each slot's
            # rows contiguous, checked as "no slot starts two runs") is
            # already a valid sorted-domain order — the O(B log B) argsort
            # and its gathers vanish. Only probed when runs are few (the
            # run-start values' unique() would itself be a sort otherwise).
            ordered = False
            if len(dslots) > 1:
                run_starts = np.empty(len(dslots), bool)
                run_starts[0] = True
                np.not_equal(dslots[1:], dslots[:-1], out=run_starts[1:])
                n_runs = int(run_starts.sum())
                if n_runs * 4 <= len(dslots):
                    start_vals = dslots[run_starts]
                    ordered = len(np.unique(start_vals)) == n_runs
            else:
                ordered = True
            if ordered:
                order = np.arange(len(dslots), dtype=np.int64)
                sel = order if dev_rows is None else dev_rows
                s_sorted = dslots
            else:
                order = np.argsort(dslots, kind="stable")
                sel = order if dev_rows is None else dev_rows[order]
                s_sorted = dslots[order]
            uniq, starts_idx, grp_sorted, col_sorted, counts = _group(s_sorted)
            # ordered: dgids is already in sorted-domain order — masking it
            # avoids re-gathering what's in hand.
            gid_idx_sorted = (
                dgids & 0xFFFFFFFF if ordered else voter_gids[sel] & 0xFFFFFFFF
            )
            lanes_sorted = self._pool.fresh_lanes_grouped(
                s_sorted, gid_idx_sorted, col_sorted, uniq, counts
            )
            fast_lanes = lanes_sorted is not None
            if lanes_sorted is None:
                # General path (pre-voted slots or an in-batch duplicate
                # voter); assume_live: the gids_live gate above ran.
                lanes_sorted = self._pool.lanes_for_batch(
                    dslots, dgids, assume_live=True
                )[order]
            no_lane = lanes_sorted < 0
            if no_lane.any():
                statuses[sel[no_lane]] = int(
                    StatusCode.VOTER_CAPACITY_EXCEEDED
                )
                keep = ~no_lane
                order = order[keep]
                sel = sel[keep]
                s_sorted = s_sorted[keep]
                lanes_sorted = lanes_sorted[keep]
                if len(order):
                    uniq, starts_idx, grp_sorted, col_sorted, counts = _group(
                        s_sorted
                    )
            vals_sorted = (
                values
                if ordered and dev_rows is None and len(sel) == len(values)
                else values[sel]
            )

        # Dispatch plan. Preferred: ONE closed-form (scan-free) dispatch for
        # the whole batch — valid exactly when the fast lane path ran (fresh
        # slots, no duplicate voters) and every touched slot is still ACTIVE
        # (rare non-ACTIVE fresh slots: empty sessions decided by timeout).
        # The grid is [S, depth]-padded, so a cell-budget guard falls back
        # to the segmented scan when padding would blow up (one slot with a
        # huge chain amid many shallow ones). Fallback: bounded-depth scan
        # segmentation — in the sorted domain each slot's items are
        # contiguous and arrival-ordered, so segment k (votes
        # [k*D, (k+1)*D) of every slot) is a repeat/arange gather with no
        # per-segment re-sort.
        segs: list[tuple] = []  # (uniq_k, rows_k, cols_k, depth_k, idx_k, fresh)
        use_fresh = (
            fast_lanes
            and len(order) > 0
            and self._pool.fresh_ingest_viable(
                uniq, int(counts.max()), len(order)
            )
        )
        fleet_fresh = False
        if self._multihost:
            # Fleet agreement on the dispatch PLAN, not just the count: the
            # fresh and scan kernels are different global programs, so the
            # path is taken only when EVERY process votes yes (an empty
            # local batch votes yes if its pool supports the kernel — it
            # then dispatches one empty fresh call to hold the collective
            # cadence), AND the fleet-max grid shapes — which the dispatch
            # pads every process to — fit the cell budget.
            from jax.experimental import multihost_utils

            fresh_ok = (use_fresh or len(order) == 0) and getattr(
                self._pool, "supports_fresh_ingest", False
            )
            plan = np.array(
                [
                    1 if fresh_ok else 0,
                    len(uniq) if len(order) else 0,
                    int(counts.max()) if len(order) else 0,
                ],
                np.int64,
            )
            agreed_plan = multihost_utils.process_allgather(plan)
            use_fresh = bool(
                np.min(agreed_plan[..., 0])
            ) and self._pool.fresh_grid_within_budget(
                int(np.max(agreed_plan[..., 1])),
                int(np.max(agreed_plan[..., 2])),
            )
            fleet_fresh = use_fresh
            if use_fresh and len(order) == 0:
                empty = np.empty(0, np.int64)
                segs.append((empty, empty, empty, 0, empty, True))
        if use_fresh and len(order) > 0:
            self.tracer.count("engine.fresh_dispatches")
            segs.append(
                (
                    uniq,
                    grp_sorted,
                    col_sorted,
                    int(counts.max()),
                    np.arange(len(order), dtype=np.int64),
                    True,
                )
            )
        elif len(order):
            depth = int(counts.max())
            if depth > max_depth:
                d = max_depth
                for k in range(-(-depth // d)):
                    seg_mask = counts > k * d
                    g_starts = starts_idx[seg_mask] + k * d
                    g_lens = np.minimum(counts[seg_mask] - k * d, d)
                    m = int(g_lens.sum())
                    off = np.zeros(len(g_lens) + 1, np.int64)
                    np.cumsum(g_lens, out=off[1:])
                    local = np.arange(m, dtype=np.int64) - np.repeat(
                        off[:-1], g_lens
                    )
                    idx_k = np.repeat(g_starts, g_lens) + local
                    rows_k = np.repeat(
                        np.arange(int(seg_mask.sum()), dtype=np.int64), g_lens
                    )
                    # Uniform depth d (not g_lens.max()): a shallower final
                    # segment would give its output a different shape,
                    # splitting complete_all's single stacked readback into
                    # two transfers. Pad columns are valid=0, inert.
                    segs.append(
                        (uniq[seg_mask], rows_k, local, d, idx_k, False)
                    )
            else:
                segs.append(
                    (
                        uniq,
                        grp_sorted,
                        col_sorted,
                        depth,
                        np.arange(len(order), dtype=np.int64),
                        False,
                    )
                )
        if self._multihost and not fleet_fresh:
            # Collective cadence for the scan plan: every process must
            # issue the same number of dispatches this call, empty ones
            # included. (The fresh plan is exactly one dispatch per process
            # by construction, so it needs no second collective.)
            from jax.experimental import multihost_utils

            agreed = multihost_utils.process_allgather(
                np.array([len(segs)], np.int64)
            )
            empty = np.empty(0, np.int64)
            for _ in range(int(np.max(agreed)) - len(segs)):
                segs.append((empty, empty, empty, 0, empty, False))
        if not segs:
            return None

        pendings = []
        orig_of = []  # statuses rows per pending, in dispatch item order
        for uniq_k, rows_k, cols_k, depth_k, idx_k, fresh_k in segs:
            pendings.append(
                self._pool.ingest_async_grouped(
                    uniq_k,
                    rows_k,
                    cols_k,
                    depth_k,
                    lanes_sorted[idx_k],
                    vals_sorted[idx_k],
                    now,
                    fresh=fresh_k,
                )
            )
            orig_of.append(sel[idx_k])
        return pendings, orig_of, order, sel, uniq, grp_sorted

    def _columnar_bookkeeping(
        self,
        results: list,
        orig_of: list,
        order: np.ndarray,
        sel: np.ndarray,
        uniq,
        grp_sorted,
        now: int,
        statuses: np.ndarray,
    ) -> None:
        """Post-apply for :meth:`_columnar_apply`: the dispatches' statuses
        into ``statuses``, decision timelines, counters, rounds and
        last-activity per touched slot, and the events."""
        wall = time.monotonic()
        accepted = 0
        reached_transitions: list[tuple[int, int]] = []
        n_transitions = 0
        for orig_rows, (seg_statuses, transitions) in zip(orig_of, results):
            statuses[orig_rows] = seg_statuses
            accepted += int(np.sum(seg_statuses == int(StatusCode.OK)))
            n_transitions += len(transitions)
            for slot, new_state in transitions:
                if new_state in (STATE_REACHED_YES, STATE_REACHED_NO):
                    reached_transitions.append((slot, new_state))
                outcome = _OUTCOME_OF_STATE.get(new_state)
                if outcome is not None:
                    self._timelines.decided(slot, outcome, now, wall)
        self.tracer.count("engine.votes_accepted", accepted)
        self.tracer.count("engine.transitions", n_transitions)
        self._m_votes_accepted.inc(accepted)
        self._m_decisions.inc(n_transitions)

        # Round + late-vote bookkeeping per touched slot, via bincount over
        # the sorted-domain group index (no re-sort; totals are
        # order-independent).
        if len(orig_of) == 1 and len(orig_of[0]) == len(order):
            # Single dispatch covering the whole sorted domain (the fresh
            # fast path): its output IS the sorted-domain statuses — skip
            # the O(B) re-gather through statuses.
            sorted_statuses = results[0][0]
        else:
            sorted_statuses = (
                statuses[sel] if len(order) else np.empty(0, np.int32)
            )
        if len(order):
            ok_m = sorted_statuses == int(StatusCode.OK)
            if ok_m.any():
                cnt = np.bincount(grp_sorted[ok_m], minlength=len(uniq))
                for g in np.nonzero(cnt)[0].tolist():
                    slot = int(uniq[g])
                    record = self._records[slot]
                    record.bump_round(int(cnt[g]))
                    record.last_activity = now
                    self._timelines.voted(slot, now, wall)

        # Events: one ConsensusReached per deciding transition plus one per
        # late (ALREADY_REACHED) vote — same per-session counts as the
        # scalar path; cross-session order is per-slot grouped.
        for slot, new_state in reached_transitions:
            record = self._records[slot]
            self._emit(
                record.scope,
                ConsensusReached(
                    proposal_id=record.proposal.proposal_id,
                    result=new_state == STATE_REACHED_YES,
                    timestamp=now,
                ),
            )
        if len(order):
            ar_m = sorted_statuses == int(StatusCode.ALREADY_REACHED)
            if ar_m.any():
                cnt = np.bincount(grp_sorted[ar_m], minlength=len(uniq))
                for g in np.nonzero(cnt)[0].tolist():
                    slot = int(uniq[g])
                    record = self._records[slot]
                    state = self._pool.state_of(slot)
                    event = ConsensusReached(
                        proposal_id=record.proposal.proposal_id,
                        result=state == STATE_REACHED_YES,
                        timestamp=now,
                    )
                    for _ in range(int(cnt[g])):
                        self._emit(record.scope, event)

    def _drop_pid_cache(self, scope: Scope) -> None:
        """Invalidate pid-resolution caches after a membership change in
        ``scope`` (register/evict/delete). The fused multi-scope cache is
        cleared outright — its tuples may span any scopes."""
        self._pid_tables.pop(scope, None)
        self._pid_hashes.pop(scope, None)
        self._fused_pid_cache.clear()

    def _fused_pid_lookup(self, scopes: list) -> "_PidLookup | None":
        """One composite-key hash for a whole multi-scope resolution:
        key = scope_ordinal << 32 | pid. Registered pids always fit u32
        (generate_id / batch draw / wire decode all mask to 32 bits), so
        the composite is injective; if a table somehow holds a wider pid,
        returns None and the caller falls back to per-scope probing.
        One build pass + one probe pass replaces len(scopes) of each —
        at the 256-scope churn shape that is ~100ms/wave of numpy
        fixed-overhead eliminated."""
        cache_key = tuple(scopes)
        cached = self._fused_pid_cache.get(cache_key)
        if cached is not None:
            return cached
        key_parts: list[np.ndarray] = []
        val_parts: list[np.ndarray] = []
        for k, scope in enumerate(scopes):
            pids, slot_arr = self._pid_table(scope)
            if len(pids) and (
                int(pids.min()) < 0 or (int(pids.max()) >> 32) != 0
            ):
                return None
            key_parts.append(pids | (np.int64(k) << np.int64(32)))
            val_parts.append(slot_arr)
        lookup = _PidLookup(
            np.concatenate(key_parts) if key_parts else np.empty(0, np.int64),
            np.concatenate(val_parts) if val_parts else np.empty(0, np.int64),
        )
        if len(self._fused_pid_cache) >= 8:  # bound distinct tuples per epoch
            self._fused_pid_cache.clear()
        self._fused_pid_cache[cache_key] = lookup
        return lookup

    def _pid_lookup(self, scope: Scope) -> "_PidLookup":
        """Vectorized pid -> slot hash for one scope (lazily rebuilt with
        the sorted table). Columnar resolution uses this instead of
        searchsorted: numpy's searchsorted walks O(log P) scalar probes per
        row (~70 ms for the 655k-row config-3 batch), while the
        open-addressing probe loop is ~1.3 vectorized gathers per row."""
        lookup = self._pid_hashes.get(scope)
        if lookup is None:
            pids_sorted, slots_sorted = self._pid_table(scope)
            lookup = _PidLookup(pids_sorted, slots_sorted)
            self._pid_hashes[scope] = lookup
        return lookup

    def _pid_table(self, scope: Scope) -> tuple[np.ndarray, np.ndarray]:
        """(proposal_ids, slots) membership arrays for one scope — the
        vectorized replacement for per-vote dict lookups; rebuilt lazily
        after any membership change. Unordered: both consumers
        (_pid_lookup's hash build, _draw_unique_pids' np.isin) are
        order-independent, so the old O(P log P) sort was dead weight."""
        table = self._pid_tables.get(scope)
        if table is None:
            scope_slots = self._scopes.get(scope, [])
            pids = np.fromiter(
                (
                    self._records[s].proposal.proposal_id
                    for s in scope_slots
                ),
                np.int64,
                len(scope_slots),
            )
            slot_arr = np.fromiter(scope_slots, np.int64, len(scope_slots))
            table = (pids, slot_arr)
            self._pid_tables[scope] = table
        return table

    def _host_add_vote(
        self, record: SessionRecord[Scope], vote: Vote, now: int
    ) -> tuple[int, ConsensusEvent | None]:
        """Apply one validated vote to a host-spilled session, mapping scalar
        outcomes to the same status codes the device path produces (parity:
        the scalar session IS the oracle the kernels are fuzzed against).
        Returns (status code, event-to-emit-or-None); the caller queues the
        event so emission order follows per-vote arrival order even when a
        batch mixes substrates."""
        code, event = self._host_apply(record, lambda s: s.add_vote(vote, now), now)
        if code == int(StatusCode.OK):
            # add_vote appended to the shared proposal's vote list.
            record.scalar_seqs.append(record.next_arrival_seq())
        return code, event

    def _host_add_tally(
        self, record: SessionRecord[Scope], owner: bytes, value: bool, now: int
    ) -> tuple[int, ConsensusEvent | None]:
        """Columnar counterpart of _host_add_vote: apply one tally to a
        host-spilled session (session.add_tally — no Vote object is
        fabricated, so the session's exportable chain stays valid)."""
        return self._host_apply(
            record, lambda s: s.add_tally(owner, value, now), now
        )

    def _host_apply(
        self, record: SessionRecord[Scope], mutate, now: int
    ) -> tuple[int, ConsensusEvent | None]:
        """Shared outcome mapping for host-spilled mutations: run the
        session mutation, translate scalar outcomes to the device path's
        status codes, and surface the transition event (if any) for the
        caller to queue in arrival order."""
        session = record.session
        already = session.state.is_reached
        try:
            transition = mutate(session)
        except ConsensusError as exc:
            return int(exc.code), None
        event = None
        if transition.is_reached:
            event = ConsensusReached(
                proposal_id=record.proposal.proposal_id,
                result=transition.reached,
                timestamp=now,
            )
        return (
            int(StatusCode.ALREADY_REACHED) if already else int(StatusCode.OK),
            event,
        )

    def _host_timeout(self, record: SessionRecord[Scope], now: int) -> int:
        """Timeout decision for a host-spilled session; returns the new dense
        state code (same contract as pool.timeout rows). Mirrors the scalar
        service (reference: src/service.rs:323-373): idempotent for decided
        sessions, Failed sessions stay Failed."""
        session = record.session
        if session.state.is_active:
            result = session.decide_now(True)
            session.state = (
                ConsensusState.reached(result)
                if result is not None
                else ConsensusState.failed()
            )
        return state_code_of(session.state)

    # ── Timeouts ───────────────────────────────────────────────────────

    def handle_consensus_timeout(self, scope: Scope, proposal_id: int, now: int) -> bool:
        """App-driven timeout for one session
        (reference: src/service.rs:323-373). Idempotent for decided sessions;
        raises InsufficientVotesAtTimeout (after emitting ConsensusFailed)
        when undecidable."""
        slot = self._index.get((scope, proposal_id))
        if slot is None:
            # A demoted (idle) session can still be timed out by the
            # embedder: page it back in and fire as if it never left.
            slot = self._tier_lookup_promote(scope, proposal_id)
            if slot is None:
                raise SessionNotFound()
        # Timeout calls carry the embedder's clock even when vote traffic
        # has stopped — exactly when the liveness watchdog needs a
        # current tick to measure silence against.
        self.health.tick(now)
        record = self._records[slot]
        owned = self._owns_slot(slot)
        was_active = self._state_code(record) == STATE_ACTIVE
        if was_active:
            # A fired timeout is the session's deciding activity: the GC
            # TTL for decided sessions measures from here.
            record.last_activity = now
        if record.session is not None:
            new_state = self._host_timeout(record, now)
        else:
            transitions = self._pool.timeout([slot])
            if transitions:
                [(_, new_state)] = transitions
            else:
                # Multi-host collective: this process joined the dispatch
                # but another process owns the slot; pool.timeout synced the
                # state mirror, so the result is readable (and the owner
                # emitted the event).
                new_state = self._pool.state_of(slot)
        if was_active and owned:
            # Only count timeouts that actually fired, on the owning
            # process only: the call is idempotent for already-decided
            # sessions (polls must not inflate the counter), and in a
            # multi-host fleet every process runs this collective — a
            # metrics sum across processes must report one firing.
            self._m_timeouts.inc()
        if was_active and self._health_live:
            # Actually-fired timeout: back off the scope's learned
            # timeout. Ownership-independent — each process keeps its own
            # advisory book, and identical collectives keep them aligned.
            self._adaptive.on_timeout(scope, self._scope_configs.get(scope))
        outcome = _OUTCOME_OF_STATE.get(new_state)
        if outcome is not None:
            # Idempotent for sessions that already decided by votes (the
            # store ignores a second outcome); the latency observation is
            # ownership-gated like events, the timeline stamp is not.
            self._timelines.decided(
                slot, outcome, now, time.monotonic(), by_timeout=True,
                observe=owned,
            )
            if trace_store.enabled and was_active and record.trace is not None:
                trace_store.instant(
                    "consensus.timeout_decided",
                    record.trace,
                    peer=self._trace_peer,
                    attrs={"outcome": outcome},
                )
        if new_state in (STATE_REACHED_YES, STATE_REACHED_NO):
            result = new_state == STATE_REACHED_YES
            if owned:
                self._emit(
                    scope,
                    ConsensusReached(
                        proposal_id=proposal_id, result=result, timestamp=now
                    ),
                )
            return result
        if owned:
            self._emit(
                scope, ConsensusFailedEvent(proposal_id=proposal_id, timestamp=now)
            )
        raise InsufficientVotesAtTimeout()

    def sweep_timeouts(
        self, now: int, _gc_sink: "list | None" = None
    ) -> list[tuple[Scope, int, bool | None]]:
        """Engine-level convenience absent from the reference (its embedder
        schedules per-proposal timers): fire the timeout decision for every
        still-undecided session whose expiration has passed, in one device
        dispatch. Returns (scope, proposal_id, result-or-None) per swept
        session and emits the same events as per-session timeouts. Only
        ACTIVE sessions are swept: a FAILED session's tallies are frozen (the
        ingest kernel rejects votes on non-ACTIVE slots) so re-sweeping it
        would deterministically re-fail and re-emit forever.

        Multi-host: collective (same cadence everywhere). The state mirror
        is synced first so every process computes the IDENTICAL expired set
        — remote slots' mirrored states lag between collectives by design
        (zero DCN on the ingest path)."""
        if self._multihost:
            self._pool.sync_states()
        # Expired idle sessions sleeping in the demoted tier must fire
        # their timeouts exactly like live ones: page them in first.
        self._promote_expired_tier(now)
        expired: list[int] = []
        host_expired: list[int] = []
        for slot, record in self._records.items():
            if record.session is not None:
                if (
                    record.session.state.is_active
                    and record.proposal.expiration_timestamp <= now
                ):
                    host_expired.append(slot)
            elif self._pool.state_of(slot) == STATE_ACTIVE:
                if self._pool.meta(slot).expiry <= now:
                    expired.append(slot)
        self.tracer.count("engine.timeout_sweeps")
        self.tracer.count("engine.timeouts_fired", len(expired) + len(host_expired))
        self.health.tick(now)  # watchdog clock advances with the sweep cadence
        if expired or host_expired:
            flight_recorder.record(
                "engine.sweep", fired=len(expired) + len(host_expired)
            )
        wall = time.monotonic()
        out: list[tuple[Scope, int, bool | None]] = []
        # pool.timeout is collective on a multi-host pool and returns only
        # this process's slots; host-spilled sessions advance identically on
        # every process but their events/results belong to process 0.
        swept = [(slot, st, True) for slot, st in self._pool.timeout(expired)] + [
            (
                slot,
                self._host_timeout(self._records[slot], now),
                self._owns_slot(slot),
            )
            for slot in host_expired
        ]
        # Fired count and latency observations are ownership-gated like
        # events: a multi-host fleet's metrics sum must report each swept
        # session once, not once per process.
        self._m_timeouts.inc(sum(1 for _, _, owned in swept if owned))
        for slot, new_state, owned in swept:
            # The fired timeout is the session's deciding activity (GC
            # TTLs measure from it); ownership-independent like the
            # timeline stamp.
            self._records[slot].last_activity = now
            if self._health_live:
                swept_scope = self._records[slot].scope
                self._adaptive.on_timeout(
                    swept_scope, self._scope_configs.get(swept_scope)
                )
            outcome = _OUTCOME_OF_STATE.get(new_state)
            if outcome is not None:
                self._timelines.decided(
                    slot, outcome, now, wall, by_timeout=True, observe=owned
                )
                if trace_store.enabled:
                    tctx = self._records[slot].trace
                    if tctx is not None:
                        trace_store.instant(
                            "consensus.timeout_decided",
                            tctx,
                            peer=self._trace_peer,
                            attrs={"outcome": outcome},
                        )
            if not owned:
                continue
            record = self._records[slot]
            pid = record.proposal.proposal_id
            if new_state in (STATE_REACHED_YES, STATE_REACHED_NO):
                result = new_state == STATE_REACHED_YES
                self._emit(
                    record.scope,
                    ConsensusReached(proposal_id=pid, result=result, timestamp=now),
                )
                out.append((record.scope, pid, result))
            else:
                self._emit(
                    record.scope,
                    ConsensusFailedEvent(proposal_id=pid, timestamp=now),
                )
                out.append((record.scope, pid, None))
        # The engine-wide tier cadence rides the sweep the embedder
        # already drives: demote idle sessions, GC decided ones past
        # their per-scope TTLs (no-op without ScopeConfig tier knobs).
        self.lifecycle_sweep(now, _gc_sink=_gc_sink)
        return out

    # ── Queries (reference: src/storage.rs:112-180 derived helpers) ────

    def _decoded_retained(
        self, record: SessionRecord[Scope]
    ) -> list[tuple[int, list[Vote]]]:
        """Decode a record's retained wire bytes once per growth, keeping
        each chunk's arrival seq; exports clone the cached Vote objects so
        callers can't mutate the cache."""
        n = len(record.retained_wire)
        if n == 0:
            return []
        if record.retained_cache is None or record.retained_cache[0] != n:
            chunks: list[tuple[int, list[Vote]]] = []
            for seq, data, offs in record.retained_wire:
                chunks.append(
                    (
                        seq,
                        [
                            Vote.decode(data[offs[k] : offs[k + 1]])
                            for k in range(len(offs) - 1)
                        ],
                    )
                )
            record.retained_cache = (n, chunks)
        return record.retained_cache[1]

    def _materialized_proposal(self, record: SessionRecord[Scope]) -> Proposal:
        """Export view of a record's proposal: retained columnar wire bytes
        (if any) are decoded and merged with the scalar-ingested votes in
        TRUE arrival order (per-record seq: one tick per scalar accept, one
        per retained chunk), so a session fed through both paths still
        re-gossips a chain-valid vote list."""
        proposal = record.proposal.clone()
        retained = self._decoded_retained(record)
        if retained:
            scalar = proposal.votes
            # Votes embedded at registration predate the arrival clock and
            # keep their leading position (seq -1, stable sort).
            n_pre = len(scalar) - len(record.scalar_seqs)
            items: list[tuple[int, list[Vote]]] = [
                (-1, scalar[:n_pre])
            ] if n_pre else []
            items.extend(
                (seq, [vote])
                for seq, vote in zip(record.scalar_seqs, scalar[n_pre:])
            )
            items.extend(
                (seq, [v.clone() for v in votes]) for seq, votes in retained
            )
            items.sort(key=lambda t: t[0])
            proposal.votes = [v for _, votes in items for v in votes]
        return proposal

    def get_proposal(self, scope: Scope, proposal_id: int) -> Proposal:
        return self._materialized_proposal(self._get_record(scope, proposal_id))

    def get_consensus_result(self, scope: Scope, proposal_id: int) -> bool | None:
        """None while active; raises ConsensusFailed for a failed session —
        the same contract as the storage derived helper
        (reference: src/storage.rs:112-126), so the framework's two front
        doors agree."""
        record = self._get_record(scope, proposal_id)
        state = self._state_code(record)
        if state == STATE_REACHED_YES:
            return True
        if state == STATE_REACHED_NO:
            return False
        if state == STATE_FAILED:
            raise ConsensusFailed()
        return None

    def _tier_sessions_where(self, scope: Scope, want_state: "int | None"):
        """Decode a scope's demoted sessions (``want_state`` filters on
        the stored snapshot state code; None = all) WITHOUT promoting —
        enumeration reads pass through the tier, only point reads and
        mutations page sessions back in."""
        entries = self._tier.get(scope)
        if not entries:
            return
        from ..sync.snapshot import decode_session_item

        for entry in entries.values():
            if want_state is not None and entry.state != want_state:
                continue
            _, session = decode_session_item(entry.item)
            yield entry, session

    def get_active_proposals(self, scope: Scope) -> list[Proposal]:
        out = [
            self._materialized_proposal(r)
            for r in self._scope_records(scope)
            if self._state_code(r) == STATE_ACTIVE
        ]
        out.extend(
            session.proposal
            for _, session in self._tier_sessions_where(scope, 0)
        )
        return out

    def get_reached_proposals(self, scope: Scope) -> list[tuple[Proposal, bool]]:
        out = []
        for r in self._scope_records(scope):
            state = self._state_code(r)
            if state in (STATE_REACHED_YES, STATE_REACHED_NO):
                out.append((self._materialized_proposal(r), state == STATE_REACHED_YES))
        out.extend(
            (session.proposal, bool(entry.result))
            for entry, session in self._tier_sessions_where(scope, 1)
        )
        return out

    def get_scope_stats(self, scope: Scope) -> ConsensusStats:
        """reference: src/service_stats.rs:32-59 (zeros for unknown scope).
        Demoted sessions count from their stored state metadata — no
        decode, no promotion."""
        stats = ConsensusStats()
        for r in self._scope_records(scope):
            stats.total_sessions += 1
            state = self._state_code(r)
            if state == STATE_ACTIVE:
                stats.active_sessions += 1
            elif state == STATE_FAILED:
                stats.failed_sessions += 1
            else:
                stats.consensus_reached += 1
        entries = self._tier.get(scope)
        if entries:
            for entry in entries.values():
                stats.total_sessions += 1
                if entry.state == 0:
                    stats.active_sessions += 1
                elif entry.state == 2:
                    stats.failed_sessions += 1
                else:
                    stats.consensus_reached += 1
        return stats

    def proposal_timeline(self, scope: Scope, proposal_id: int) -> dict | None:
        """Lifecycle timeline readout for one proposal: created /
        first_vote / quorum / decided logical timestamps, outcome
        (yes/no/failed + by_timeout), and the derived wall-clock latencies
        (``decision_latency_s`` is what feeds the
        ``hashgraph_decision_latency_seconds`` histogram). Falls back to
        the bounded finished-timeline ring for recently deleted/evicted
        sessions; None when the proposal was never seen (or aged out)."""
        slot = self._index.get((scope, proposal_id))
        if slot is not None:
            tl = self._timelines.get(slot)
            if tl is not None and tl.proposal_id == proposal_id:
                return tl.as_dict()
        tl = self._timelines.find(scope, proposal_id)
        return tl.as_dict() if tl is not None else None

    def trace_context_of(self, scope: Scope, proposal_id: int):
        """The distributed :class:`~hashgraph_tpu.obs.trace.TraceContext`
        bound to a live session (None when untracked/untraced). The bridge
        serializes this onto CREATE_PROPOSAL / CAST_VOTE responses so
        embedders can carry it to the peers they gossip to."""
        slot = self._index.get((scope, proposal_id))
        if slot is None:
            return None
        return self._records[slot].trace

    def explain_decision(self, scope: Scope, proposal_id: int) -> dict:
        """Decision provenance: one JSON-ready verdict answering *why and
        how* this proposal is in its current state.

        Reconstructs the accepted vote chain (chain order, per-peer
        contributions — columnar tallies included), the quorum arithmetic
        (``div_ceil(2n, 3)`` exact path / ``ceil(n·t)`` general path /
        n≤2 unanimity, with the observed yes/no/silent counts and an
        independent re-run of the decision kernel as a cross-check), the
        lifecycle timeline phases, and the bound distributed-trace
        identity. Raises SessionNotFound for unknown proposals; a
        :class:`~hashgraph_tpu.wal.DurableEngine` overlays the WAL LSN
        watermark. Exposed over the bridge as ``OP_EXPLAIN``
        (``BridgeClient.explain``)."""
        record = self._get_record(scope, proposal_id)
        session = self.export_session(scope, proposal_id)
        proposal = session.proposal
        n = proposal.expected_voters_count
        thr = session.config.consensus_threshold
        state = self._state_code(record)
        status = {
            STATE_ACTIVE: "active",
            STATE_FAILED: "failed",
            STATE_REACHED_YES: "reached",
            STATE_REACHED_NO: "reached",
        }[state]
        result = (
            state == STATE_REACHED_YES
            if state in (STATE_REACHED_YES, STATE_REACHED_NO)
            else None
        )
        timeline = self.proposal_timeline(scope, proposal_id)
        by_timeout = bool(timeline and timeline.get("by_timeout"))
        yes, total = session.tally_counts()
        if n <= 2:
            # Unanimity rule (reference: src/utils.rs:239-244).
            rule = "unanimity (n <= 2)"
            required = choice_required = n
        else:
            required = calculate_required_votes(n, thr)
            choice_required = calculate_threshold_based_value(n, thr)
            # EXACTLY the comparison calculate_threshold_based_value
            # makes, so the stated rule always names the path that
            # produced the numbers beside it.
            rule = (
                "div_ceil(2n, 3)"
                if abs(thr - _TWO_THIRDS) < _F64_EPSILON
                else f"ceil(n * {thr!r})"
            )
        # Independent re-run of the decision kernel over the reconstructed
        # session (the same decide_now the scalar substrate runs, so the
        # cross-check can never drift from the real semantics): must agree
        # with the recorded outcome for vote-decided sessions (None for
        # still-active / failed ones).
        recomputed = session.decide_now(by_timeout)
        chain = [
            {
                "position": i,
                "owner": v.vote_owner.hex(),
                "vote": v.vote,
                "vote_id": v.vote_id,
                "timestamp": v.timestamp,
                "parent_hash": v.parent_hash.hex(),
                "vote_hash": v.vote_hash.hex(),
            }
            for i, v in enumerate(proposal.votes)
        ]
        contributions = {
            v.vote_owner.hex(): {"vote": v.vote, "via": "vote"}
            for v in session.votes.values()
        }
        for owner, value in session.tallies.items():
            contributions[owner.hex()] = {"vote": value, "via": "tally"}
        trace = None
        if record.trace is not None:
            trace = {
                "traceparent": record.trace.to_traceparent(),
                "trace_id": record.trace.trace_id.hex(),
                "span_id": record.trace.span_id.hex(),
            }
        return {
            "scope": str(scope),
            "proposal_id": proposal.proposal_id,
            "status": status,
            "result": result,
            "by_timeout": by_timeout,
            "proposal": {
                "name": proposal.name,
                "owner": proposal.proposal_owner.hex(),
                "round": proposal.round,
                "created_at": record.created_at,
                "expiration_timestamp": proposal.expiration_timestamp,
                "liveness_criteria_yes": proposal.liveness_criteria_yes,
            },
            "quorum": {
                "expected_voters": n,
                "threshold": thr,
                "rule": rule,
                "required_votes": required,
                "required_choice_votes": choice_required,
                "yes": yes,
                "no": total - yes,
                "total": total,
                "silent": max(n - total, 0),
                "reached": status == "reached",
                "recomputed_result": recomputed,
            },
            "vote_chain": chain,
            "contributions": contributions,
            "timeline": timeline,
            "trace": trace,
        }

    def health_report(self, now: int | None = None) -> dict:
        """Consensus-health snapshot: per-peer scorecards (graded), the
        retained equivocation/fork evidence, liveness-watchdog state, and
        the firing alert rules — :meth:`HealthMonitor.snapshot` plus this
        engine's signer identity. ``now`` is the embedder's logical tick
        (default: the latest tick the monitor has seen — HTTP scrapes
        have no embedder clock). Exposed over the bridge as ``OP_HEALTH``
        (``BridgeClient.health``); a
        :class:`~hashgraph_tpu.wal.DurableEngine` overlays the WAL LSN
        watermark. Deliberately NOT engine-locked: the monitor has its
        own lock, so scrape threads never contend with ingest."""
        out = self.health.snapshot(now)
        out["identity"] = self._signer.identity().hex()
        return out

    def occupancy(self) -> dict:
        """Capacity snapshot: live sessions, device slots claimed vs pool
        capacity, and host-spilled sessions (negative synthetic ids hold
        no pool row). The same numbers the scrape-time gauges sample,
        exposed as one consistent read for fleet routers and capacity
        planners (parallel.fleet's per-shard breakdown)."""
        with self._lock:
            slots = list(self._records)
            tier_sessions = self._tier_count
            tier_bytes = self._tier_bytes
            demotions = self._tier_demotions
            promotions = self._tier_promotions
            gc = self._tier_gc
        device_used = sum(1 for s in slots if s >= 0)
        return {
            "live_sessions": len(slots),
            "device_slots_used": device_used,
            "host_spilled": len(slots) - device_used,
            "capacity": self._pool.capacity,
            "voter_capacity": self._pool.voter_capacity,
            # Demoted tier: population + serialized footprint, and this
            # engine's lifetime demote/promote/GC traffic.
            "tier_sessions": tier_sessions,
            "tier_bytes": tier_bytes,
            "tier_demotions_total": demotions,
            "tier_promotions_total": promotions,
            "tier_gc_total": gc,
        }

    def session_keys(self) -> "list[tuple[Scope, int]]":
        """Every tracked ``(scope, proposal_id)`` in one consistent read —
        the enumeration a gossip node needs to bootstrap its anti-entropy
        bookkeeping after installing state it did not ingest itself
        (catch-up, storage load). Demoted sessions are tracked sessions:
        their keys enumerate too (anti-entropy watermarks must cover
        them, or a peer would re-push state this engine already holds)."""
        with self._lock:
            keys = list(self._index.keys())
            for scope, entries in self._tier.items():
                keys.extend((scope, pid) for pid in entries)
            return keys

    def export_session(self, scope: Scope, proposal_id: int) -> ConsensusSession:
        """Materialise a scalar ConsensusSession from the pooled state —
        the bridge back to ConsensusStorage backends (checkpoint/interop).

        Pooled sessions read their columnar tallies back from the device
        (lane -> owner via the gid registry); rows whose verbatim wire bytes
        were retained export as real signed votes instead of tallies, so the
        re-gossip capability survives a save/load round-trip."""
        return self._export_record(self._get_record(scope, proposal_id))

    def _export_record(
        self, record: SessionRecord[Scope], row: "dict | None" = None
    ) -> ConsensusSession:
        """Body of :meth:`export_session` over an already-resolved record.
        ``row`` optionally injects the slot's device row (vote_mask /
        vote_val) pre-fetched by a batched ``pool.read_slots`` gather — the
        demotion path exports many sessions per device round-trip."""
        retained_votes = [
            vote for _, votes in self._decoded_retained(record) for vote in votes
        ]
        if record.session is not None:
            session = record.session.clone()
            if retained_votes:
                # The materialized proposal merges both paths' votes in
                # arrival order; the dict/tally bookkeeping follows.
                session.proposal = self._materialized_proposal(record)
                for vote in retained_votes:
                    # A retained signed vote supersedes its tally entry.
                    session.tallies.pop(vote.vote_owner, None)
                    if vote.vote_owner not in session.votes:
                        session.votes[vote.vote_owner] = vote.clone()
            return session
        votes = {k: v.clone() for k, v in record.votes.items()}
        tallies: dict[bytes, bool] = {}
        if row is None:
            row = self._pool.read_slot(record.slot)
        lane_owners = self._pool.lane_owners(record.slot)
        for lane in np.nonzero(row["vote_mask"])[0]:
            owner = lane_owners.get(int(lane))
            if owner is None or owner in votes:
                continue  # scalar votes already carry this participant
            tallies[owner] = bool(row["vote_val"][lane])
        for vote in retained_votes:
            tallies.pop(vote.vote_owner, None)
            votes.setdefault(vote.vote_owner, vote.clone())
        return ConsensusSession(
            # The materialized proposal embeds retained votes in chain
            # order, so re-gossip capability survives save -> load.
            proposal=self._materialized_proposal(record),
            state=_STATE_TO_SCALAR[self._pool.state_of(record.slot)],
            votes=votes,
            created_at=record.created_at,
            config=record.config,
            tallies=tallies,
        )

    # ── Checkpoint / resume (SURVEY §5: host storage is the source of
    #    truth; device tensors are a rebuildable cache) ─────────────────

    def save_to_storage(self, storage) -> int:
        """Persist every tracked session (and scope configs) into a
        ConsensusStorage backend — the reference's durability abstraction
        (src/storage.rs:18-22). Returns the number of sessions written.

        Demoted sessions are persisted too, decoded straight from their
        canonical tier bytes — snapshot builds and fingerprints therefore
        carry the identical session items whether a session is live or
        demoted (the codec round-trips byte-identically; the tier/untier'd
        fingerprint-equality property pins it)."""
        count = 0
        for scope, slots in self._scopes.items():
            for slot in slots:
                record = self._records[slot]
                storage.save_session(
                    scope, self.export_session(scope, record.proposal.proposal_id)
                )
                count += 1
        for scope in self._tier:
            for _, session in self._tier_sessions_where(scope, None):
                storage.save_session(scope, session)
                count += 1
        for scope, config in self._scope_configs.items():
            storage.set_scope_config(scope, config.clone())
        return count

    def load_from_storage(self, storage) -> int:
        """Rebuild pool state from a ConsensusStorage backend: every stored
        session is loaded into a fresh slot with its original created_at,
        tallies, lanes, and lifecycle state (no re-validation — storage is
        trusted, exactly as the reference trusts its own persisted sessions).
        Returns the number of sessions loaded."""
        count = 0
        scopes = storage.list_scopes() or []
        for scope in scopes:
            config = storage.get_scope_config(scope)
            if config is not None:
                self._scope_configs[scope] = config.clone()
            sessions = storage.list_scope_sessions(scope) or []
            for session in sorted(sessions, key=lambda s: s.created_at):
                if (scope, session.proposal.proposal_id) in self._index or (
                    self._tier_has(scope, session.proposal.proposal_id)
                ):
                    continue  # already tracked (idempotent restore)
                self._register_session(scope, session.clone(), session.created_at)
                count += 1
        return count

    def delete_scope(self, scope: Scope) -> None:
        """Drop every session and the config of a scope
        (reference: src/storage.rs:92 delete_scope semantics)."""
        self.delete_scopes([scope])

    def delete_scopes(self, scopes: "list[Scope]") -> None:
        """Batched delete_scope: ONE pool release dispatch (and one lane
        retirement pass) covers every scope's sessions — the teardown half
        of the config-5 churn shape (mirror of create_proposals_multi,
        which batches the registration half). Observable semantics are
        identical to calling delete_scope once per scope."""
        all_slots: list[int] = []
        for scope in scopes:
            slots = self._scopes.pop(scope, [])
            for slot in slots:
                record = self._records.pop(slot)
                del self._index[(scope, record.proposal.proposal_id)]
                self._timelines.forget(slot)
            # Host spills (slot < 0) have no pool slot to release.
            all_slots.extend(s for s in slots if s >= 0)
            self._scope_configs.pop(scope, None)
            self._drop_pid_cache(scope)
            # The demoted tier drops with the scope, like live sessions.
            entries = self._tier.pop(scope, None)
            if entries:
                self._tier_count -= len(entries)
                self._tier_bytes -= sum(len(e.item) for e in entries.values())
                for pid, entry in entries.items():
                    if entry.state == 0:
                        self._tier_active.pop((scope, pid), None)
                self._tier_pid_arrays.pop(scope, None)
            self._pinned_scopes.discard(scope)
            self._scope_seq.pop(scope, None)
        self._pool.release(all_slots)

    # ── Tiered session lifecycle (demote / demand-page / GC) ───────────
    #
    # The ARIES / Raft log-compaction frame (PAPERS.md): the WAL already
    # makes any in-memory representation a rebuildable cache, so a
    # decided/idle session can drop its device slot and host record and
    # live on as its canonical serialized bytes (the PR-8 snapshot item
    # format — the exact signed wire, so promotion re-registers without
    # re-signing and fingerprints hash the same items either way). Every
    # public surface reads through the tier: point reads and mutations
    # page the session back in, enumerations/stats/save_to_storage read
    # the tier without promoting — callers observe an untier'd engine.

    def _tier_has(self, scope: Scope, proposal_id: int) -> bool:
        entries = self._tier.get(scope)
        return entries is not None and proposal_id in entries

    def _tier_lookup_promote(self, scope: Scope, proposal_id: int) -> "int | None":
        """Slot of a demoted session after paging it back in; None when
        the session is not in the tier (the caller's miss is real)."""
        entries = self._tier.get(scope)
        if entries is None or proposal_id not in entries:
            return None
        return self._promote_key(scope, proposal_id)

    def demote_session(self, scope: Scope, proposal_id: int) -> bool:
        """Move one session out of its device slot / host record into the
        compact serialized tier. Idempotent: False when already demoted.
        Raises SessionNotFound for unknown sessions. The session stays
        fully addressable — any read or late vote transparently promotes
        it back (see the section comment)."""
        if self._multihost:
            raise RuntimeError(
                "session tiering is not supported on multi-host pools"
            )
        if self._tier_has(scope, proposal_id):
            return False
        slot = self._index.get((scope, proposal_id))
        if slot is None:
            raise SessionNotFound()
        self._demote_records(scope, [slot])
        return True

    # Pool lifecycle code -> (snapshot state code, result).
    _POOL_TO_SNAP = {
        STATE_ACTIVE: (0, False),
        STATE_REACHED_YES: (1, True),
        STATE_REACHED_NO: (1, False),
        STATE_FAILED: (2, False),
    }

    def _demote_records(self, scope: Scope, slots: "list[int]") -> int:
        """Batched demotion of live slots belonging to one scope: ONE
        device gather for every pooled slot's tally row, one pool release
        dispatch, one pid-cache drop. Plain pooled sessions (the churn
        steady state) encode field-direct — per-call memoized scope/config
        bytes, tallies straight off the gathered row, the live proposal's
        wire bytes — with no intermediate ConsensusSession; byte-identity
        with the session-object codec is pinned by the tier fingerprint
        property suite."""
        from ..sync.snapshot import (
            _STATE_CODE,
            encode_session_fields,
            encode_session_item,
        )
        from ..wal import format as F

        records = [self._records[s] for s in slots]
        rows: dict[int, dict] = {}
        pool_states: dict[int, int] = {}
        pooled = [r for r in records if r.session is None]
        if pooled:
            pooled_slots = [r.slot for r in pooled]
            batch = self._pool.read_slots(pooled_slots)
            states = self._pool.states_of(pooled_slots).tolist()
            masks = batch["vote_mask"]
            vals = batch["vote_val"]
            for k, r in enumerate(pooled):
                rows[r.slot] = {
                    "vote_mask": masks[k],
                    "vote_val": vals[k],
                }
                pool_states[r.slot] = states[k]
        entries = self._tier.setdefault(scope, {})
        scope_bytes = F.encode_scope(scope)
        cfg_bytes: dict[int, bytes] = {}  # id(config) -> canonical encode
        # Vote-free proposals sharing every field but the id (the churn
        # steady state: whole waves minted from one request shape) encode
        # via ONE cached (head, tail) split per shape + a per-item id
        # varint — Proposal.encode's nine-field walk was the single
        # biggest demotion cost.
        split_cache: dict[tuple, tuple[bytes, bytes]] = {}
        from ..wire import _U32_MASK as _PIDM
        from ..wire import _encode_uint_field
        for record in records:
            pid = record.proposal.proposal_id
            if record.session is None and not record.retained_wire:
                # Fast path: encode from the record's parts directly.
                state, result = self._POOL_TO_SNAP[pool_states[record.slot]]
                row = rows[record.slot]
                lane_owners = self._pool.lane_owners(record.slot)
                votes = record.votes
                tallies: dict[bytes, bool] = {}
                # Voter lanes are few (<= voter_capacity): a plain list
                # walk beats np.nonzero on tiny rows.
                val_row = row["vote_val"].tolist()
                for lane, on in enumerate(row["vote_mask"].tolist()):
                    if not on:
                        continue
                    owner = lane_owners.get(lane)
                    if owner is None or owner in votes:
                        continue
                    tallies[owner] = bool(val_row[lane])
                config_bytes = cfg_bytes.get(id(record.config))
                if config_bytes is None:
                    config_bytes = F.encode_consensus_config(record.config)
                    cfg_bytes[id(record.config)] = config_bytes
                p = record.proposal
                if not p.votes:
                    shape = (
                        p.name,
                        p.payload,
                        p.proposal_owner,
                        p.expected_voters_count,
                        p.round,
                        p.timestamp,
                        p.expiration_timestamp,
                        p.liveness_criteria_yes,
                    )
                    parts = split_cache.get(shape)
                    if parts is None:
                        parts = p.encode_split()
                        split_cache[shape] = parts
                    buf = bytearray(parts[0])
                    _encode_uint_field(buf, 12, p.proposal_id & _PIDM)
                    buf += parts[1]
                    proposal_wire = bytes(buf)
                else:
                    proposal_wire = p.encode()
                item = encode_session_fields(
                    scope_bytes,
                    state,
                    result,
                    record.created_at,
                    config_bytes,
                    tallies,
                    proposal_wire,
                )
            else:
                session = self._export_record(record, row=rows.get(record.slot))
                item = encode_session_item(scope, session)
                state = _STATE_CODE[session.state.kind]
                result = bool(session.state.result)
            entries[pid] = _TierEntry(
                item,
                state,
                result,
                record.created_at,
                record.seq,
                record.proposal.expiration_timestamp,
                record.last_activity,
            )
            self._tier_count += 1
            self._tier_bytes += len(item)
            if state == 0:
                # Idle-but-active: the timeout sweep must still find it.
                self._tier_active[(scope, pid)] = (
                    record.proposal.expiration_timestamp
                )
        self._drop_live_slots(scope, slots)
        self._tier_pid_arrays.pop(scope, None)
        n = len(records)
        self._tier_demotions += n
        self._m_tier_demotions.inc(n)
        self.tracer.count("engine.tier_demotions", n)
        return n

    def _promote_key(self, scope: Scope, proposal_id: int) -> "int | None":
        """Page one demoted session back in: decode the stored item bytes
        and re-register on the live substrate (device slot, or the
        host-spilled negative-slot path for sessions the pool geometry
        cannot hold — tally-carrying ones included). The session keeps its
        original created_at / LRU rank / idle clock, so demote→promote is
        invisible to eviction and TTL policies."""
        from ..sync.snapshot import decode_session_item

        entries = self._tier[scope]
        entry = entries.pop(proposal_id)
        if not entries:
            del self._tier[scope]
        self._tier_count -= 1
        self._tier_bytes -= len(entry.item)
        if entry.state == 0:
            self._tier_active.pop((scope, proposal_id), None)
        self._tier_pid_arrays.pop(scope, None)
        _, session = decode_session_item(entry.item)
        self._promoting = True
        try:
            self._register_session(scope, session, entry.created_at)
        finally:
            self._promoting = False
        self._tier_promotions += 1
        self._m_tier_promotions.inc()
        self.tracer.count("engine.tier_promotions")
        slot = self._index.get((scope, proposal_id))
        if slot is None:
            return None  # lost the per-scope LRU ranking outright
        record = self._records[slot]
        record.last_activity = entry.last_activity
        record.seq = entry.seq
        return slot

    def _promote_expired_tier(self, now: int) -> None:
        """Page back every ACTIVE demoted session whose expiry has passed
        so the timeout sweep fires it exactly as if it had never left.
        Scans only the (small) active-tier side map, never the decided
        mass."""
        if not self._tier_active:
            return
        due = [
            key for key, expiry in self._tier_active.items() if expiry <= now
        ]
        for scope, pid in due:
            if self._tier_has(scope, pid):
                self._promote_key(scope, pid)

    def _promote_columnar_misses(
        self, scopes: list, scope_idx, proposal_ids: np.ndarray,
        found: np.ndarray,
    ) -> bool:
        """Demand-page demoted sessions hit by a columnar batch: check the
        unresolved rows against the tier and promote any hits. Returns
        True when a promotion happened (the caller re-resolves — the pid
        caches were rebuilt by registration). Free when the tier is empty;
        otherwise per-MISS Python only, never per-row."""
        if not self._tier:
            return False
        miss = np.nonzero(~found)[0]
        if miss.size == 0:
            return False
        promoted = False
        seen: set = set()
        idx_list = None if scope_idx is None else scope_idx
        for i in miss.tolist():
            scope = scopes[0] if idx_list is None else scopes[int(idx_list[i])]
            pid = int(proposal_ids[i])
            key = (scope, pid)
            if key in seen:
                continue
            seen.add(key)
            entries = self._tier.get(scope)
            if entries is not None and pid in entries:
                self._promote_key(scope, pid)
                promoted = True
        return promoted

    def _drop_live_slots(self, scope: Scope, slots: "list[int]") -> None:
        """Shared live-slot teardown (cap eviction / TTL GC / demotion):
        untrack records, forget timelines, filter the scope list, release
        pool slots, drop the pid caches — ONE copy of the sequence, so a
        future bookkeeping field cannot be dropped from just one site."""
        gone = set(slots)
        for slot in slots:
            record = self._records.pop(slot)
            del self._index[(scope, record.proposal.proposal_id)]
            self._timelines.forget(slot)
        live = self._scopes.get(scope)
        if live is not None:
            self._scopes[scope] = [s for s in live if s not in gone]
        release = [s for s in slots if s >= 0]
        if release:
            self._pool.release(release)
        self._drop_pid_cache(scope)

    def _gc_live(self, scope: Scope, slots: "list[int]") -> int:
        """Garbage-collect decided live sessions past their per-scope
        ``evict_decided_after`` TTL: dropped outright (session, slot,
        timeline), exactly like a per-scope-cap eviction but policy-driven."""
        self._drop_live_slots(scope, slots)
        n = len(slots)
        self._tier_gc += n
        self._m_tier_gc.inc(n)
        self.tracer.count("engine.tier_gc", n)
        return n

    def _gc_tier(self, scope: Scope, pids: "list[int]") -> int:
        """Garbage-collect demoted decided sessions past the TTL."""
        entries = self._tier[scope]
        for pid in pids:
            entry = entries.pop(pid)
            self._tier_count -= 1
            self._tier_bytes -= len(entry.item)
        if not entries:
            del self._tier[scope]
        self._tier_pid_arrays.pop(scope, None)
        n = len(pids)
        self._tier_gc += n
        self._m_tier_gc.inc(n)
        self.tracer.count("engine.tier_gc", n)
        return n

    def lifecycle_sweep(self, now: int, _gc_sink: "list | None" = None) -> dict:
        """Apply every scope's tier TTL policies (ScopeConfig
        ``demote_after`` / ``evict_decided_after``) at the embedder's
        logical clock: GC decided/failed sessions past the eviction TTL
        (live or already demoted), then demote sessions idle past the
        demotion TTL. Runs automatically at the end of every
        :meth:`sweep_timeouts` (the engine-wide cadence embedders already
        drive); callable standalone for a custom cadence. Pinned scopes
        (:meth:`pin_scope` — fleet migration freeze) and scopes without
        TTL knobs are untouched. Returns ``{demoted, gc_live, gc_tier}``.

        ``_gc_sink`` (private) collects the GC'd ``(scope, pid)`` keys —
        a DurableEngine logs them as the KIND_GC outcome record. During
        WAL replay the whole sweep is a no-op (set_replay_mode): TTL
        decisions ride idle clocks a snapshot restore does not carry, so
        recovery applies the live run's logged outcome instead of
        re-deriving the policy. A freshly recovered engine's sessions
        restart their idle clocks from created_at (or their replayed
        activity) — demotion may then run early, which is invisible, and
        decided-session GC may collect somewhat earlier than the
        pre-crash clock would have, which is the documented
        retention-policy semantics across restarts."""
        out = {"demoted": 0, "gc_live": 0, "gc_tier": 0}
        if self._multihost or not self._lifecycle_live:
            return out  # replicated control plane / WAL replay
        for scope, config in list(self._scope_configs.items()):
            demote_after = config.demote_after
            evict_after = config.evict_decided_after
            if (demote_after is None and evict_after is None) or (
                scope in self._pinned_scopes
            ):
                continue
            records = self._records
            if evict_after is not None:
                # Cheap TTL filter first (one attribute compare per live
                # record); the state check — a batched host-mirror gather
                # for pooled records — runs on the survivors only.
                cutoff = now - evict_after
                cand = [
                    s
                    for s in self._scopes.get(scope, [])
                    if records[s].last_activity <= cutoff
                ]
                gc_slots = []
                if cand:
                    pooled = [s for s in cand if records[s].session is None]
                    pooled_state = (
                        dict(zip(pooled, self._pool.states_of(pooled).tolist()))
                        if pooled
                        else {}
                    )
                    for s in cand:
                        state = pooled_state.get(s)
                        if state is None:
                            state = state_code_of(records[s].session.state)
                        if state != STATE_ACTIVE:
                            gc_slots.append(s)
                if gc_slots:
                    if _gc_sink is not None:
                        _gc_sink.extend(
                            (scope, records[s].proposal.proposal_id)
                            for s in gc_slots
                        )
                    out["gc_live"] += self._gc_live(scope, gc_slots)
                entries = self._tier.get(scope)
                if entries:
                    dead = [
                        pid
                        for pid, e in entries.items()
                        if e.state != 0 and e.last_activity <= cutoff
                    ]
                    if dead:
                        if _gc_sink is not None:
                            _gc_sink.extend((scope, pid) for pid in dead)
                        out["gc_tier"] += self._gc_tier(scope, dead)
            if demote_after is not None:
                cutoff = now - demote_after
                idle = [
                    s
                    for s in self._scopes.get(scope, [])
                    if records[s].last_activity <= cutoff
                ]
                if idle:
                    out["demoted"] += self._demote_records(scope, idle)
        if out["demoted"] or out["gc_live"] or out["gc_tier"]:
            flight_recorder.record("engine.lifecycle_sweep", **out)
        return out

    def gc_sessions(self, keys: "list[tuple[Scope, int]]") -> int:
        """Apply an exact GC outcome: drop each ``(scope, pid)`` — live
        or demoted — counting it as tier GC. Unknown keys are skipped
        (idempotent). This is the replay entry point for KIND_GC records
        (the live sweep's logged outcome), usable by embedders as an
        explicit per-session retirement too."""
        applied = 0
        by_scope_live: dict[Scope, list[int]] = {}
        by_scope_tier: dict[Scope, list[int]] = {}
        for scope, pid in keys:
            slot = self._index.get((scope, pid))
            if slot is not None:
                by_scope_live.setdefault(scope, []).append(slot)
            elif self._tier_has(scope, pid):
                by_scope_tier.setdefault(scope, []).append(pid)
        for scope, slots in by_scope_live.items():
            applied += self._gc_live(scope, slots)
        for scope, pids in by_scope_tier.items():
            applied += self._gc_tier(scope, pids)
        return applied

    def pin_scope(self, scope: Scope) -> None:
        """Exclude a scope from the lifecycle sweep's demote/GC policies
        (idempotent). The fleet/federation routers pin a shard's scopes
        for the duration of a live migration so nothing pages mid-flip."""
        self._pinned_scopes.add(scope)

    def unpin_scope(self, scope: Scope) -> None:
        self._pinned_scopes.discard(scope)

    def _taken_pids(self, scope: Scope) -> np.ndarray:
        """Every proposal id currently claimed in ``scope`` — live AND
        demoted — for batch id draws (a fresh id colliding with a demoted
        session would alias two sessions onto one key at promotion)."""
        live = self._pid_table(scope)[0]
        entries = self._tier.get(scope)
        if not entries:
            return live
        tier = self._tier_pid_arrays.get(scope)
        if tier is None:
            tier = np.fromiter(entries.keys(), np.int64, len(entries))
            self._tier_pid_arrays[scope] = tier
        return np.concatenate([live, tier])

    # ── Scope config (reference: src/service.rs:375-484) ───────────────

    def scope(self, scope: Scope):
        """Fluent per-scope configuration builder, same surface as the
        scalar service (reference: src/service.rs:558-668)."""
        from ..service import ScopeConfigBuilderWrapper

        existing = self._scope_configs.get(scope)
        builder = (
            ScopeConfigBuilder.from_existing(existing)
            if existing is not None
            else ScopeConfigBuilder()
        )
        return ScopeConfigBuilderWrapper(self, scope, builder)

    def set_scope_config(self, scope: Scope, config: ScopeConfig) -> None:
        config.validate()
        self._scope_configs[scope] = config

    def get_scope_config(self, scope: Scope) -> ScopeConfig | None:
        return self._scope_configs.get(scope)

    def adaptive_timeout(self, scope: Scope) -> float:
        """The consensus timeout the embedder should schedule next for
        ``scope``, in seconds: the learned value when the scope declared
        ``timeout_min``/``timeout_max`` bounds, else the scope's static
        ``default_timeout`` (or the gossipsub default) — exactly the
        reference behavior. Advisory only: timers stay embedder-owned
        (reference: src/lib.rs:15-34)."""
        cfg = self._scope_configs.get(scope)
        learned = self._adaptive.current(scope, cfg)
        if learned is not None:
            return learned
        return cfg.default_timeout if cfg is not None else DEFAULT_TIMEOUT_SECONDS

    def adaptive_timeout_snapshot(self) -> dict:
        """Learner introspection (per-scope learned values + counters)."""
        return self._adaptive.snapshot()

    # ScopeConfigBuilderWrapper terminal hooks (shared with the service).
    def _initialize_scope(self, scope: Scope, config: ScopeConfig) -> None:
        self.set_scope_config(scope, config)

    def _update_scope_config(self, scope: Scope, config: ScopeConfig) -> None:
        """Create-default-then-mutate-then-validate, matching
        InMemoryConsensusStorage.update_scope_config
        (reference: src/storage.rs:366-375)."""
        existing = self._scope_configs.get(scope, ScopeConfig())
        existing.network_type = config.network_type
        existing.default_consensus_threshold = config.default_consensus_threshold
        existing.default_timeout = config.default_timeout
        existing.default_liveness_criteria_yes = config.default_liveness_criteria_yes
        existing.max_rounds_override = config.max_rounds_override
        existing.demote_after = config.demote_after
        existing.evict_decided_after = config.evict_decided_after
        existing.decide_p99_ms = config.decide_p99_ms
        existing.timeout_min = config.timeout_min
        existing.timeout_max = config.timeout_max
        existing.validate()
        self._scope_configs[scope] = existing

    def _resolve_config(
        self,
        scope: Scope,
        proposal_override: ConsensusConfig | None,
        proposal: Proposal | None,
    ) -> ConsensusConfig:
        """Same precedence as the service: explicit override > scope config >
        gossipsub default; timeout from the proposal's expiration window
        unless overridden; liveness always from the proposal
        (reference: src/service.rs:440-484)."""
        has_override = proposal_override is not None
        if proposal_override is not None:
            base = proposal_override
        else:
            scope_config = self._scope_configs.get(scope)
            base = (
                ConsensusConfig.from_scope_config(scope_config)
                if scope_config is not None
                else ConsensusConfig.gossipsub()
            )
        if proposal is None:
            return base
        if has_override:
            timeout_seconds = base.consensus_timeout
        elif proposal.expiration_timestamp > proposal.timestamp:
            timeout_seconds = float(proposal.expiration_timestamp - proposal.timestamp)
        else:
            timeout_seconds = base.consensus_timeout
        return ConsensusConfig(
            consensus_threshold=base.consensus_threshold,
            consensus_timeout=timeout_seconds,
            max_rounds=base.max_rounds,
            use_gossipsub_rounds=base.use_gossipsub_rounds,
            liveness_criteria=proposal.liveness_criteria_yes,
        )

    # ── Internals ──────────────────────────────────────────────────────

    def _state_code(self, record: SessionRecord[Scope]) -> int:
        """Dense lifecycle state regardless of substrate: host-mirrored pool
        state for pooled records, scalar state for host-spilled ones."""
        if record.session is not None:
            return state_code_of(record.session.state)
        return self._pool.state_of(record.slot)

    def _get_record(self, scope: Scope, proposal_id: int) -> SessionRecord[Scope]:
        slot = self._index.get((scope, proposal_id))
        if slot is None:
            # Demand-page: a point read on a demoted session promotes it
            # back transparently (get_result / EXPLAIN / export / gossip
            # reconstruction all land here).
            slot = self._tier_lookup_promote(scope, proposal_id)
            if slot is None:
                raise SessionNotFound()
        return self._records[slot]

    def _scope_records(self, scope: Scope) -> list[SessionRecord[Scope]]:
        return [self._records[s] for s in self._scopes.get(scope, [])]

    def _evict_for(self, scope: Scope, now: int) -> bool:
        """LRU-by-created_at eviction beyond the per-scope cap
        (reference: src/service.rs:512-522), applied for an incoming session
        stamped ``created_at=now`` *before* it is allocated: keep the newest
        ``max`` of incumbents+newcomer (ties favor incumbents, matching the
        insert-then-trim stable sort). Evicts surplus incumbents; returns
        True when the newcomer itself loses the ranking and must not be
        tracked.

        Demoted sessions are incumbents too: they count against the cap
        and evict on the same ranking (ordered by their per-scope ``seq``,
        which reconstructs the original insertion order even after a
        demote→promote round-trip re-appended a record), so a tiered
        engine evicts exactly the sessions its untier'd twin would."""
        slots = self._scopes.get(scope, [])
        tier_entries = self._tier.get(scope)
        n_tier = len(tier_entries) if tier_entries else 0
        if len(slots) + n_tier + 1 <= self._max_sessions_per_scope:
            return False
        # (created_at, seq, is_tier, key): seq-ascending reproduces the
        # per-scope insertion order; the newcomer's infinite seq loses
        # created_at ties to every incumbent (insert-then-trim order).
        items = [
            (self._records[s].created_at, self._records[s].seq, False, s)
            for s in slots
        ]
        if tier_entries:
            items.extend(
                (e.created_at, e.seq, True, pid)
                for pid, e in tier_entries.items()
            )
        newcomer = (now, float("inf"), False, None)
        items.append(newcomer)
        items.sort(key=lambda t: t[1])
        items.sort(key=lambda t: t[0], reverse=True)
        keep = items[: self._max_sessions_per_scope]
        evicted = items[self._max_sessions_per_scope :]
        evicted_slots = [k for _, _, is_tier, k in evicted if not is_tier and k is not None]
        evicted_pids = [k for _, _, is_tier, k in evicted if is_tier]
        if evicted_slots:
            self._drop_live_slots(scope, evicted_slots)
        if evicted_pids:
            for pid in evicted_pids:
                entry = tier_entries.pop(pid)
                self._tier_count -= 1
                self._tier_bytes -= len(entry.item)
                if entry.state == 0:
                    self._tier_active.pop((scope, pid), None)
            if not tier_entries:
                del self._tier[scope]
            self._tier_pid_arrays.pop(scope, None)
        return newcomer not in keep

    def _emit(self, scope: Scope, event: ConsensusEvent) -> None:
        self._event_bus.publish(scope, event)

    # ── Multi-host ownership (parallel/multihost.py contract) ──────────

    def _owns_replicated_event(self) -> bool:
        """Events arising from replicated, not-slot-owned work — proposal
        loads and host-spilled sessions — are emitted by process 0 only in
        multi-host mode, so a fleet of engine front-ends never
        double-publishes."""
        return self._process_zero

    def _owns_slot(self, slot: int) -> bool:
        """EVENT-emission ownership of one session. Single-host pools own
        everything. On a multi-host pool a device slot belongs to the
        process whose local range holds it; host-spilled sessions
        (replicated on every process) belong to process 0."""
        if not self._multihost:
            return True
        if slot < 0:
            return self._process_zero
        lo, hi = self._pool.local_slots()
        return lo <= slot < hi

    def is_local(self, scope: Scope, proposal_id: int) -> bool:
        """Routing query for multi-host embedders: should THIS process
        apply the session's votes? Device-pooled sessions: the slot-owning
        process only (route to it). Host-spilled sessions are replicated
        control-plane state: True on EVERY process — the relay must deliver
        their votes fleet-wide (like proposals) so the replicas advance
        identically; their events still come from process 0 only."""
        slot = self._index.get((scope, proposal_id))
        if slot is None:
            raise SessionNotFound()
        if slot < 0:
            return True
        return self._owns_slot(slot)


class _PidLookup:
    """Open-addressing proposal-id -> slot hash with fully vectorized
    probing. Fibonacci hashing, power-of-two size, load factor <= 0.5, so
    probe chains are short; both build and lookup run as numpy passes over
    shrinking active sets (no per-row Python)."""

    _GOLDEN = np.uint64(0x9E3779B97F4A7C15)

    def __init__(self, pids: np.ndarray, slots: np.ndarray):
        n = max(len(pids), 1)
        size = 1
        while size < 2 * n:
            size <<= 1
        self._size = size
        self._shift = np.uint64(64 - (size.bit_length() - 1))
        self._mask = np.int64(size - 1)
        self.keys = np.full(size, -1, np.int64)
        self.vals = np.zeros(size, np.int64)
        if len(pids) == 0:
            return
        rem_pids = np.asarray(pids, np.int64)
        rem_slots = np.asarray(slots, np.int64)
        if (rem_pids == -1).any():
            raise ValueError("proposal id -1 collides with the hash sentinel")
        h = self._bucket(rem_pids)
        while rem_pids.size:
            # A bucket can be contested by several pending keys: the first
            # occupant wins, the rest advance one step (linear probing).
            empty = self.keys[h] == -1
            _, first = np.unique(h, return_index=True)
            win = np.zeros(len(h), bool)
            win[first] = True
            place = empty & win
            self.keys[h[place]] = rem_pids[place]
            self.vals[h[place]] = rem_slots[place]
            rest = ~place
            h = (h[rest] + 1) & self._mask
            rem_pids = rem_pids[rest]
            rem_slots = rem_slots[rest]

    def _bucket(self, q: np.ndarray) -> np.ndarray:
        return (
            (q.astype(np.uint64) * self._GOLDEN) >> self._shift
        ).astype(np.int64)

    def lookup(self, q: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Returns (found bool[B], slot int64[B]; 0 where not found)."""
        q = np.asarray(q, np.int64)
        batch = len(q)
        if batch >= 512:
            # Fused native probe (one C pass per query, GIL released) —
            # the numpy loop below pays ~12 array passes per probe round.
            from .. import native as _native

            res = _native.pid_lookup(self.keys, self.vals, int(self._shift), q)
            if res is not None:
                return res
        found = np.zeros(batch, bool)
        out = np.zeros(batch, np.int64)
        # Any int64 key hashes fine (uint64 cast); only -1 must be excluded
        # or it would match the empty-bucket sentinel and "resolve" to
        # slot 0. (-1 is also rejected at build, so it can never be stored.)
        active = np.nonzero(q != -1)[0]
        h = self._bucket(q[active])
        while active.size:
            k = self.keys[h]
            hit = k == q[active]
            hit &= k != -1  # never match the empty sentinel
            if hit.any():
                rows = active[hit]
                found[rows] = True
                out[rows] = self.vals[h[hit]]
            cont = ~hit & (k != -1)
            active = active[cont]
            h = (h[cont] + 1) & self._mask
        return found, out


def _synchronized(fn):
    """Run ``fn`` under the engine lock. On the frame-level entry points
    (``_LOCK_WAIT_TIMED``) the outermost call records its acquire wait as
    an ``engine.lock_wait`` span into ``hashgraph_engine_lock_wait_seconds``
    — a zero wait when the non-blocking try succeeds — so the mean covers
    every frame, contended or not. A reentrant call (this thread already
    inside the engine) and the scalar entry points record nothing."""
    timed = fn.__name__ in _LOCK_WAIT_TIMED

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        lock, nesting = self._lock, self._lock_depth
        depth = getattr(nesting, "depth", 0)
        # The non-blocking try succeeds at once on a free lock or one this
        # thread holds; only a failed try times a blocking acquire. Every
        # path after an acquire reaches the release in the finally.
        acquired = lock.acquire(blocking=False)
        try:
            if timed and depth == 0:
                with observed_span(
                    self.tracer, "engine.lock_wait", self._m_lock_wait
                ):
                    if not acquired:
                        acquired = lock.acquire()
            elif not acquired:
                acquired = lock.acquire()
            nesting.depth = depth + 1
            return fn(self, *args, **kwargs)
        except ConsensusError:
            # The engine's caller-facing contract: typed rejections,
            # not faults — no flight dump for them.
            raise
        except Exception as exc:
            # Anything else — including a bare KeyError/ValueError from
            # internal bookkeeping, which is almost always an invariant
            # break, not an API rejection — is a fault: preserve the
            # evidence. The ring already holds the recent
            # batch/creation/sweep events; dumping is rate-limited
            # inside the recorder, so a crash loop (or a caller
            # hammering a malformed-argument path) costs one file per
            # second, not one per call.
            flight_recorder.record(
                "engine.fault", api=fn.__name__, error=repr(exc)
            )
            flight_recorder.dump(f"engine-fault:{fn.__name__}")
            raise
        finally:
            nesting.depth = depth
            if acquired:
                lock.release()

    return wrapper


# Entry points that carry a whole frame or batch: their lock waits are
# timed. The scalar ones (cast_vote, process_incoming_vote, ...) are not.
_LOCK_WAIT_TIMED = frozenset(
    (
        "ingest_wire_columnar",
        "ingest_columnar",
        "ingest_columnar_multi",
        "deliver_proposals",
    )
)


# Public API surface runs under the engine lock (reentrant: scalar entry
# points funnel into ingest_votes). Event-bus publishes are non-blocking
# (bounded queues, silent drop), so holding the lock across them is safe.
for _name in (
    "create_proposal",
    "create_proposals",
    "create_proposals_multi",
    "process_incoming_proposal",
    "ingest_proposals",
    "deliver_proposal",
    "deliver_proposals",
    "ingest_columnar",
    "ingest_columnar_multi",
    "ingest_wire_columnar",
    "voter_gid",
    "cast_vote",
    "cast_vote_and_get_proposal",
    "process_incoming_vote",
    "ingest_votes",
    "handle_consensus_timeout",
    "sweep_timeouts",
    "demote_session",
    "lifecycle_sweep",
    "gc_sessions",
    "pin_scope",
    "unpin_scope",
    "get_proposal",
    "get_consensus_result",
    "get_active_proposals",
    "get_reached_proposals",
    "get_scope_stats",
    "proposal_timeline",
    "trace_context_of",
    "explain_decision",
    "set_replay_mode",
    "export_session",
    "save_to_storage",
    "load_from_storage",
    "delete_scope",
    "delete_scopes",
    "set_scope_config",
    "get_scope_config",
    "_initialize_scope",
    "_update_scope_config",
):
    setattr(
        TpuConsensusEngine,
        _name,
        _synchronized(getattr(TpuConsensusEngine, _name)),
    )
