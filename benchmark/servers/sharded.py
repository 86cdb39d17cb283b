"""The served path over a slot pool sharded across chips: the program's
``BridgeServer`` in the run's own process, as ``servers/bridge.py`` builds
it, except that each engine holds a ``ShardedPool`` over the first
``chips`` devices, ``slots / chips`` slots on each, with the
configuration's voter lanes. It sets no ``HASHGRAPH_TPU_*`` variable and
no option that is off by default.

Found by name: a configuration's ``server``."""

from benchmark.traffic import scope_name


def build(cfg: dict, mix: dict, scheme, fault: "str | None"):
    import hashgraph_tpu
    from hashgraph_tpu import BroadcastEventBus, ScopeConfigBuilder
    from hashgraph_tpu.bridge.server import BridgeServer
    from hashgraph_tpu.engine import TpuConsensusEngine, VerifiedVoteCache
    from hashgraph_tpu.parallel import ShardedPool, consensus_mesh

    def engine_factory(signer):
        pool = ShardedPool(cfg["slots"] // cfg["chips"], cfg["voter_lanes"],
                           consensus_mesh(cfg["chips"]))
        engine = TpuConsensusEngine(
            signer, event_bus=BroadcastEventBus(),
            max_sessions_per_scope=cfg["max_sessions_per_scope"],
            verify_cache=VerifiedVoteCache(), pool=pool)
        builder = ScopeConfigBuilder()
        builder = (builder.p2p_preset() if cfg["network"] == "p2p"
                   else builder.gossipsub_preset())
        if fault == "control_quorum":
            builder = builder.with_threshold(0.6)
        config = builder.build()
        for s in range(cfg["scopes"]):
            engine.set_scope_config(scope_name(s), config)
        return engine

    server = BridgeServer(
        capacity=cfg["slots"], voter_capacity=cfg["voter_lanes"],
        signer_factory=getattr(hashgraph_tpu, scheme.PROGRAM_SIGNER),
        engine_factory=engine_factory)
    if fault and fault != "control_quorum":
        from benchmark.faults import install

        install(server, fault)
    return server
