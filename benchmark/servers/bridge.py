"""The served path on one chip: the program's ``BridgeServer`` in the run's
own process, built with the configuration's deployment parameters only
(slots, voter lanes, scopes and their preset, sessions kept per scope) and
the signer that the mix's signature scheme names. It sets no
``HASHGRAPH_TPU_*`` variable and no option that is off by default.

A server builder returns an object with ``start()``, ``address`` and
``stop()`` that speaks the bridge protocol. Found by name: a
configuration's ``server`` (default ``bridge``)."""

from benchmark.traffic import scope_name


def build(cfg: dict, mix: dict, scheme, fault: "str | None"):
    import hashgraph_tpu
    from hashgraph_tpu import BroadcastEventBus, ScopeConfigBuilder
    from hashgraph_tpu.bridge.server import BridgeServer
    from hashgraph_tpu.engine import TpuConsensusEngine, VerifiedVoteCache

    def engine_factory(signer):
        engine = TpuConsensusEngine(
            signer, event_bus=BroadcastEventBus(),
            max_sessions_per_scope=cfg["max_sessions_per_scope"],
            verify_cache=VerifiedVoteCache(), capacity=cfg["slots"],
            voter_capacity=cfg["voter_lanes"])
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
