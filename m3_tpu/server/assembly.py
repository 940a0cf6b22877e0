"""Config-driven server assembly: one call from NodeConfig to a running
node.

Equivalent of the reference's monolithic startup
(`src/dbnode/server/server.go:171 Run`: config → pools → topology →
storage.NewDatabase → servers → bootstrap; and the query side
`src/query/server/query.go:195`): build the instrument registry, the
Database (with namespaces from config), bootstrap it, open the mediator
loop, and serve the HTTP API.  `Assembly.close()` tears down in reverse
order.
"""

from __future__ import annotations

import dataclasses
import time as _time

from m3_tpu import instrument
from m3_tpu.core.config import NodeConfig, load_config, parse_duration
from m3_tpu.server.http_api import ApiContext, serve_background
from m3_tpu.storage.database import Database, DatabaseOptions, NamespaceOptions
from m3_tpu.storage.mediator import Mediator


@dataclasses.dataclass
class Assembly:
    config: NodeConfig
    registry: "instrument.Registry"
    db: Database | None              # None: an aggregator process
    mediator: Mediator | None
    http_server: object | None
    carbon_server: object | None = None
    tracer: object | None = None
    admin_server: object | None = None
    kv: object | None = None
    rpc_server: object | None = None
    peer_handles: list = dataclasses.field(default_factory=list)
    scrubber: object | None = None
    topology: object | None = None   # cluster.topology.TopologyWatcher
    migrator: object | None = None   # storage.migration.ShardMigrator
    query_server: object | None = None  # query.remote.QueryServer
    remote_stores: list = dataclasses.field(default_factory=list)
    downsampler: object | None = None   # coordinator.downsample.Downsampler
    checkpointer: object | None = None  # aggregator.checkpoint driver
    selfmon: object | None = None       # instrument.selfmon.SelfMonitor
    controller: object | None = None    # x.controller.Controller
    aggregator: object | None = None    # aggregator.service.AggregatorService

    @property
    def port(self) -> int | None:
        return self.http_server.server_address[1] if self.http_server else None

    @property
    def rpc_port(self) -> int | None:
        return self.rpc_server.port if self.rpc_server else None

    @property
    def query_port(self) -> int | None:
        return self.query_server.port if self.query_server else None

    @property
    def carbon_port(self) -> int | None:
        return self.carbon_server.port if self.carbon_server else None

    @property
    def admin_port(self) -> int | None:
        return self.admin_server.server_address[1] if self.admin_server else None

    def close(self) -> None:
        if self.aggregator is not None:
            self.aggregator.close()
        for h in self.peer_handles:
            h.close()
        for r in self.remote_stores:
            r.close()
        if self.query_server is not None:
            self.query_server.shutdown()
            self.query_server.server_close()
        if self.rpc_server is not None:
            self.rpc_server.shutdown()
            self.rpc_server.server_close()
        if self.admin_server is not None:
            self.admin_server.shutdown()
            self.admin_server.server_close()
        if self.carbon_server is not None:
            self.carbon_server.shutdown()
            self.carbon_server.server_close()
        if self.http_server is not None:
            self.http_server.shutdown()
            self.http_server.server_close()
        if self.mediator is not None:
            self.mediator.close()
        if self.migrator is not None:
            self.migrator.close()
        if self.topology is not None:
            self.topology.close()
        # the KV client closes only after every server that used it is
        # down — a racing admin request must not reconnect a closed store
        if self.kv is not None and hasattr(self.kv, "close"):
            self.kv.close()
        if self.db is not None:
            self.db.close()
        if self.tracer is not None:
            # no longer the process's tracer; its ring stays readable
            from m3_tpu.instrument import tracing

            tracing.uninstall(self.tracer)

    def drain(self, handoff_timeout_s: float = 60.0) -> None:
        """True SIGTERM drain (the reference dbnode's graceful shutdown
        discipline): stop taking ingest → persist what we hold → wait
        for any LEAVING shards to cut over to their new owners → tear
        down.  The RPC listener stays up until the very end so peers
        can stream this node's blocks throughout the handoff window.

        Idempotent-ish with close(): the servers stopped here are
        nulled so close() skips them."""
        import time as _time

        from m3_tpu.instrument import logger as _logger

        log = _logger("server.assembly")
        for attr in ("carbon_server", "http_server"):
            srv = getattr(self, attr)
            if srv is not None:
                srv.shutdown()
                srv.server_close()
                setattr(self, attr, None)
        if self.mediator is not None:
            self.mediator.close()
            self.mediator = None
        if self.db is None:
            # an aggregator holds only its open windows: nothing to
            # persist beyond the flush times its ticks already wrote
            self.close()
            return
        # Persist everything persistable: seal+flush whatever left the
        # warm window, snapshot the still-open buffers, rotate the WAL
        # — a restart replays cleanly AND peers can stream every
        # flushed block.  (The active warm block cannot become a
        # fileset early; replicas + the snapshot cover it.)
        try:
            now = _time.time_ns()
            self.db.tick(now)
            self.db.snapshot()
        except Exception:  # noqa: BLE001 — drain must reach close()
            log.exception("drain: final flush/snapshot failed")
        if self.checkpointer is not None:
            # Final arena checkpoint: a SIGTERM'd aggregator resumes
            # its open windows on restart (aggregator/checkpoint.py)
            try:
                self.checkpointer.save()
            except Exception:  # noqa: BLE001 — drain must reach close()
                log.exception("drain: aggregator checkpoint failed")
        if self.migrator is not None:
            if not self.migrator.wait_handed_off(handoff_timeout_s):
                log.warning(
                    "drain: handoff incomplete after %.0fs "
                    "(LEAVING shards remain; replicas will repair)",
                    handoff_timeout_s,
                )
        self.close()


def namespace_options(ns_cfg) -> NamespaceOptions:
    kw = {}
    # cardinality sizing: 0 keeps the storage defaults; a node serving
    # million-series traffic raises slot_capacity per shard (the soak
    # found the default wall at 2^17 series/shard)
    if ns_cfg.slot_capacity:
        kw["slot_capacity"] = ns_cfg.slot_capacity
    if ns_cfg.sample_capacity:
        kw["sample_capacity"] = ns_cfg.sample_capacity
    return NamespaceOptions(
        block_size_nanos=parse_duration(ns_cfg.block_size),
        retention_nanos=parse_duration(ns_cfg.retention),
        buffer_past_nanos=parse_duration(ns_cfg.buffer_past),
        buffer_future_nanos=parse_duration(ns_cfg.buffer_future),
        cold_writes_enabled=ns_cfg.cold_writes_enabled,
        num_shards=ns_cfg.num_shards,
        **kw,
    )


def _open_kv(cfg: NodeConfig):
    """The process's control plane: the shared external KV service
    (etcd role — survives this process and is visible to every
    replica) where `db.kv_endpoint` names one, else file-backed under
    `db.root`."""
    if cfg.db.kv_endpoint:
        from m3_tpu.cluster.kv_remote import RemoteKVStore

        h, _, p = cfg.db.kv_endpoint.rpartition(":")
        return RemoteKVStore((h, int(p)))
    from pathlib import Path

    from m3_tpu.cluster.kv import KVStore

    Path(cfg.db.root).mkdir(parents=True, exist_ok=True)
    return KVStore(cfg.db.root)


def run_node(source, start_mediator: bool | None = None,
             serve_http: bool = True, ruleset=None) -> Assembly:
    """Boot a node from a YAML path/string or a NodeConfig.

    Mirrors server.Run's order: config validate → storage → bootstrap →
    background maintenance → front door.  `ruleset` (a
    metrics.rules.RuleSet) is required when the coordinator config sets
    `downsample: true` — rules are programmatic/KV objects in the
    reference too (`metrics/rules` in etcd), not static YAML.
    """
    from m3_tpu.core.config import ConfigError

    cfg = source if isinstance(source, NodeConfig) else load_config(source)
    cfg.validate()
    if (cfg.coordinator is not None and cfg.coordinator.downsample
            and ruleset is None):
        raise ConfigError(
            "coordinator.downsample=true requires run_node(..., ruleset=...)"
        )
    # Device-boundary knobs FIRST: the memory budget must be installed
    # before any arena/buffer reserves against it, and the stage
    # breakers bind their thresholds at first guarded call.
    from m3_tpu.x import devguard as _devguard, membudget as _membudget

    _membudget.set_budget(cfg.device.mem_budget)
    _devguard.configure(
        failures=cfg.device.breaker_failures,
        reset_s=parse_duration(cfg.device.breaker_reset) / 1e9)
    # Disk ledger next (membudget's twin): armed before the Database
    # exists so the very first mediator tick refreshes real watermarks.
    # reset() when disabled — the ledger is process-global and a prior
    # in-process node's configuration must not leak into this one.
    from m3_tpu.x import diskbudget as _diskbudget

    if cfg.disk.enabled:
        _diskbudget.configure(
            cfg.db.root,
            capacity=cfg.disk.capacity,
            reserve=cfg.disk.reserve,
            low_ratio=cfg.disk.low_ratio,
            critical_ratio=cfg.disk.critical_ratio)
    else:
        _diskbudget.reset()
    registry = instrument.new_registry()
    scope = registry.scope(cfg.metrics_prefix)
    # Mirror the process-global fault/retry counters onto this node's
    # /metrics so dtest scenarios can assert injected faults and retry
    # activity from outside the process.
    from m3_tpu.x import register_metrics

    register_metrics(registry)
    # Process-level self-observation (RSS/CPU/threads/FDs/uptime): the
    # runtime facts debug.py only ever put in the on-demand debug zip
    # now ride every scrape — the selfmon loop and operator dashboards
    # see a node eating memory, not just the post-mortem.
    from m3_tpu.instrument.procstats import install_process_collector

    install_process_collector(registry, scope)
    # One span mechanism (instrument/tracing.py): the node's tracer is
    # always there and is the process's tracer; it records while the
    # operator's coordinator.tracing is set or a profile is captured.
    from m3_tpu.instrument import tracing

    tracer = tracing.Tracer(
        enabled=cfg.coordinator is not None and cfg.coordinator.tracing)
    tracing.install(tracer)

    from m3_tpu.storage.limits import LimitsOptions, QueryLimits

    limits = QueryLimits(
        LimitsOptions(
            max_docs_matched=cfg.db.limits.max_docs_matched,
            max_series_read=cfg.db.limits.max_series_read,
            max_bytes_read=cfg.db.limits.max_bytes_read,
            lookback_s=parse_duration(cfg.db.limits.lookback) / 1e9,
        ),
        instrument=scope,
    )
    namespaces = {
        name: namespace_options(ns) for name, ns in cfg.db.namespaces.items()
    }
    if cfg.selfmon.enabled and cfg.selfmon.namespace not in namespaces:
        # Auto-provision the reserved self-monitoring namespace as an
        # ordinary db.namespaces entry (declare it in config to tune
        # retention/blocks).  num_shards follows the serving namespace
        # so a placement installed by the topology watcher scopes it
        # identically — selfmon writes cross the same ownership gate
        # as user ingest.
        base = cfg.db.namespaces.get(
            cfg.coordinator.namespace if cfg.coordinator is not None
            else "default")
        namespaces[cfg.selfmon.namespace] = NamespaceOptions(
            num_shards=base.num_shards if base is not None else 4)
    db = Database(
        DatabaseOptions(
            root=cfg.db.root, commitlog_enabled=cfg.db.commitlog_enabled
        ),
        namespaces=namespaces,
        instrument=scope,
        tracer=tracer,
        limits=limits,
    )
    # Tear down everything already started if a later step fails (e.g.
    # the carbon port is taken) — a half-built node must not leak its
    # mediator thread or bound HTTP socket.
    asm = Assembly(cfg, registry, db, None, None, None, tracer)
    try:
        # Control plane FIRST: the topology watcher must install this
        # node's shard ownership before bootstrap so WAL replay and the
        # peers pass are placement-scoped from the very first byte.
        need_kv = (
            cfg.db.kv_endpoint is not None
            or cfg.db.instance_id is not None
            or (cfg.coordinator is not None
                and cfg.coordinator.admin_listen_port is not None)
        )
        if need_kv:
            asm.kv = _open_kv(cfg)
        if cfg.db.instance_id is not None and asm.kv is not None:
            from m3_tpu.cluster.placement import PlacementService
            from m3_tpu.cluster.topology import TopologyWatcher
            from m3_tpu.storage.migration import ShardMigrator

            asm.topology = TopologyWatcher(asm.kv, cfg.db.instance_id)
            asm.migrator = ShardMigrator(
                db, asm.topology, PlacementService(asm.kv),
                stream_blocks_per_tick=cfg.mediator.migrate_blocks,
                grace_ticks=cfg.mediator.migrate_grace_ticks,
                instrument=scope,
            )

        db.bootstrap()

        # Wire peers bootstrap: after local fs+commitlog recovery, pull
        # any (shard, block) filesets a replica peer has that this node
        # lacks, over the socket RPC (the bootstrap chain's final
        # `peers` stage — bootstrapper/peers/source.go).  Unreachable
        # peers are skipped; repair converges them later.  With a
        # topology watcher installed the pass is scoped to
        # placement-owned shards (peers_bootstrap reads the ownership
        # the watcher installed) — a restarting node pulls its shards,
        # never every peer's full dataset.
        if cfg.db.peers:
            from m3_tpu.server.rpc import RemoteDatabase

            asm.peer_handles = [
                RemoteDatabase((h, int(p)))
                for h, _, p in (a.rpartition(":") for a in cfg.db.peers)
            ]
            if cfg.db.bootstrap_peers:
                from m3_tpu.storage.repair import peers_bootstrap

                for ns_name in cfg.db.namespaces:
                    peers_bootstrap(db, asm.peer_handles, ns_name)

        if cfg.db.rpc_listen_port is not None:
            from m3_tpu.server.rpc import serve_rpc_background

            asm.rpc_server = serve_rpc_background(
                db, host=cfg.db.rpc_listen_host, port=cfg.db.rpc_listen_port
            )
            if cfg.disk.enabled:
                # CRITICAL watermark → refuse write batches un-acked
                # (typed RPC_ERR the session's consistency level
                # absorbs); reads/repair/ticks are never gated.
                asm.rpc_server.ingest_gate = _diskbudget.check_ingest

        # Query federation (query/remote): serve THIS node's storage to
        # peer coordinators over QUERY_FETCH, and/or federate peer
        # coordinators' stores into this node's engine.  Each remote
        # gets the process-shared per-peer circuit breaker so a dead
        # region fails fast for every query at once.
        ns0 = (cfg.coordinator.namespace if cfg.coordinator is not None
               else "default")
        if cfg.query.listen_port is not None:
            from m3_tpu.query.remote import serve_query_background
            from m3_tpu.query.storage_adapter import DatabaseStorage

            asm.query_server = serve_query_background(
                DatabaseStorage(db, ns0),
                host=(cfg.coordinator.listen_host
                      if cfg.coordinator is not None else "127.0.0.1"),
                port=cfg.query.listen_port,
                tracer=tracer,
            )
        if cfg.query.remotes:
            from m3_tpu.query.remote import RemoteStorage
            from m3_tpu.x.breaker import breaker_for

            breaker_reset_s = parse_duration(cfg.query.breaker_reset) / 1e9
            asm.remote_stores = [
                RemoteStorage(
                    (h, int(p)),
                    timeout_s=parse_duration(cfg.query.default_timeout) / 1e9,
                    breaker=breaker_for(
                        f"query:{h}:{p}",
                        failure_threshold=cfg.query.breaker_failures,
                        reset_timeout_s=breaker_reset_s),
                )
                for h, _, p in (a.rpartition(":") for a in cfg.query.remotes)
            ]

        # Corruption scrubber: always constructed (the admin endpoint
        # scrubs on demand); attached to the mediator loop only when a
        # per-tick budget is configured.  Peers double as the repair
        # source — a quarantined (shard, block) hole heals from a
        # replica on the next sweep.
        from m3_tpu.storage.scrub import Scrubber

        asm.scrubber = Scrubber(
            db, peers=asm.peer_handles,
            budget_volumes=cfg.mediator.scrub_volumes, instrument=scope,
        )

        # Downsampler BEFORE the mediator: its window drain and arena
        # checkpoint ride the mediator tick, and a checkpoint restore
        # must land before any traffic re-opens the windows.
        downsampler = None
        if (serve_http and cfg.coordinator is not None
                and cfg.coordinator.downsample):
            from m3_tpu.coordinator.downsample import Downsampler

            downsampler = Downsampler(
                db, ruleset, namespace=cfg.coordinator.namespace
            )
            asm.downsampler = downsampler
            if cfg.coordinator.checkpoint_every > 0:
                from pathlib import Path as _Path

                from m3_tpu.aggregator.checkpoint import (
                    AggregatorCheckpointer,
                )

                asm.checkpointer = AggregatorCheckpointer(
                    downsampler,
                    _Path(cfg.db.root) / "checkpoint" / "aggregator.ckpt",
                    instrument=scope,
                )
                # Resume open aggregation windows from the last
                # checkpoint (SIGKILL/SIGTERM recovery); a corrupt file
                # is moved aside and the node boots fresh.
                asm.checkpointer.restore()

        # Self-monitoring BEFORE the mediator: the scrape task rides
        # the tick loop, and its SLO evaluator binds the selfmon
        # namespace engine at construction.
        if cfg.selfmon.enabled:
            from m3_tpu.instrument.selfmon import SelfMonitor
            from m3_tpu.query.slo import default_rules, rule_from_dict

            rules = (default_rules(cfg.metrics_prefix)
                     if cfg.selfmon.default_rules else [])
            rules += [rule_from_dict(r) for r in cfg.selfmon.rules]
            asm.selfmon = SelfMonitor(
                db, registry,
                namespace=cfg.selfmon.namespace,
                instance=(cfg.selfmon.instance or cfg.db.instance_id
                          or "self"),
                budget=cfg.selfmon.budget,
                peers=cfg.selfmon.peers,
                scrape_timeout_s=parse_duration(
                    cfg.selfmon.scrape_timeout) / 1e9,
                slo_rules=rules,
                slo_deadline_s=parse_duration(
                    cfg.selfmon.slo_deadline) / 1e9,
                instrument=scope,
            )

        # Admission is shared by the HTTP front door and the
        # controller's query_slots actuator — build it before either
        # consumer exists.
        admission = None
        if cfg.coordinator is not None:
            from m3_tpu.x.admission import AdmissionController

            admission = AdmissionController(
                max_concurrent=cfg.query.max_concurrent,
                max_queue=cfg.query.max_queue,
                queue_timeout_s=parse_duration(cfg.query.queue_timeout) / 1e9,
            )

        # The self-healing control plane BEFORE the mediator (its pass
        # rides the tick loop right after the selfmon stage, acting on
        # the verdicts evaluated the same tick).  Bindings resolve by
        # rule NAME against the evaluator's configured rule set
        # (slo.rules()) — an unconfigured name simply does not bind.
        if (cfg.controller.enabled and asm.selfmon is not None
                and getattr(asm.selfmon, "slo", None) is not None):
            from m3_tpu.x import controller as xctl
            from m3_tpu.x import membudget as _mb

            ccfg = cfg.controller
            slo = asm.selfmon.slo
            known = set(slo.rules())
            reg = xctl.ActuatorRegistry()
            bindings: list = []

            def _bind(rule: str, acts: list, name: str = "", **kw) -> None:
                if rule and rule in known and acts:
                    bindings.append(xctl.Binding(
                        rule=rule, actuators=tuple(acts),
                        name=name or rule,
                        fire_ticks=ccfg.fire_ticks,
                        clear_ticks=ccfg.clear_ticks,
                        clear_burn=ccfg.clear_burn,
                        hold_ticks=ccfg.hold_ticks, **kw))

            slot_acts = []
            if admission is not None:
                reg.register(xctl.admission_actuator(
                    admission, floor=ccfg.query_floor,
                    step=ccfg.query_step))
                slot_acts = ["query_slots"]
            _bind(ccfg.query_rule, slot_acts, name="query-burn")
            _bind(ccfg.ingest_rule, slot_acts, name="ingest-burn")
            dev_acts = [reg.register(
                xctl.devguard_fallback_actuator()).name]
            if asm.checkpointer is not None:
                dev_acts.append(reg.register(
                    xctl.checkpoint_actuator(asm.checkpointer)).name)
            budget_b = _mb.budget()
            if budget_b > 0:
                floor_b = int(budget_b * ccfg.mem_floor_frac)
                step_b = max(1, (budget_b - floor_b) // ccfg.mem_steps)
                dev_acts.append(reg.register(xctl.membudget_actuator(
                    floor_bytes=floor_b, step_bytes=step_b)).name)
            _bind(ccfg.device_rule, dev_acts, name="device-burn")
            if asm.migrator is not None:
                reg.register(xctl.rebalance_actuator(asm.migrator))
                _bind(ccfg.node_rule, ["rebalance"], name="node-burn",
                      sustain_window=ccfg.sustain_window,
                      sustain_burn=ccfg.sustain_burn)
            if cfg.disk.enabled:
                # Disk-burn → a cleanup PULSE: the watermark gate sheds
                # ingest on its own; the controller's job is to force a
                # reclaim pass the cadence wouldn't run yet.
                reg.register(xctl.emergency_cleanup_actuator(
                    lambda: db.cleanup(_time.time_ns())))
                _bind(ccfg.disk_rule, ["emergency_cleanup"],
                      name="disk-burn")
            asm.controller = xctl.Controller(
                reg, bindings, burn_source=slo.status,
                instrument=scope,
                min_interval_s=parse_duration(
                    ccfg.min_action_interval) / 1e9,
                history=xctl.BurnHistory(
                    slo.engine,
                    metric=f"{cfg.metrics_prefix}_slo_burn",
                    deadline_s=parse_duration(
                        ccfg.history_deadline) / 1e9))

        # Disk-pressure stage for the mediator: refresh the ledger every
        # pass; at/above LOW run cleanup EAGERLY (superseded volumes,
        # stale snapshots, aged quarantine, flushed commitlog segments)
        # instead of waiting out the cleanup cadence.  Shedding itself
        # happens at the ingest gates off the cached level — this stage
        # is what keeps that cache fresh.
        _disk_stage = None
        if cfg.disk.enabled:
            def _disk_stage(now: int, _db=db) -> dict:
                dsnap = _diskbudget.refresh()
                out = {"level": dsnap["level"],
                       "free_ratio": round(dsnap["free_ratio"], 4)}
                if dsnap["level_value"] >= 1:
                    out["cleanup"] = _db.cleanup(now)
                return out

        if cfg.mediator.enabled if start_mediator is None else start_mediator:
            asm.mediator = Mediator(
                db,
                tick_interval_s=parse_duration(cfg.mediator.tick_interval) / 1e9,
                snapshot_every=cfg.mediator.snapshot_every,
                cleanup_every=cfg.mediator.cleanup_every,
                scrubber=(asm.scrubber
                          if cfg.mediator.scrub_volumes > 0 else None),
                scrub_every=cfg.mediator.scrub_every,
                migrator=asm.migrator,
                migrate_every=cfg.mediator.migrate_every,
                downsampler=downsampler,
                checkpointer=asm.checkpointer,
                checkpoint_every=(cfg.coordinator.checkpoint_every
                                  if cfg.coordinator is not None else 0),
                selfmon=asm.selfmon,
                selfmon_every=cfg.selfmon.every,
                controller=asm.controller,
                controller_every=cfg.controller.every,
                diskpressure=_disk_stage,
                instrument=scope,
            )
            asm.mediator.open()

        if serve_http and cfg.coordinator is not None:
            ctx = ApiContext(
                db, namespace=cfg.coordinator.namespace, registry=registry,
                metrics_scope=scope,
                downsampler=downsampler, tracer=tracer,
                migrator=asm.migrator,
                admission=admission,
                query_timeout_s=parse_duration(cfg.query.default_timeout) / 1e9,
                slow_query_fraction=cfg.query.slow_query_fraction,
                remotes=asm.remote_stores,
                remotes_required=cfg.query.remotes_required,
                checkpointer=asm.checkpointer,
                selfmon=asm.selfmon,
                controller=asm.controller,
            )

            # Admission/slow-query observability: query_active,
            # query_shed_total etc. ride the same scrape-time collector
            # pattern as the fault/retry/breaker mirrors.
            def collect_query(_ctx=ctx) -> None:
                m = _ctx.admission.metrics()
                scope.gauge("query_active").update(m["active"])
                scope.gauge("query_queued").update(m["waiting"])
                scope.gauge("query_shed_total").update(m["shed_total"])
                scope.gauge("query_admitted_total").update(m["admitted_total"])
                scope.gauge("slow_query_total").update(_ctx.slow_query_total)

            registry.register_collector(collect_query)
            asm.http_server = serve_background(
                ctx, cfg.coordinator.listen_host, cfg.coordinator.listen_port
            )
        if (serve_http and cfg.coordinator is not None
                and cfg.coordinator.carbon_listen_port is not None):
            from m3_tpu.metrics.carbon import serve_carbon_background

            ns_name = cfg.coordinator.namespace

            def carbon_sink(docs, ts, vals, _ds=downsampler):
                # Carbon rides the same downsample-then-write path as
                # HTTP writes (the reference's carbon ingester feeds the
                # downsampler too) so rules apply regardless of ingest
                # protocol.
                keep = None
                if _ds is not None:
                    keep = _ds.write_batch(docs, ts, vals)
                if keep is not None:
                    import numpy as _np

                    idx = _np.nonzero(keep)[0]
                    if not len(idx):
                        return
                    docs = [docs[i] for i in idx]
                    ts, vals = ts[idx], vals[idx]
                from m3_tpu.storage.database import ShardNotOwnedError

                try:
                    db.write_tagged_batch(ns_name, docs, ts, vals)
                except ShardNotOwnedError:
                    # Placement-scoped node fed carbon traffic for
                    # shards it does not own: carbon has no ack channel
                    # to push back on, and the connection thread must
                    # survive (mixed batches partial-accept inside
                    # write_batch; only an ALL-unowned flush lands
                    # here).  Counted via db's shard_not_owned.
                    pass

            asm.carbon_server = serve_carbon_background(
                carbon_sink,
                cfg.coordinator.listen_host, cfg.coordinator.carbon_listen_port,
                instrument=scope,
            )
        if (serve_http and cfg.coordinator is not None
                and cfg.coordinator.admin_listen_port is not None):
            from m3_tpu.server.admin_api import (
                AdminContext, serve_admin_background,
            )

            # asm.kv was built up front (the topology watcher shares it)
            admin_ctx = AdminContext(asm.kv, db, scrubber=asm.scrubber,
                                     migrator=asm.migrator,
                                     selfmon=asm.selfmon,
                                     controller=asm.controller)
            # live-tune query limits + cache budget through runtime
            # options (runtime_options_manager.go's role)
            def _limit_applier(lim):
                def apply(value, _lim=lim):
                    _lim.limit = int(value)
                return apply

            appliers = [
                ("max_docs_matched", _limit_applier(limits.docs)),
                ("max_series_read", _limit_applier(limits.series)),
                ("max_bytes_read", _limit_applier(limits.bytes)),
                ("block_cache_max_bytes",
                 lambda v: setattr(db.block_cache, "max_bytes", int(v))),
                ("write_new_series_limit_per_sec",
                 lambda v: db.new_series_limiter.set_rate(float(v))),
            ]
            for opt, apply in appliers:
                admin_ctx.runtime.on_change(opt, apply)
                # replay the persisted value: the KV watch fired during
                # AdminContext construction, BEFORE this listener existed
                # — a restart must re-apply tuned values, not report
                # them while running untuned
                persisted = admin_ctx.runtime.get(opt)
                if persisted:
                    apply(persisted)
            asm.admin_server = serve_admin_background(
                admin_ctx, cfg.coordinator.listen_host,
                cfg.coordinator.admin_listen_port,
            )
    except BaseException:
        asm.close()
        raise
    return asm


def run_aggregator(source, clock=_time.time_ns) -> Assembly:
    """Boot a standalone aggregator from the ``aggregator:`` section of
    a YAML path/string or a NodeConfig (reference
    ``cmd/services/m3aggregator/main``): rawtcp front door -> arenas ->
    leader flush manager on the process's KV -> m3msg topic.  The
    device-boundary knobs, the registry and the tracer are wired as
    ``run_node`` wires them.  ``clock`` (nanoseconds) is what the flush
    loop ticks on and what anchors timed batches: a benchmark that
    compresses time hands in its data clock, or sets ``flush_interval``
    out of the way and calls ``asm.aggregator.tick(now)`` itself."""
    from m3_tpu.core.config import ConfigError

    cfg = source if isinstance(source, NodeConfig) else load_config(source)
    cfg.validate()
    if cfg.aggregator is None:
        raise ConfigError("run_aggregator needs an `aggregator:` section")
    from m3_tpu.aggregator.service import AggregatorService
    from m3_tpu.instrument import tracing
    from m3_tpu.instrument.procstats import install_process_collector
    from m3_tpu.x import devguard as _devguard, membudget as _membudget
    from m3_tpu.x import register_metrics

    _membudget.set_budget(cfg.device.mem_budget)
    _devguard.configure(
        failures=cfg.device.breaker_failures,
        reset_s=parse_duration(cfg.device.breaker_reset) / 1e9)
    registry = instrument.new_registry()
    scope = registry.scope(cfg.metrics_prefix)
    register_metrics(registry)
    install_process_collector(registry, scope)
    tracer = tracing.Tracer(enabled=cfg.aggregator.tracing)
    tracing.install(tracer)
    asm = Assembly(cfg, registry, None, None, None, tracer=tracer)
    try:
        asm.kv = _open_kv(cfg)
        asm.aggregator = AggregatorService(
            cfg.aggregator, asm.kv, scope=scope, tracer=tracer, clock=clock)
        if cfg.aggregator.metrics_listen_port is not None:
            asm.http_server = serve_metrics_background(
                registry, cfg.aggregator.listen_host,
                cfg.aggregator.metrics_listen_port)
    except BaseException:
        asm.close()
        raise
    return asm


def serve_metrics_background(registry, host: str, port: int):
    """``GET /metrics`` (Prometheus text) and ``/health`` for a process
    with no coordinator API."""
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path == "/metrics":
                body, ctype = (registry.render_prometheus().encode(),
                               "text/plain; version=0.0.4")
            elif self.path == "/health":
                body, ctype = b'{"ok":true}', "application/json"
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer((host, port), Handler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv
