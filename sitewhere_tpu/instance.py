"""Instance assembly + bootstrap — the application shell.

Reference: ``service-instance-management`` bootstraps a SiteWhere instance:
it writes the instance template configuration into ZooKeeper, runs Groovy
user/tenant model initializers, and sets a bootstrapped marker so init is
idempotent (``microservice/InstanceManagementMicroservice.java``,
``templates/InstanceTemplateManager.java``,
``initializer/GroovyUserModelInitializer.java``, marker logic
``Microservice.java:516-518``).  The other 18 services then assemble
themselves around that config.

Here the whole platform runs as ONE process around one device mesh, so
this module is both: the bootstrap (templates → users/tenants/datasets,
idempotent via a marker file in the data dir) and the composition root
(:class:`Instance`) that wires every component — identity, device
management, event store, state, rules, dispatcher, ingest, outbound,
commands, streams, labels — into a single lifecycle tree.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Callable, Dict, List, Optional

from sitewhere_tpu.commands.model import CommandInvocation
from sitewhere_tpu.commands.processing import CommandProcessor
from sitewhere_tpu.ids import NULL_ID, IdentityMap
from sitewhere_tpu.ingest.batcher import Batcher
from sitewhere_tpu.ingest.journal import Journal, JournalReader
from sitewhere_tpu.labels.manager import LabelGeneratorManager
from sitewhere_tpu.outbound.manager import OutboundConnectorsManager
from sitewhere_tpu.pipeline.rules import RuleManager
from sitewhere_tpu.runtime.config import Config
from sitewhere_tpu.runtime.dispatcher import PipelineDispatcher
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent
from sitewhere_tpu.security.jwt import TokenManagement
from sitewhere_tpu.security.users import UserManagement
from sitewhere_tpu.services.assets import AssetManagement
from sitewhere_tpu.services.batch_ops import BatchOperationManager
from sitewhere_tpu.services.device_management import DeviceManagement, RegistryMirror
from sitewhere_tpu.store.segmented import SegmentStore
from sitewhere_tpu.services.registration import RegistrationManager
from sitewhere_tpu.services.schedules import ScheduleManager
from sitewhere_tpu.services.streams import DeviceStreamManagement, DeviceStreamManager
from sitewhere_tpu.services.tenants import (
    MultitenantEngineManager,
    TenantEngine,
    TenantManagement,
)
from sitewhere_tpu.state.manager import DeviceStateManager
from sitewhere_tpu.state.presence import PresenceManager

logger = logging.getLogger("sitewhere_tpu.instance")


@dataclasses.dataclass
class InstanceTemplate:
    """Bootstrap template (reference instance templates: default users,
    tenants, and scripted dataset initializers — Python callables instead
    of Groovy scripts)."""

    template_id: str = "default"
    users: List[Dict[str, object]] = dataclasses.field(
        default_factory=lambda: [
            {
                "username": "admin",
                "password": "password",
                "first_name": "Admin",
                "last_name": "User",
                "authorities": ["ROLE_ADMIN"],
            }
        ]
    )
    tenants: List[Dict[str, object]] = dataclasses.field(
        default_factory=lambda: [
            {"token": "default", "name": "Default Tenant",
             "auth_token": "sitewhere1234567890"}
        ]
    )
    # dataset initializers run once per instance with the Instance as arg
    # (GroovyDeviceModelInitializer analog)
    dataset_initializers: List[Callable[["Instance"], None]] = dataclasses.field(
        default_factory=list
    )


class Instance(LifecycleComponent):
    """The composition root: one configured SiteWhere-TPU instance."""

    def __init__(self, config: Optional[Config] = None,
                 template: Optional[InstanceTemplate] = None,
                 recovery_decoder=None):
        super().__init__("instance")
        # before the first compile: the device programs take about a
        # minute each at the shipped capacity, seconds from the cache
        from sitewhere_tpu.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
        self.config = config or Config()
        self.template = template or InstanceTemplate()
        self.instance_id = self.config["instance.id"]
        self.data_dir = os.path.abspath(self.config["instance.data_dir"])
        os.makedirs(self.data_dir, exist_ok=True)

        cap = int(self.config["pipeline.registry_capacity"])
        width = int(self.config["pipeline.width"])
        n_shards = int(self.config["pipeline.n_shards"])

        # Multi-chip: one (shard, model) mesh over the visible devices; the
        # dispatcher runs the shard_map step and the batcher routes rows to
        # the owning shard (Kafka partitioning analog, SURVEY.md §2.4).
        if n_shards > 1:
            from sitewhere_tpu.parallel.mesh import make_mesh

            self.mesh = make_mesh(n_devices=n_shards)
        else:
            self.mesh = None

        # identity + security (a shared jwt secret lets peer hosts verify
        # each other's service tokens — reference: one instance-wide JWT
        # secret across all microservices)
        self.identity = IdentityMap(capacity=cap)
        self.users = UserManagement()
        jwt_secret = self.config.get("security.jwt_secret")
        self.tokens = TokenManagement(
            secret=jwt_secret.encode("utf-8") if jwt_secret else None)
        self.tenants = TenantManagement()

        # device system-of-record + device-resident mirrors
        self.mirror = RegistryMirror(capacity=cap)
        self.device_management = DeviceManagement(
            "default", self.identity, self.mirror
        )
        from sitewhere_tpu.schema import DEFAULT_EWMA_HALFLIVES_S

        ewma_halflives = tuple(self.config.get(
            "pipeline.ewma_halflives_s", DEFAULT_EWMA_HALFLIVES_S))
        self.rules = RuleManager(self.identity,
                                 ewma_halflives_s=ewma_halflives)
        self.device_state = self.add_child(DeviceStateManager(
            cap, self.identity,
            num_mtype_slots=int(self.config["pipeline.mtype_slots"]),
            tenant_id_of_device=self._tenant_ids_of_devices,
            num_ewma_scales=len(ewma_halflives),
        ))

        # instance-scoped metrics registry (the .prom exposition surface;
        # cross-cutting counters stay in metrics.global_registry()) —
        # created before the durable stores so the segment store's
        # store.* family registers here, not in the process-global one
        from sitewhere_tpu.runtime.metrics import MetricsRegistry

        self.metrics = MetricsRegistry()
        self.device_state.bind_metrics(self.metrics)

        # durable stores — the log-structured sharded segment store
        # (sitewhere_tpu/store): parallel background seal off the hot
        # path, catalog-governed retention/compaction, packed hot tier.
        # On a mesh, segment shards key to MESH shards (the registry
        # block owning each device) instead of the tenant/device hash,
        # so one egress segment's columns append into one shard buffer —
        # they never scatter across store shards host-side.
        if self.mesh is not None:
            import numpy as np

            _rows_per_shard = max(1, cap // n_shards)

            def _mesh_store_key(dev, ten, _r=_rows_per_shard, _np=np):
                return _np.asarray(dev, _np.int64) // _r

            store_shard_key = _mesh_store_key
        else:
            store_shard_key = None
        self.event_store = self.add_child(SegmentStore(
            self.data_dir,
            flush_interval_s=0.25,
            retention_s=self.config.get("events.retention_s"),
            resident_bytes=int(self.config["events.resident_bytes"]),
            n_shards=(n_shards if self.mesh is not None
                      else int(self.config["events.shards"])),
            shard_key=store_shard_key,
            seal_workers=int(self.config["events.seal_workers"]),
            hot_bytes=int(self.config["events.hot_bytes"]),
            compact_interval_s=float(
                self.config["events.compact_interval_s"]),
            metrics=self.metrics,
        ))
        self.streams = self.add_child(DeviceStreamManagement(self.data_dir))
        self.stream_manager = self.add_child(DeviceStreamManager(
            self.device_management, self.streams
        ))
        self.labels = self.add_child(LabelGeneratorManager())
        self.ingest_journal = Journal(
            self.data_dir, name="ingest",
            fsync_every=int(self.config["journal.fsync_every"]),
            segment_bytes=int(self.config["journal.segment_bytes"]),
            append_timer=self.metrics.timer("ingest.journal_append_s"),
        )
        self.dead_letters = Journal(self.data_dir, name="dead-letters")
        # terminal seal failures dead-letter instead of pinning memory /
        # blocking the commit gate forever (EventStore.flush contract)
        self.event_store.dead_letters = self.dead_letters

        # span tracing: probabilistic head sampler (reference: Jaeger 1%,
        # MicroserviceConfiguration.java:53-57) PLUS tail-based retention —
        # traces with an errored span or end-to-end latency over the
        # threshold are ALWAYS kept, so the failed and the slow are
        # inspectable even at a 1% head rate
        from sitewhere_tpu.runtime.tracing import Tracer

        tail_ms = self.config.get("tracing.tail_latency_ms", 100.0)
        self.tracer = Tracer(
            sample_rate=float(self.config.get("tracing.sample_rate", 0.01)),
            tail_errors=bool(self.config.get("tracing.tail_errors", True)),
            tail_latency_s=(float(tail_ms) / 1e3
                            if tail_ms is not None else None),
            pending_capacity=int(
                self.config.get("tracing.pending_capacity", 512)))
        # runtime-uploadable scripts (ScriptSynchronizer analog)
        from sitewhere_tpu.runtime.scripting import ScriptManager

        self.scripts = ScriptManager(self.data_dir)

        # Flight recorder (runtime/flightrec.py): always-on bounded ring
        # of per-batch records, snapshotted to JSONL on anomaly (SLO
        # burn alert, egress crash, overload transition, supervisor
        # restart) and served at /api/instance/flightrecorder.
        self.flightrec = None
        if bool(self.config.get("flightrec.enabled", True)):
            from sitewhere_tpu.runtime.flightrec import FlightRecorder

            self.flightrec = FlightRecorder(
                data_dir=self.data_dir,
                capacity=int(self.config.get("flightrec.capacity", 2048)),
                min_snapshot_interval_s=float(self.config.get(
                    "flightrec.min_snapshot_interval_s", 5.0)),
                max_snapshots=int(self.config.get(
                    "flightrec.max_snapshots", 32)),
                metrics=self.metrics,
            )

        # SLO burn-rate engine (runtime/metrics.py BurnRateEngine):
        # multi-window burn evaluation against the BASELINE.json targets
        # (1M ev/s throughput, <10ms p99, shed rate), ticked by the
        # dispatcher loop; alerts emit slo.burn spans + dump the flight
        # recorder.  slo.throughput_eps=0 disables that objective (e.g.
        # a CPU-fallback deployment that can never meet the TPU number).
        self.slo = None
        if bool(self.config.get("slo.enabled", True)):
            from sitewhere_tpu.runtime.metrics import (
                BurnRateEngine,
                SloTargets,
            )

            self.slo = BurnRateEngine(
                targets=SloTargets(
                    throughput_eps=float(self.config.get(
                        "slo.throughput_eps", 1_000_000.0)),
                    p99_ms=float(self.config.get("slo.p99_ms", 10.0)),
                    shed_rate=float(self.config.get(
                        "slo.shed_rate", 0.01))),
                windows_s=(float(self.config.get("slo.fast_window_s",
                                                 60.0)),
                           float(self.config.get("slo.slow_window_s",
                                                 600.0))),
                error_budget=float(self.config.get(
                    "slo.error_budget", 0.05)),
                alert_burn=float(self.config.get("slo.alert_burn", 2.0)),
                min_samples=int(self.config.get("slo.min_samples", 5)),
                lag_tolerance_s=float(self.config.get(
                    "slo.lag_tolerance_s", 2.0)),
                sample_interval_s=float(self.config.get(
                    "slo.sample_interval_s", 1.0)),
                sample_fn=self._slo_sample,
                metrics=self.metrics,
                tracer=self.tracer,
                on_alert=self._on_slo_alert,
            )
        self._slo_last = {"processed": 0, "shed": 0, "admitted": 0,
                          "at": None}
        import threading as _threading

        # serializes the jax.profiler start/stop check-then-act pair
        self._profiler_lock = _threading.Lock()
        self._profiler_dir: Optional[str] = None

        # Overload control (runtime/overload.py): a watermark-driven
        # state machine over signals the pipeline already exports.  The
        # dispatcher ticks it every loop cycle; admission at ingest and
        # the degradation ladder (labels, analytics/search endpoints,
        # non-priority outbound fan-out) hang off its state.  Journal
        # append + seal + checkpoint are NEVER gated by it.
        self.overload = None
        if bool(self.config.get("overload.enabled", True)):
            from sitewhere_tpu.runtime.overload import (
                OverloadController,
                Watermarks,
            )

            from sitewhere_tpu.runtime.overload import TenantBudgets

            self.overload = OverloadController(
                watermarks=Watermarks().replace(
                    self.config.get("overload.watermarks") or {}),
                cooldown_s=float(self.config.get("overload.cooldown_s", 2.0)),
                hysteresis=float(self.config.get("overload.hysteresis", 0.7)),
                confirm_samples=int(self.config.get(
                    "overload.confirm_samples", 2)),
                sample_interval_s=float(self.config.get(
                    "overload.sample_interval_s", 0.1)),
                retry_after_s=float(self.config.get(
                    "overload.retry_after_s", 1.0)),
                degraded_telemetry_rate_per_s=float(self.config.get(
                    "overload.degraded_telemetry_rate_per_s", 10_000.0)),
                degraded_telemetry_burst=float(self.config.get(
                    "overload.degraded_telemetry_burst", 20_000.0)),
                budget_refresh_s=float(self.config.get(
                    "overload.budget_refresh_s", 5.0)),
                signals_fn=self._overload_signals,
                metrics=self.metrics,
                tracer=self.tracer,
            )
            # per-tenant budget overlays (tenants.<token>.overload.*):
            # configured ceilings that compose with — never replace —
            # the ledger's measured-share scaling (min of the two)
            self.overload.set_tenant_budgets(
                TenantBudgets.from_config(self.config.get("tenants")))
            self.labels.load_gate = self.overload.allow_optional
            if self.flightrec is not None:
                # every ladder move dumps the recorder: the batches
                # surrounding a transition are the evidence items 1-2
                # of the roadmap tune against
                self.overload.on_transition(
                    lambda old, new, signals: self._flightrec_dump_async(
                        f"overload-{new.name.lower()}",
                        f"{old.name}->{new.name}"))

        # Tenant metering plane (runtime/metering.py): sliding-window
        # per-tenant usage ledger fed by (a) the packed step's tenant
        # scatter block — riding the existing D2H fetch, zero extra
        # syncs — and (b) host-side charges from shed/dead-letter/seal/
        # outbound/analytics paths.  Feeds measured share back into the
        # overload ladder's DEGRADED per-tenant rate limits and exports
        # the governed ``tenant.*`` metric family.
        self.usage_ledger = None
        if bool(self.config.get("metering.enabled", True)):
            from sitewhere_tpu.runtime.metering import UsageLedger

            self.usage_ledger = UsageLedger(
                top_k=int(self.config.get("metering.top_k", 32)),
                window_s=float(self.config.get("metering.window_s", 60.0)),
                fair_share_frac=float(self.config.get(
                    "metering.fair_share_frac", 0.25)),
                min_rate_frac=float(self.config.get(
                    "metering.min_rate_frac", 0.1)),
            )
            self.usage_ledger.bind_metrics(
                self.metrics, resolve=self.identity.tenant.token_of)
            if self.overload is not None:
                self.overload.set_usage_ledger(
                    self.usage_ledger, resolve=self._tenant_dense_id)
            self.event_store.usage_ledger = self.usage_ledger

        # Metered quotas (runtime/metering.py QuotaTable): per-tenant
        # rule/analytics eval-seconds budgets over the ledger's sliding
        # window — deprioritize (live rows skipped) then refuse (429)
        # as the window fills; NEVER consulted on the ingest hot path.
        self.quotas = None
        if self.usage_ledger is not None and bool(self.config.get(
                "metering.quota.enabled", True)):
            from sitewhere_tpu.runtime.metering import QuotaTable

            self.quotas = QuotaTable(
                self.usage_ledger,
                default_eval_s=self.config.get(
                    "metering.quota.eval_s_per_window"),
                soft_frac=float(self.config.get(
                    "metering.quota.soft_frac", 0.8)),
                metrics=self.metrics,
            )
            tenants_cfg = self.config.get("tenants")
            if isinstance(tenants_cfg, dict):
                for tok, overlay in tenants_cfg.items():
                    quota = (overlay.get("quota")
                             if isinstance(overlay, dict) else None)
                    if isinstance(quota, dict) \
                            and "eval_s_per_window" in quota:
                        self.quotas.set_quota(
                            self._tenant_dense_id(str(tok)),
                            float(quota["eval_s_per_window"]))

        # Tenant-partitioned device-state views (state/manager.py
        # TenantPartitions): pow2 rung ladders per tenant over the
        # registry mirror's tenant column, so one tenant's registration
        # churn resizes/recompiles only its own partition view
        _mirror = self.mirror

        def _tenant_column():
            import numpy as np

            return np.where(_mirror.active, _mirror.tenant_id, NULL_ID)

        self.device_state.attach_partitions(
            _tenant_column,
            min_capacity=int(self.config.get(
                "state.partition_min_capacity", 64)),
            metrics=self.metrics)

        # domain services the dispatcher egresses into — registered as
        # children BEFORE it so the reverse-order stop keeps them alive
        # through the dispatcher's shutdown flush
        self.assets = AssetManagement("default", self.identity)
        self.commands = self.add_child(CommandProcessor(
            self.device_management,
            on_undelivered=self._on_undelivered_command,
            metrics=self.metrics,
        ))
        self.batch_ops = self.add_child(BatchOperationManager(
            self.device_management, self.commands,
            throttle_delay_ms=int(self.config.get(
                "batch.throttle_delay_ms", 0)),
        ))
        self.schedules = self.add_child(ScheduleManager(executors={
            "CommandInvocation": self._run_scheduled_invocation,
            "BatchCommandInvocation": self._run_scheduled_batch,
        }))
        # per-tenant engine lifecycle over the SHARED tensors (reference:
        # MultitenantMicroservice.java:242-260,358-380 — engine per tenant,
        # independent restart); engines share the instance identity map so
        # their dense tenant ids match the pipeline's tenant column
        self.engines = self.add_child(MultitenantEngineManager(
            self.tenants,
            engine_factory=self._make_tenant_engine,
            tenant_ids=self.identity,
        ))
        self.outbound = self.add_child(
            OutboundConnectorsManager(metrics=self.metrics,
                                      overload=self.overload))
        self.outbound.usage_ledger = self.usage_ledger
        # Streaming analytics & CEP (analytics/ subsystem): registered
        # Window/Session/Pattern queries compile once and run BOTH on
        # the live enriched batches (dispatcher egress offers them to
        # the runner's worker; sheds from SHEDDING as a non-priority
        # consumer) and retrospectively over the sealed event store
        # (REST-gated from DEGRADED like the other analytics surfaces).
        # Added before the dispatcher so the reverse-order stop keeps it
        # alive through the dispatcher's shutdown flush.
        self.analytics = None
        if bool(self.config.get("analytics.enabled", True)):
            from sitewhere_tpu.analytics.runner import QueryRunner

            self.analytics = self.add_child(QueryRunner(
                capacity=cap,
                resolve_mtype=self.identity.mtype.mint,
                event_store=self.event_store,
                outbound=self.outbound,
                overload=self.overload,
                metrics=self.metrics,
                tracer=self.tracer,
                max_queries=int(self.config.get(
                    "analytics.max_queries", 32)),
                max_matches=int(self.config.get(
                    "analytics.max_matches", 1024)),
                queue_depth=int(self.config.get(
                    "analytics.queue_depth", 64)),
                fanout_matches=bool(self.config.get(
                    "analytics.fanout_matches", True)),
            ))
            self.analytics.usage_ledger = self.usage_ledger
            self.analytics.quotas = self.quotas
        # Bring-your-own-rules (rules/ subsystem): per-tenant declarative
        # rule & enrichment programs compiled into per-structure batched
        # kernels.  Same egress-offer lifecycle as analytics — added
        # before the dispatcher so the reverse-order stop keeps the
        # engine draining through the dispatcher's shutdown flush.
        self.rule_engine = None
        if bool(self.config.get("rules.programs_enabled", True)):
            from sitewhere_tpu.rules.engine import RuleEngineRunner

            self.rule_engine = self.add_child(RuleEngineRunner(
                capacity=cap,
                n_mtype_slots=int(self.config.get(
                    "pipeline.mtype_slots", 8)),
                asset_capacity=int(self.config.get(
                    "rules.asset_capacity", 1024)),
                resolve_mtype=self.identity.mtype.mint,
                resolve_alert=self.identity.alert_type.mint,
                overload=self.overload,
                metrics=self.metrics,
                programs_per_tenant=int(self.config.get(
                    "rules.programs_per_tenant", 4)),
                max_programs=int(self.config.get(
                    "rules.max_programs", 262144)),
                queue_depth=int(self.config.get(
                    "rules.queue_depth", 64)),
            ))
            self.rule_engine.usage_ledger = self.usage_ledger
            self.rule_engine.quotas = self.quotas
        self.registration = self.add_child(RegistrationManager(
            self.device_management,
            default_device_type=self.config.get("registration.default_device_type"),
            allow_new_devices=bool(
                self.config.get("registration.allow_new_devices", True)
            ),
        ))

        # dispatch
        # Adaptive emission window (overlapped host pipeline): the
        # configured deadline is the ANCHOR; the controller shrinks the
        # window under idle traffic (chasing the <10ms p99 SLO) and grows
        # it under backlog (chasing full-width batches).  Disable with
        # pipeline.adaptive_deadline=false for a fixed window.
        controller = None
        if bool(self.config.get("pipeline.adaptive_deadline", True)):
            from sitewhere_tpu.ingest.batcher import AdaptiveBatchController

            controller = AdaptiveBatchController(
                deadline_ms=float(self.config["pipeline.deadline_ms"]),
                min_ms=self.config.get("pipeline.deadline_min_ms"),
                max_ms=self.config.get("pipeline.deadline_max_ms"),
                metrics=self.metrics,
            )
        self.batcher = Batcher(
            width=width,
            n_shards=n_shards,
            registry_capacity=cap,
            resolve_device=self.identity.device.lookup,
            resolve_mtype=self.identity.mtype.mint,
            resolve_alert=self.identity.alert_type.mint,
            invocations=self.identity.invocation,
            deadline_ms=float(self.config["pipeline.deadline_ms"]),
            metrics=self.metrics,
            controller=controller,
        )
        # Decode worker pool (overlapped host pipeline, stage 1): wire
        # payloads decode on these workers while earlier windows are on
        # device; per-source lanes keep delivery in submission order.
        # ingest.decode_workers=0 disables (synchronous decode).
        from sitewhere_tpu.ingest.sources import DecodePool

        decode_workers = int(self.config.get("ingest.decode_workers", 2))
        self.decode_pool = (
            DecodePool(workers=decode_workers,
                       max_pending=int(self.config.get(
                           "ingest.decode_max_pending", 128)),
                       metrics=self.metrics)
            if decode_workers > 0 else None)
        self.dispatcher = self.add_child(PipelineDispatcher(
            batcher=self.batcher,
            registry_provider=self.mirror.publish_registry,
            state_manager=self.device_state,
            rules_provider=self.rules.publish,
            zones_provider=self.mirror.publish_zones,
            event_store=self.event_store,
            outbound=self.outbound,
            registration=self.registration,
            on_command_rows=self._on_command_rows,
            analytics=self.analytics,
            rules_engine=self.rule_engine,
            journal=self.ingest_journal,
            dead_letters=self.dead_letters,
            resolve_tenant=self._tenant_dense_id,
            on_host_request=self._on_host_request,
            inflight_depth=int(self.config.get("pipeline.inflight_depth", 0)),
            egress_offload=self.config.get("pipeline.egress_offload"),
            # Device-resident dispatch ring (pipeline/packed.py
            # build_packed_chain): unset → backend-adaptive (8 on TPU,
            # off elsewhere); 0/1 disables; ≥2 forces — the tier-1 CPU
            # smoke forces 2 so the chained path runs on every backend.
            ring_depth=(int(self.config["pipeline.ring_depth"])
                        if self.config.get("pipeline.ring_depth")
                        is not None else None),
            mesh=self.mesh,
            journal_reader=JournalReader(self.ingest_journal, "pipeline"),
            recovery_decoder=recovery_decoder,
            tracer=self.tracer,
            metrics=self.metrics,
            overload=self.overload,
            flightrec=self.flightrec,
            slo=self.slo,
            quarantine_after=int(self.config.get(
                "pipeline.quarantine_after", 3)),
            cost_analysis=self.config.get("telemetry.cost_analysis"),
            usage_ledger=self.usage_ledger,
        ))
        if self.rule_engine is not None:
            # fired tenant programs re-enter the pipeline as first-class
            # ALERT events through the dispatcher's derived-alert edge
            self.rule_engine.inject = self.dispatcher.inject_rule_alerts
        self.presence = self.add_child(PresenceManager(
            self.device_state,
            check_interval_s=float(self.config["presence.scan_interval_s"]),
            missing_after_s=int(self.config["presence.missing_after_s"]),
            on_state_changes=self._on_presence_changes,
        ))
        self.sources: List[LifecycleComponent] = []
        self._config_sources_built = False

        # cross-host fabric (rpc/ package; sitewhere-grpc-client analog):
        # the server publishes this instance's domain surface; a 2+ entry
        # peers list additionally turns on keyed forwarding so every
        # ingest row lands on the host that owns its device's shard
        # (SURVEY.md §2.4 — Kafka partition-leadership at the host plane)
        self.rpc_server = None
        self.forwarder = None
        peers: List[str] = list(self.config.get("rpc.peers") or [])
        if bool(self.config.get("rpc.server.enabled")) or peers:
            from sitewhere_tpu.rpc import RpcServer, bind_instance

            self.rpc_server = self.add_child(RpcServer(
                host=str(self.config.get("rpc.server.host", "127.0.0.1")),
                port=int(self.config.get("rpc.server.port", 0)),
                tokens=self.tokens, tracer=self.tracer,
                metrics=self.metrics))
            bind_instance(self.rpc_server, self)
            if self.overload is not None:
                # overload piggyback on every RPC response header: busy
                # fabrics learn this host's pressure at call rate,
                # faster than the fleet heartbeat period
                self.rpc_server.overload_provider = (
                    lambda: (int(self.overload.state),
                             self.overload.retry_after()))
        if len(peers) > 1:
            from sitewhere_tpu.rpc import HostForwarder, RpcDemux

            process_id = int(self.config.get("rpc.process_id", 0))
            if not 0 <= process_id < len(peers):
                raise ValueError(
                    f"rpc.process_id {process_id} outside peers list")
            if not jwt_secret:
                # without a shared secret every forwarded batch would be
                # rejected as unauthorized and dead-lettered — fail at
                # boot, not silently at runtime
                raise ValueError(
                    "multi-host (rpc.peers) requires a shared "
                    "security.jwt_secret so peers can verify each "
                    "other's service tokens")

            def _system_jwt() -> str:
                # service-to-service identity (reference SystemUserRunnable)
                return self.tokens.mint("system", ["ROLE_ADMIN"])

            self._peer_demuxes = {
                p: (None if p == process_id
                    else RpcDemux([ep], token_provider=_system_jwt))
                for p, ep in enumerate(peers)
            }
            self.forwarder = self.add_child(HostForwarder(
                self.dispatcher, process_id, self._peer_demuxes,
                dead_letters=self.dead_letters,
                deadline_ms=float(self.config.get(
                    "rpc.forward_deadline_ms", 25.0)),
                data_dir=self.data_dir,
                tracer=self.tracer,
                metrics=self.metrics,
                overload=self.overload,
                heartbeat_interval_s=float(self.config.get(
                    "rpc.heartbeat_interval_s", 0.5)),
                call_timeout_s=float(self.config.get(
                    "rpc.call_timeout_s", 10.0)),
                # hung-step watchdog flag on every beat: peers park
                # forwards toward a host whose device tier is wedged —
                # plus the mesh-shard attribution so a single sick
                # shard's wedge doesn't park the whole host
                device_unhealthy=lambda: self.dispatcher.device_unhealthy,
                device_unhealthy_shards=(
                    lambda: self.dispatcher.device_unhealthy_shards)))
        else:
            self._peer_demuxes = {}
        self._rpc_peers = list(peers)
        if self._peer_demuxes:
            # live endpoint reload (the Consul-watch analog): a peer that
            # moved hosts/ports picks up on config.reload() without a
            # restart.  Changing the NUMBER of peers changes device
            # ownership (rendezvous hash over P) and requires a restart —
            # reject it rather than silently split streams.
            self.config.on_change(self._on_peers_changed)

        # event search (service-event-search analog): the local store is
        # the built-in index; in a multi-host topology every peer's store
        # is a remote index and "federated" fans out + merges newest-first
        from sitewhere_tpu.outbound.search import (
            EventSearchProvider,
            FederatedSearchProvider,
            RemoteSearchProvider,
            SearchProvidersManager,
            TokenSearchAdapter,
        )

        self.search_providers = SearchProvidersManager(
            [EventSearchProvider("local", self.event_store)])
        if self._peer_demuxes:
            local_adapter = TokenSearchAdapter(
                "local", self.event_store, self.identity,
                self.device_management)
            legs = [local_adapter] + [
                RemoteSearchProvider(f"peer-{p}", demux)
                for p, demux in sorted(self._peer_demuxes.items())
                if demux is not None
            ]
            for leg in legs[1:]:
                self.search_providers.add_provider(leg)
            self.search_providers.add_provider(
                FederatedSearchProvider("federated", legs))

        # checkpoint/resume (SURVEY.md §5): restore the newest complete
        # snapshot BEFORE start so devices/assignments/users/tenants/rules,
        # DeviceState AND live analytics/CEP operator state survive a
        # restart; the journal replay in start() then re-derives anything
        # journaled after each component's snapshotted as-of offset.
        from sitewhere_tpu.runtime.checkpoint import (
            Checkpointer,
            StateProvider,
        )

        self._engine_snapshots: Dict[str, dict] = {}
        self._dedup_snapshot: Dict[str, list] = {}
        self.checkpointer = self.add_child(Checkpointer(
            self,
            interval_s=float(self.config.get("checkpoint.interval_s", 30.0)),
            prune_journal=bool(self.config.get(
                "journal.prune_after_checkpoint", False)),
        ))
        # a stall record says whether a save overlapped it
        self.dispatcher.stall_witness.save_probe = \
            self.checkpointer.saving_within
        if self.analytics is not None:
            # live query/CEP state: open windows, rings, sessions,
            # pattern stages — carried with its exact applied offset
            self.checkpointer.register_provider(StateProvider(
                name="analytics",
                snapshot_fn=self.analytics.snapshot_state,
                restore_fn=self.analytics.restore_state,
                version=1))
        if self.rule_engine is not None:
            # tenant rule programs + attribute tables (docs are the
            # durable identity; operand tables and kernels rebuild on
            # the first post-restore publish)
            self.checkpointer.register_provider(StateProvider(
                name="rule-programs",
                snapshot_fn=self.rule_engine.snapshot_state,
                restore_fn=self.rule_engine.restore_state,
                version=1))
        # ingest dedup tables + forward-spool cursors (the spools
        # themselves are already durable journals; the cursor record is
        # observability for the recovery report)
        self.checkpointer.register_provider(StateProvider(
            name="runtime",
            snapshot_fn=self._snapshot_runtime_state,
            restore_fn=self._restore_runtime_state,
            version=1))
        # segment-store catalog manifest: rides the same CRC-framed,
        # generation-committed snapshot protocol; restore cross-checks
        # the directory-rebuilt catalog against the last committed
        # generation's view and exports the drift as a gauge
        from sitewhere_tpu.store.catalog import catalog_state_provider

        self.checkpointer.register_provider(
            catalog_state_provider(self.event_store))
        if self.usage_ledger is not None:
            # tenant usage totals + heavy-hitter/count-min sketches; the
            # sliding window deliberately restarts empty (shares describe
            # CURRENT load, not pre-restart load)
            self.checkpointer.register_provider(StateProvider(
                name="tenant-metering",
                snapshot_fn=self.usage_ledger.snapshot_payload,
                restore_fn=self.usage_ledger.restore_payload,
                version=1))
        self.restored = self.checkpointer.restore()

    # -- wiring helpers -----------------------------------------------------

    def _snapshot_runtime_state(self):
        """Checkpoint section for the small volatile runtime tables: the
        per-source ingest dedup LRUs (so a restart doesn't re-admit the
        duplicates the window had already caught) and the forward-spool
        committed cursors (informational — the spools are durable
        journals with their own offset files)."""
        import pickle

        dedup: Dict[str, list] = {}
        for src in self.sources:
            d = getattr(src, "deduplicator", None)
            if d is not None and hasattr(d, "export_keys"):
                dedup[src.name] = d.export_keys()
        spools: Dict[str, int] = {}
        if self.forwarder is not None:
            spools = {
                str(p): int(r.committed)
                for p, r in getattr(self.forwarder, "_spool_readers",
                                    {}).items()
            }
        return (pickle.dumps({"dedup": dedup, "spools": spools},
                             protocol=4), None)

    def _restore_runtime_state(self, header, payload) -> None:
        import pickle

        doc = pickle.loads(payload)
        # sources attach after __init__ — add_source hydrates from this
        self._dedup_snapshot = dict(doc.get("dedup") or {})

    def _on_peers_changed(self, config) -> None:
        from sitewhere_tpu.rpc.wire import parse_endpoint

        new_peers = list(config.get("rpc.peers") or [])
        # validate EVERY endpoint before touching any demux: a typo'd
        # port must not leave the fleet half-updated
        try:
            for ep in new_peers:
                parse_endpoint(str(ep))
        except ValueError as e:
            logger.error("rpc.peers reload rejected: %s", e)
            return
        old_peers = self._rpc_peers
        if len(new_peers) != len(old_peers):
            logger.error(
                "rpc.peers count changed %d -> %d: device ownership "
                "(rendezvous over P) would shift — restart required; "
                "keeping the old endpoints",
                len(old_peers), len(new_peers))
            return
        # A reorder of EXISTING endpoints rebinds process ids to
        # different hosts — the same ownership shift as a count change
        # (devices of process p would ship to a host that believes it is
        # process q).  A host MOVING keeps its index; an address already
        # bound to another index (including our own) may not reappear at
        # a changed one.
        for p, ep in enumerate(new_peers):
            if ep != old_peers[p] and ep in old_peers:
                logger.error(
                    "rpc.peers reorder detected (%s moved from index %d "
                    "to %d): process-id/host binding would shift — "
                    "restart required; keeping the old endpoints",
                    ep, old_peers.index(ep), p)
                return
        for p, demux in self._peer_demuxes.items():
            if demux is not None and demux.endpoints != [new_peers[p]]:
                logger.info("peer %d endpoint -> %s", p, new_peers[p])
                demux.set_endpoints([new_peers[p]])
        self._rpc_peers = new_peers

    def apply_membership_change(self, new_peers: List[str],
                                process_id: Optional[int] = None) -> dict:
        """Adopt a NEW peers list whose COUNT may differ — the explicit
        ops path for cluster grow/shrink (the config reload deliberately
        rejects count changes; see ``_on_peers_changed``).

        Sequence (reference: Kafka consumer rebalance + demux discovery
        add/remove, ``ApiDemux.java`` DiscoveryMonitor):

        1. build demuxes for the new endpoints (reusing live channels
           for endpoints that did not move);
        2. requeue every pending forwarded row under the new ownership
           (:meth:`HostForwarder.apply_membership` — a departed peer's
           spool drains to the rows' new owners);
        3. hand off locally-owned devices whose new owner is elsewhere
           (:func:`sitewhere_tpu.rpc.migration.migrate_out` — registry
           rows + newest-wins DeviceState over ``migration.import``).

        Returns the handoff summary.  Every host in the fleet must apply
        the SAME list (ownership is the rendezvous hash over it).
        """
        from sitewhere_tpu.rpc import RpcDemux
        from sitewhere_tpu.rpc.migration import migrate_out
        from sitewhere_tpu.rpc.wire import parse_endpoint
        from sitewhere_tpu.services.common import ValidationError

        for ep in new_peers:
            parse_endpoint(str(ep))
        if process_id is None:
            process_id = self._process_id()
        old_n = max(len(self._rpc_peers), 1)
        if not 0 <= process_id < len(new_peers):
            raise ValueError(
                f"process_id {process_id} outside new peers list")

        def _system_jwt() -> str:
            return self.tokens.mint("system", ["ROLE_ADMIN"])

        old_by_endpoint = {}
        for p, ep in enumerate(self._rpc_peers):
            demux = self._peer_demuxes.get(p)
            if demux is not None:
                old_by_endpoint[ep] = demux
        new_demuxes = {}
        for p, ep in enumerate(new_peers):
            if p == process_id:
                new_demuxes[p] = None
            elif ep in old_by_endpoint:
                new_demuxes[p] = old_by_endpoint.pop(ep)
            else:
                new_demuxes[p] = RpcDemux([ep], token_provider=_system_jwt)

        if self.forwarder is not None:
            self.forwarder.apply_membership(new_demuxes,
                                            process_id=process_id)
        elif len(new_peers) > 1:
            # A standalone instance has its protocol sources wired
            # straight to the dispatcher and (usually) no RpcServer for
            # peers to deliver to — conjuring a forwarder here would
            # leave every attached source bypassing it, splitting device
            # streams across hosts.  Multi-host membership starts at
            # boot (rpc.peers); this API then grows/shrinks it.
            raise ValidationError(
                "this instance booted standalone (no rpc.peers); "
                "restart it with rpc.peers + rpc.server.enabled to "
                "join a fleet")
        self._peer_demuxes = new_demuxes
        self._rpc_peers = list(new_peers)
        self.config.set("rpc.peers", list(new_peers))
        self.config.set("rpc.process_id", process_id)
        # closed-over demuxes for endpoints that left the fleet
        for demux in old_by_endpoint.values():
            try:
                demux.close()
            except Exception:
                logger.exception("old peer demux close failed")

        summary = migrate_out(self, old_n, len(new_peers), process_id,
                              new_demuxes)
        logger.info("membership change to %d peers: %s",
                    len(new_peers), summary)
        return summary

    def _process_id(self) -> int:
        return int(self.config.get("rpc.process_id", 0))

    def _overload_signals(self):
        """One sample of the pressure signals the overload controller
        watches — all of them gauges/counters the system already
        exports, read lock-free (a slightly stale read only delays a
        transition by one sample)."""
        from sitewhere_tpu.runtime.overload import OverloadSignals

        d = self.dispatcher
        pool = self.decode_pool
        decode_backlog = (pool.pending / pool.max_pending
                          if pool is not None and pool.max_pending else 0.0)
        # ingest→seal lag comes from the LIVE watermark (age of the
        # oldest unsealed event), not the last-value seal gauge — the
        # gauge pins historical spikes (a jit compile's 3s seal) for as
        # long as anything is busy, which would read as sustained
        # overload; the live measure self-decays as work seals.
        return OverloadSignals(
            seal_lag_s=d.oldest_unsealed_wait_s(),
            decode_backlog=decode_backlog,
            # ring-held plans are emitted-but-unstepped work the egress
            # window hasn't seen yet — in-flight pressure all the same
            egress_inflight=((len(d._inflight) + len(d._ring))
                             / max(1, d.egress_queue_depth)),
            batcher_backlog=self.batcher.pending / max(1, self.batcher.width),
            fsync_latency_s=float(self.ingest_journal.last_fsync_s),
        )

    def _slo_sample(self):
        """One SLO burn-rate sample: counter DELTAS since the previous
        sample (events processed, shed vs admitted) plus the rolling p99
        — the engine judges each delta against the BASELINE targets."""
        import time as _time

        now = _time.monotonic()
        last = self._slo_last
        snap = self.dispatcher.metrics_snapshot()
        processed = int(snap.get("processed", 0))
        shed = (int(self.overload.shed_total)
                if self.overload is not None else 0)
        admitted = (int(self.overload.admitted_total)
                    if self.overload is not None else processed)
        sample = None
        if last["at"] is not None:
            events = processed - last["processed"]
            sample = {
                "events": events,
                "elapsed_s": max(1e-9, now - last["at"]),
                # the rolling p99 is only evidence while traffic flows:
                # the latency reservoir is never time-pruned, so after a
                # burst it would keep reporting the burst's percentile
                # forever and an idle instance would read as burning
                "p99_ms": (snap.get("latency_p99_ms")
                           if events > 0 else None),
                "shed": shed - last["shed"],
                "admitted": admitted - last["admitted"],
                # queue SNAPSHOT (not a delta): the engine's wedge
                # witness for deployments whose admitted counter aliases
                # processed (overload disabled) — rows pending while
                # nothing completes judges as a stall, never as idle
                "backlog": int(snap.get("pending_rows", 0)),
            }
        self._slo_last = {"processed": processed, "shed": shed,
                          "admitted": admitted, "at": now}
        return sample

    def _flightrec_dump_async(self, reason: str, detail: str) -> None:
        """Anomaly dump OFF the calling thread: overload transitions and
        SLO alerts fire on the dispatcher loop, and a snapshot is a file
        write — during a disk-stressed incident (slow fsync is itself an
        overload signal) an inline dump would stall the dispatch loop at
        the exact moment it is overloaded.  The per-reason rate limit is
        checked inside anomaly(), so a storm spawns counted no-op
        threads, not files."""
        import threading as _threading

        _threading.Thread(
            target=lambda: self.flightrec.anomaly(reason, detail=detail),
            daemon=True, name="flightrec-dump").start()

    def _on_slo_alert(self, objective: str, burn: float) -> None:
        """A burn alert armed: stamp the tail sampler (traces around
        the breach are retained) and dump the flight recorder."""
        note = getattr(self.tracer, "note_anomaly", None)
        if note is not None:
            note()
        if self.flightrec is not None:
            self._flightrec_dump_async(f"slo-{objective}",
                                       f"burn {burn:.2f}x budget")

    def run_device_profile(self, iters: int = 16,
                           repeats: int = 3) -> dict:
        """On-demand device-stage calibration (the fori-chain probes of
        ``pipeline/telemetry.py`` at the size of the step ONE chip runs):
        records ``device.stage_ms.*`` histogram samples and returns the
        stage medians.  Compiles one probe chain per stage — seconds of
        work; REST exposes it admin-only for exactly that reason.

        On a mesh (``pipeline.n_shards`` > 1) every chip steps its own
        shard — ``width // n_shards`` batch rows against
        ``registry_capacity // n_shards`` registry rows — so that share
        is what the probe runs, on one chip (the default device), and
        what the watchdog's budgets are calibrated from.  The whole
        registry on one chip is a step no chip of the mesh runs, and at
        the capacities a mesh exists for it does not fit one.  The
        collective (a psum of a few hundred scalars) is not in the
        probe.  With one shard the share is the whole."""
        from sitewhere_tpu.pipeline.telemetry import profile_device_stages

        # the LIVE table shapes: rule/zone eval cost is shape-driven, so
        # the probes must run at this deployment's actual capacities
        rules = self.rules.publish()
        zones = self.mirror.publish_zones()
        n_shards = int(self.config["pipeline.n_shards"])
        result = profile_device_stages(
            width=int(self.config["pipeline.width"]) // n_shards,
            capacity=(int(self.config["pipeline.registry_capacity"])
                      // n_shards),
            rules_capacity=int(rules.threshold.shape[0]),
            zones_capacity=int(zones.nvert.shape[0]),
            iters=iters, repeats=repeats, metrics=self.metrics)
        full_ms = result.get("full_ms")
        if full_ms:
            # re-anchor the hung-step watchdog's soft/hard budgets to
            # the MEASURED per-step device time (floored inside
            # calibrate so a CPU test host never false-trips)
            self.dispatcher.watchdog.calibrate(float(full_ms))
        return result

    def start_profiler_capture(self) -> dict:
        """Start an on-demand ``jax.profiler`` trace into the data dir
        (the device-side flamegraph an operator opens in TensorBoard /
        XProf).  One capture at a time; returns the trace directory."""
        import time as _time

        import jax as _jax

        from sitewhere_tpu.services.common import ValidationError

        # the lock makes check-then-start atomic: two racing starts must
        # yield one capture and one honest "already running" error, not
        # a misdiagnosed "profiler unavailable" from the loser
        with self._profiler_lock:
            if getattr(self, "_profiler_dir", None):
                raise ValidationError(
                    "profiler capture already running: "
                    f"{self._profiler_dir}")
            trace_dir = os.path.join(
                self.data_dir, "profiles", f"capture-{int(_time.time())}")
            os.makedirs(trace_dir, exist_ok=True)
            try:
                _jax.profiler.start_trace(trace_dir)
            except Exception as e:
                raise ValidationError(f"jax profiler unavailable: {e}")
            self._profiler_dir = trace_dir
        logger.info("jax profiler capture started -> %s", trace_dir)
        return {"capturing": True, "trace_dir": trace_dir}

    def stop_profiler_capture(self) -> dict:
        import jax as _jax

        from sitewhere_tpu.services.common import ValidationError

        with self._profiler_lock:
            trace_dir = getattr(self, "_profiler_dir", None)
            if not trace_dir:
                raise ValidationError("no profiler capture running")
            try:
                _jax.profiler.stop_trace()
            except Exception as e:
                # keep _profiler_dir: a failed stop must stay retryable
                # — clearing it first would wedge BOTH endpoints (stop
                # says "nothing running", start "already started")
                raise ValidationError(f"profiler stop failed: {e}")
            self._profiler_dir = None
        logger.info("jax profiler capture stopped (%s)", trace_dir)
        return {"capturing": False, "trace_dir": trace_dir}

    def _tenant_dense_id(self, token: str) -> int:
        return self.identity.tenant.mint(token)

    def _make_tenant_engine(self, tenant, tenant_id: int,
                            config: Dict[str, object]) -> TenantEngine:
        """Engine factory: per-tenant service façades over the instance's
        shared identity map + registry mirror, with per-tenant config
        overlays from ``tenants.<token>`` in the instance config."""
        overlay = dict(config)
        per_tenant = self.config.get(f"tenants.{tenant.token}", None)
        if isinstance(per_tenant, dict):
            overlay.update(per_tenant)
        if tenant.token == "default":
            # the instance-level services ARE the default tenant's engine
            return TenantEngine(
                tenant, tenant_id, overlay,
                identity=self.identity, mirror=self.mirror,
                device_management=self.device_management,
                asset_management=self.assets,
            )
        engine = TenantEngine(
            tenant, tenant_id, overlay,
            identity=self.identity, mirror=self.mirror,
        )
        # checkpoint resume: hydrate the engine's host dicts (its rows in
        # the shared tensors were restored with the mirror snapshot).
        # `.get`, not `.pop` — the snapshot must survive for a later
        # rebuild-restart or a failed-then-retried engine start.
        snap = getattr(self, "_engine_snapshots", {}).get(tenant.token)
        if snap:
            from sitewhere_tpu.runtime.checkpoint import merge_store

            merge_store(engine.device_management,
                        snap.get("device_management", {}))
            merge_store(engine.asset_management, snap.get("assets", {}))
        return engine

    def _tenant_ids_of_devices(self, device_ids):
        # the mirror's host column: the published registry is its copy
        # on the device, and a report must not fetch a registry-sized
        # column back for a few hundred rows
        return self.mirror.tenant_id[device_ids]

    def _on_presence_changes(self, cols) -> None:
        """Re-inject a sweep's STATE_CHANGE rows (host columns) as
        first-class events through the columnar intake edge."""
        self.dispatcher.ingest_arrays(**cols)

    def _on_command_rows(self, cols, mask, trace=None) -> None:
        """Deliver pipeline COMMAND_INVOCATION events (reference:
        enriched-command-invocations → command-delivery, SURVEY.md §3.4).

        The tensor row carries only dense handles; the command token +
        parameters live in the journaled source payload (``payload_ref``).
        Rows without a resolvable command spec dead-letter.
        """
        from sitewhere_tpu.ingest.journal import CorruptJournal

        refs = cols["payload_ref"][mask]
        device_ids = cols["device_id"][mask]
        for ref, dev in zip(refs, device_ids):
            invocation = None
            try:
                if int(ref) != NULL_ID:
                    doc = json.loads(self.ingest_journal.read_one(int(ref)))
                    body = doc.get("request", doc)
                    command = body.get("commandToken")
                    if command:
                        assignment = body.get("assignmentToken")
                        if not assignment:
                            token = self.identity.device.token_of(int(dev))
                            active = (self.device_management
                                      .get_active_assignment(token)
                                      if token else None)
                            assignment = active.token if active else None
                        if assignment:
                            kwargs = {}
                            if body.get("invocationToken"):
                                kwargs["token"] = str(body["invocationToken"])
                            invocation = CommandInvocation(
                                command_token=str(command),
                                target_assignment=str(assignment),
                                parameter_values=dict(
                                    body.get("parameterValues", {})),
                                initiator=str(body.get("initiator", "EVENT")),
                                initiator_id=body.get("initiatorId"),
                                **kwargs,
                            )
            except (ValueError, KeyError, CorruptJournal) as e:
                logger.debug("unresolvable command payload ref %s: %s", ref, e)
            if invocation is not None:
                self.commands.invoke(invocation, trace=trace)
            else:
                self.dead_letters.append_json({
                    "kind": "undeliverable-invocation",
                    "device_id": int(dev),
                    "payload_ref": int(ref),
                })

    def _on_undelivered_command(self, invocation, reason) -> None:
        """Undelivered commands dead-letter (reference:
        undelivered-command-invocations topic)."""
        from sitewhere_tpu.runtime.resilience import dead_letter as _dl

        _dl(self.dead_letters, {
            "kind": "undelivered-command",
            "invocation": invocation.token,
            "command": invocation.command_token,
            "assignment": invocation.target_assignment,
            "parameterValues": invocation.parameter_values,
            "reason": str(reason),
        })

    def _run_scheduled_invocation(self, job) -> None:
        """Executor for CommandInvocation jobs (reference
        ``jobs/CommandInvocationJob.java``)."""
        self.commands.invoke(CommandInvocation(
            command_token=str(job.config["commandToken"]),
            target_assignment=str(job.config["assignmentToken"]),
            parameter_values=dict(job.config.get("parameterValues", {})),
            initiator="SCHEDULER",
            initiator_id=job.token,
        ))

    def _run_scheduled_batch(self, job) -> None:
        """Executor for BatchCommandInvocation jobs (reference
        ``jobs/BatchCommandInvocationJob.java``)."""
        self.batch_ops.create_batch_command_invocation(
            command_token=str(job.config["commandToken"]),
            parameter_values=dict(job.config.get("parameterValues", {})),
            devices=list(job.config.get("devices", [])) or None,
            group=job.config.get("group"),
        )

    def add_source(self, source: LifecycleComponent) -> LifecycleComponent:
        """Attach an ingest source wired into the dispatcher — or, in a
        multi-host topology, into the forwarder, which keeps locally-owned
        rows in-process and ships the rest to their owning host."""
        if self.forwarder is not None:
            source.on_event = (
                lambda req, payload=b"": self.forwarder.ingest_requests(
                    [req], payload))
            if hasattr(source, "on_events"):
                source.on_events = self.forwarder.ingest_requests
            if getattr(source, "raw_wire", False):
                # raw lane, multi-host form: owner-split the NDJSON
                # lines and ship remote rows to their owning host;
                # decode errors come back to the source for its
                # failure accounting
                source.on_wire_payload = (
                    lambda p, sid: self.forwarder.ingest_payload(
                        p, sid, raise_on_decode_error=True))
            source.on_registration = self.forwarder.ingest_registration
            # stream requests route to the device's owning host, which
            # handles them via its local _on_host_request
            self.forwarder.on_host_request = self._on_host_request
            source.on_host_request = self.forwarder.ingest_host_request
        else:
            source.on_event = self.dispatcher.ingest
            if hasattr(source, "on_events"):
                # batch forward: one columnar call per wire payload
                source.on_events = self.dispatcher.ingest_many
            if getattr(source, "raw_wire", False):
                # raw lane: C columnar decode + in-scanner token
                # resolution, no per-line json.loads; decode errors come
                # back to the source for its failure accounting
                source.on_wire_payload = (
                    lambda p, sid: self.dispatcher.ingest_wire_lines(
                        p, sid, raise_on_decode_error=True))
                # split halves for the decode pool: decode on a worker,
                # journal+batch in per-source order
                source.on_wire_decode = self.dispatcher.decode_wire_lines
                source.on_wire_decoded = self.dispatcher.ingest_wire_decoded
            source.on_registration = self.dispatcher.ingest_registration
        if self.decode_pool is not None and hasattr(source, "decode_pool"):
            # overlapped decode; the source itself keeps ack-gated
            # receivers (broker redelivery semantics) synchronous
            source.decode_pool = self.decode_pool
        # checkpoint resume: re-seed the source's dedup window so a
        # restart doesn't re-admit duplicates the window had caught
        dedup_keys = self._dedup_snapshot.get(source.name)
        if dedup_keys and getattr(source, "deduplicator", None) is not None \
                and hasattr(source.deduplicator, "import_keys"):
            source.deduplicator.import_keys(dedup_keys)
        source.on_failed_decode = self.dispatcher.ingest_failed_decode
        if getattr(source, "on_host_request", None) is None \
                and self.forwarder is None:
            source.on_host_request = self._on_host_request
        self.sources.append(self.add_child(source))
        return source

    def _on_host_request(self, req, payload: bytes = b"") -> None:
        """Route host-plane requests from sources (reference: device
        stream create/data/send-back requests flow through the event
        sources into ``DeviceStreamManager``,
        ``media/DeviceStreamManager.java``).  Stream requests are
        handled by the RECEIVING host (streams are assignment-scoped,
        management-plane); anything unroutable dead-letters."""
        from sitewhere_tpu.ingest.decoders import RequestKind
        from sitewhere_tpu.services.common import ServiceError

        try:
            if req.kind == RequestKind.STREAM_CREATE:
                self.stream_manager.handle_device_stream_request(
                    req.device_token, req.stream_id,
                    req.content_type or "application/octet-stream")
                return
            if req.kind == RequestKind.STREAM_DATA:
                self.stream_manager.handle_device_stream_data_request(
                    req.device_token, req.stream_id,
                    req.sequence_number, req.stream_data or b"")
                return
            if req.kind == RequestKind.STREAM_SEND:
                self.stream_manager.handle_send_device_stream_data_request(
                    req.device_token, req.stream_id, req.sequence_number)
                return
        except ServiceError as e:
            from sitewhere_tpu.ingest.decoders import encode_envelope

            # the raw request is recorded so the operator requeue path
            # can replay it (e.g. after the missing stream was created)
            self.dead_letters.append_json({
                "kind": "failed-stream-request",
                "request_kind": req.kind.name,
                "device_token": req.device_token,
                "stream_id": req.stream_id,
                "error": str(e),
                "payload": (payload or encode_envelope(req)).hex(),
            })
            return
        self.dead_letters.append_json({
            "kind": "unsupported-host-request",
            "request_kind": req.kind.name,
            "device_token": req.device_token,
        })

    # -- bootstrap (service-instance-management) ----------------------------

    @property
    def _marker_path(self) -> str:
        return os.path.join(self.data_dir, ".bootstrapped")

    @property
    def bootstrapped(self) -> bool:
        return os.path.exists(self._marker_path)

    def bootstrap(self) -> bool:
        """Ensure template users/tenants exist (idempotent, re-run on every
        start since the management stores are memory-resident until a
        checkpoint restores them) and run dataset initializers ONCE — the
        marker gates only the arbitrary-code initializers, the analog of
        the reference's bootstrapped marker around its Groovy scripts
        (``Microservice.java:516-518``).  Returns True if the dataset
        initializers ran."""
        for spec in self.template.users:
            spec = dict(spec)
            authorities = list(spec.pop("authorities", []))
            existing = {a.authority for a in self.users.list_granted_authorities()}
            for auth in authorities:
                if auth not in existing:
                    self.users.create_granted_authority(auth)
            if not any(u.username == spec["username"] for u in
                       self.users.list_users()):
                self.users.create_user(authorities=authorities, **spec)
        known = {t.token for t in self.tenants.list_tenants()}
        for spec in self.template.tenants:
            if spec["token"] not in known:
                self.tenants.create_tenant(**spec)
            self._tenant_dense_id(spec["token"])
        if self.bootstrapped:
            logger.info("instance %s already bootstrapped", self.instance_id)
            return False
        for initializer in self.template.dataset_initializers:
            initializer(self)
        with open(self._marker_path, "w") as f:
            json.dump({"template": self.template.template_id}, f)
        logger.info("bootstrapped instance %s from template %s",
                    self.instance_id, self.template.template_id)
        return True

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        self.bootstrap()
        # Warm the native wire decoder OFF the data path: its first-use
        # build (cc subprocess) must never stall a receiver thread's
        # decode into the <10ms p99 budget.  Decodes that arrive while
        # the build is in flight take the Python path silently — the
        # dispatcher surfaces that count as the ``native.build_fallbacks``
        # gauge, and kicking the build HERE is what keeps it near zero.
        import threading as _threading

        from sitewhere_tpu.native import load_swwire

        _threading.Thread(target=load_swwire, daemon=True,
                          name="native-warmup").start()
        # Config-declared sources (EventSourcesParser analog): built and
        # attached before the lifecycle start below brings them up.  A bad
        # declaration fails boot, like the reference's schema-validated
        # tenant XML.
        source_docs = self.config.get("sources")
        if source_docs and not self._config_sources_built:
            from sitewhere_tpu.ingest.factory import build_sources

            for src in build_sources(source_docs, scripts=self.scripts):
                self.add_source(src)
            self._config_sources_built = True
        # Capture the journal end BEFORE sources start so crash recovery
        # never double-ingests a fresh append racing the replay.
        recover_upto = self.ingest_journal.end_offset
        super().start()
        if bool(self.config.get("telemetry.device_profile_on_start",
                                False)):
            # boot-time device-stage calibration OFF the data path: the
            # probe chains compile on a background thread and land in
            # the device.stage_ms.* histograms when done
            def _calibrate():
                try:
                    self.run_device_profile()
                except Exception:
                    logger.exception("device-stage calibration failed")

            _threading.Thread(target=_calibrate, daemon=True,
                              name="device-profile").start()
        # Crash recovery: re-ingest journal records past each restored
        # component's as-of offset (at-least-once;
        # MicroserviceKafkaConsumer.java:116-139).  Records between the
        # replay floor and the committed offset rebuild volatile state
        # (open windows, device tensors newer than the snapshot) without
        # duplicating event-store persistence (store_dedup_floor).
        import time as _time

        t0 = _time.perf_counter()
        replayed = self.dispatcher.replay_journal(
            upto=recover_upto,
            from_offset=self.checkpointer.replay_floor)
        replay_s = _time.perf_counter() - t0
        # RTO as a measured number: how long the restore + replay halves
        # of recovery actually took, exported every boot
        self.metrics.gauge("recovery.replay_events").set(replayed)
        self.metrics.gauge("recovery.replay_s").set(replay_s)
        if replayed:
            logger.info("recovered %d journaled events in %.3fs on start "
                        "(floor %s)", replayed, replay_s,
                        self.checkpointer.replay_floor)
        if self.restored and self.flightrec is not None:
            # every restore leaves a flight-recorder snapshot: the batch
            # records of the replay plus the recovery numbers an operator
            # needs when asking "what did the restart cost us"
            self.flightrec.snapshot(
                "recovery",
                detail=(f"restored gen {self.checkpointer.restored_generation}"
                        f" in {self.checkpointer.restore_s:.3f}s; replayed "
                        f"{replayed} events in {replay_s:.3f}s from floor "
                        f"{self.checkpointer.replay_floor}"))

    def stop(self) -> None:
        # Stop the receivers, THEN drain the decode pool: a payload a
        # still-running receiver accepts after the flush would otherwise
        # deliver concurrently with (or after) the dispatcher's shutdown
        # flush below.  super().stop() skips the already-stopped sources.
        if self.decode_pool is not None:
            from sitewhere_tpu.runtime.lifecycle import LifecycleState

            for src in self.sources:
                if src.state == LifecycleState.STARTED:
                    try:
                        src.stop()
                    except Exception:  # keep stopping, like super().stop()
                        logger.exception("error stopping %s", src.name)
            self.decode_pool.flush()
        super().stop()  # dispatcher stop flushes + commits the offset
        # Final snapshot AFTER the flush so the checkpoint captures the
        # last committed state (components are stopped but data is live).
        # Ordering contract (audited, regression-tested in
        # tests/test_checkpoint.py): the dispatcher's stop() has drained
        # the ring and egress and committed the final journal offset, and
        # save() captures that offset BEFORE reading any component — the
        # snapshot's claimed offsets can never lead the sealed journal.
        self.checkpointer.save()

    def terminate(self) -> None:
        super().terminate()
        if self.decode_pool is not None:
            # release the pool's worker threads (tests build many
            # instances; daemons would pile up)
            self.decode_pool.stop(timeout_s=2.0)
            self.decode_pool = None
        if self._peer_demuxes:
            # the Config can outlive this Instance: a stale listener
            # would hold the whole graph and resurrect closed channels
            self.config.remove_listener(self._on_peers_changed)
        for demux in self._peer_demuxes.values():
            if demux is not None:
                demux.close()
        self.ingest_journal.close()
        self.dead_letters.close()

    # -- topology (admin surface) -------------------------------------------

    def topology(self) -> dict:
        """Live component tree + counters (reference
        ``TopologyStateAggregator`` → admin UI WebSocket feed)."""
        from sitewhere_tpu.runtime.metrics import global_registry

        topo = {
            "instance": self.instance_id,
            "bootstrapped": self.bootstrapped,
            "components": self.status_tree(),
            "pipeline": self.dispatcher.metrics_snapshot(),
            "devices": len(self.identity.device),
            "events_stored": self.event_store.total_events,
            "store": self.event_store.store_stats(),
            "tracing": self.tracer.stats(),
            # cross-cutting resilience counters (retries, breaker
            # transitions, supervisor restarts, dead-letter totals)
            "resilience": {
                k: v for k, v in
                global_registry().snapshot()["counters"].items()
                if k.startswith("resilience.")
            },
        }
        if self.overload is not None:
            topo["overload"] = self.overload.snapshot()
        if self.flightrec is not None:
            topo["flightrec"] = self.flightrec.stats()
        if self.slo is not None:
            topo["slo"] = self.slo.snapshot()
        if self.forwarder is not None:
            topo["forwarding"] = self.forwarder.metrics()
        return topo

    # -- dead-letter operations (the reprocess-topic analog) ----------------

    def list_dead_letters(self, limit: int = 100,
                          start: Optional[int] = None) -> List[dict]:
        """Dead-letter records with their offsets.

        Without ``start``: the newest ``limit`` records (the tail —
        offsets are dense, so this reads at most ``limit`` records
        regardless of journal size).  With ``start``: the first ``limit``
        records from that offset (oldest-first paging; pass the last
        returned offset + 1 as the next page's start).

        Reference: the dead-letter topics (failed-decode, unregistered,
        undelivered commands — ``KafkaTopicNaming.java:48-78``) are
        operator-inspectable with Kafka tooling; here they are one
        CRC-checked journal.  Records already requeued carry
        ``"requeued": true``.
        """
        limit = max(1, limit)
        if start is None:
            begin = self.dead_letters.end_offset - limit
            stop = None
        else:
            begin = start
            stop = start + limit
        requeued = self._requeued_dead_letters()
        out: List[dict] = []
        for offset, raw in self.dead_letters.scan(max(0, begin), stop):
            try:
                doc = json.loads(raw)
            except ValueError:
                doc = {"kind": "corrupt", "raw": raw.hex()}
            if doc.get("kind") == "requeue-marker":
                continue  # bookkeeping, not an operator-facing record
            doc["offset"] = offset
            if offset in requeued:
                doc["requeued"] = True
            out.append(doc)
        return out[-limit:]

    def _requeued_dead_letters(self) -> set:
        """Offsets already requeued, rebuilt from the retained journal
        tail's marker records (cached against the journal end offset)."""
        end = self.dead_letters.end_offset
        cache = getattr(self, "_requeue_cache", None)
        if cache is not None and cache[0] == end:
            return cache[1]
        done: set = set()
        # scan(0) starts at the first RETAINED segment (prune contract),
        # so this is bounded by the retention window
        for _, raw in self.dead_letters.scan(0):
            try:
                doc = json.loads(raw)
            except ValueError:
                continue
            if doc.get("kind") == "requeue-marker":
                done.add(int(doc.get("target", -1)))
        self._requeue_cache = (end, done)
        return done

    def _mark_requeued(self, offset: int) -> None:
        """Durable idempotency marker: requeuing the same offset twice
        must not re-deliver (markers ride the same journal, so they
        survive restarts and age out with the records they guard)."""
        self.dead_letters.append_json(
            {"kind": "requeue-marker", "target": int(offset)})

    def requeue_dead_letter(self, offset: int) -> dict:
        """Re-drive one dead-letter record through the pipeline (the
        reprocess-topic analog, ``KafkaTopicNaming.java:172-174``).

        - ``failed-decode``: re-decode the captured raw payload with the
          dispatcher's recovery decoder (the operator may have fixed the
          device type/scripts since) and re-ingest; a second decode
          failure dead-letters again.
        - ``unregistered``: re-read each referenced ingest-journal
          payload and re-ingest — after the operator registered the
          device manually, the rows now validate.
        - ``intake-shed``: re-ingest a payload that overload admission
          refused (the audit/replay half of the shedding contract) —
          admission applies again, so a requeue during a STILL-overloaded
          window is refused, not silently re-shed.
        - ``tenant-budget``: same replay path as ``intake-shed``, for
          sheds the tenant's CONFIGURED budget overlay caused.  Replay
          re-checks the tenant's CURRENT budget — re-ingest runs the
          composed admission again, so a tenant still over its budget
          is refused (with the budget named), and one whose budget was
          raised (or whose window drained) gets the rows back.
        - ``forward-shed``: re-route remote-owned rows the forwarder's
          shed-retention bound forced out — back through
          ``HostForwarder.ingest_payload`` so ownership recomputes and
          the owner's (possibly recovered) admission decides again.
        - ``undelivered-command``: re-invoke the command against its
          target assignment.
        Requeue granularity is the PAYLOAD (at-least-once): a multi-device
        payload whose other rows already processed re-ingests those rows
        too, exactly like the reference's reprocess topic redelivering a
        whole record.
        """
        from sitewhere_tpu.ingest.decoders import DecodeError, JsonLinesDecoder
        from sitewhere_tpu.services.common import EntityNotFound, ValidationError

        try:
            raw = self.dead_letters.read_one(int(offset))
        except KeyError:
            raise EntityNotFound(f"dead letter {offset} (pruned or invalid)")
        try:
            doc = json.loads(raw)
        except ValueError:
            raise ValidationError(f"dead letter {offset} is not requeueable "
                                  f"(corrupt record)")
        kind = doc.get("kind")
        if int(offset) in self._requeued_dead_letters():
            # idempotent retry: a second POST must not re-deliver
            return {"requeued": False, "kind": kind, "already": True,
                    "reason": "record was already requeued"}
        # same default the dispatcher's crash recovery uses
        decoder = self.dispatcher.recovery_decoder or JsonLinesDecoder()
        if kind == "forward-shed" and "payload" in doc:
            from sitewhere_tpu.runtime.overload import OverloadShed

            if self.forwarder is None:
                return {"requeued": False, "kind": kind,
                        "reason": "no forwarder on this host"}
            payload = bytes.fromhex(doc["payload"])
            try:
                self.forwarder.ingest_payload(payload, source_id="requeue")
            except OverloadShed as e:
                # owner still shedding: the record stays un-requeued so
                # the operator can retry after the fleet recovers
                return {"requeued": False, "kind": kind,
                        "reason": f"owner still shedding: {e}"}
            self._mark_requeued(offset)
            return {"requeued": True, "kind": kind,
                    "rows": payload.count(b"\n") + 1}
        if kind in ("failed-decode", "failed-stream-request",
                    "intake-shed", "tenant-budget") and "payload" in doc:
            payload = bytes.fromhex(doc["payload"])
            try:
                reqs = decoder(payload)
            except DecodeError as e:
                self.dispatcher.ingest_failed_decode(
                    payload, doc.get("source", "requeue"), e)
                return {"requeued": False, "kind": kind,
                        "reason": f"decode failed again: {e}"}
            if not reqs:
                return {"requeued": False, "kind": kind,
                        "reason": "decode failed again: no rows decoded"}
            from sitewhere_tpu.ingest.decoders import RequestKind

            from sitewhere_tpu.runtime.overload import OverloadShed

            events = [r for r in reqs if r.event_type is not None]
            if kind == "tenant-budget" and events:
                # budget replay carries the shedding tenant: re-stamp
                # rows that lost their metadata so the re-ingest below
                # re-checks THAT tenant's current composed budget, not
                # the default tenant's
                tenant = doc.get("tenant")
                if tenant:
                    for r in events:
                        if r.metadata is None or "tenant" not in r.metadata:
                            r.metadata = dict(r.metadata or {},
                                              tenant=tenant)
            if events:
                try:
                    self.dispatcher.ingest_many(events, payload,
                                                source_id="requeue")
                except OverloadShed as e:
                    # still overloaded / still over budget: the record
                    # stays un-requeued so the operator can retry after
                    # recovery (or after raising the tenant's budget)
                    reason = ("still over tenant budget"
                              if kind == "tenant-budget"
                              else "refused by admission")
                    return {"requeued": False, "kind": kind,
                            "reason": f"{reason}: {e}"}
            rows = len(events)
            for r in reqs:
                if r.event_type is not None:
                    continue
                if r.kind == RequestKind.REGISTRATION:
                    self.dispatcher.ingest_registration(r)
                else:
                    # host-plane (stream) request — re-route; a repeat
                    # failure dead-letters a fresh record
                    self._on_host_request(r, payload)
                    rows += 1
            self._mark_requeued(offset)
            return {"requeued": True, "kind": kind, "rows": rows}
        if kind == "unregistered" and doc.get("refs"):
            rows = 0
            missing: List[int] = []
            for ref in doc["refs"]:
                try:
                    payload, tenant = self.ingest_journal.read_record(
                        int(ref))
                    reqs = [r for r in decoder(payload)
                            if r.event_type is not None]
                except Exception:
                    missing.append(int(ref))
                    continue
                if tenant != "default":
                    # the payload's tenant rides its journal record; a
                    # line's own metadata.tenant wins, as it does live
                    for r in reqs:
                        r.metadata = {"tenant": tenant, **(r.metadata or {})}
                if reqs:
                    self.dispatcher.ingest_many(reqs, payload)
                    rows += len(reqs)
            if rows > 0:
                self._mark_requeued(offset)
            return {"requeued": rows > 0, "kind": kind, "rows": rows,
                    **({"unreadable_refs": missing} if missing else {})}
        if kind == "device-poison" and doc.get("columns"):
            # poison rows isolated by the dispatcher's bisect
            # (_dead_letter_poison): the document carries the raw host
            # columns, so the rows re-enter the normal batch path
            # exactly as fresh ingest — requeue AFTER the producer-side
            # corruption is fixed (or to reproduce the quarantine)
            import numpy as np

            from sitewhere_tpu.ingest.batcher import _COL_FIELDS, _DTYPE
            from sitewhere_tpu.runtime.overload import OverloadShed

            columns = doc["columns"]
            if "device_id" not in columns:
                return {"requeued": False, "kind": kind,
                        "reason": "poison record lacks device_id column"}
            cols = {
                field: np.asarray(columns[field],
                                  dtype=_DTYPE.get(field, np.float32))
                for field in _COL_FIELDS if field in columns
            }
            try:
                rows = self.dispatcher.requeue_rows(cols)
            except OverloadShed as e:
                return {"requeued": False, "kind": kind,
                        "reason": f"refused by admission: {e}"}
            self._mark_requeued(offset)
            return {"requeued": True, "kind": kind, "rows": rows}
        if kind == "undelivered-command" and doc.get("command") \
                and doc.get("assignment"):
            ok = self.commands.invoke(CommandInvocation(
                command_token=doc["command"],
                target_assignment=doc["assignment"],
                parameter_values=doc.get("parameterValues", {}),
                initiator="REQUEUE",
            ))
            if ok:
                self._mark_requeued(offset)
            # a repeat failure has already dead-lettered a fresh record
            return {"requeued": bool(ok), "kind": kind,
                    **({} if ok else {"reason": "delivery failed again"})}
        return {"requeued": False, "kind": kind,
                "reason": "record kind is not requeueable"}

    def create_command_invocation(self, assignment_token: str,
                                  command_token: str,
                                  parameter_values: Optional[Dict[str, str]] = None,
                                  initiator: str = "REST",
                                  initiator_id: Optional[str] = None,
                                  ts_s: Optional[int] = None) -> dict:
        """Create a command-invocation EVENT for an assignment: journal
        the invocation body and let the pipeline's command-row egress
        deliver it (reference: REST creates an invocation event which
        flows enriched-command-invocations → command-delivery,
        SURVEY.md §3.4).  One delivery path — a direct ``commands.invoke``
        would double-deliver.  Raises EntityNotFound when the assignment
        is not on THIS host; the web layer federates that case over the
        fabric to the owner (``command.invoke``)."""
        import json as _json

        from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind
        from sitewhere_tpu.services.common import mint_token, now_s

        assignment = self.device_management.get_device_assignment(
            assignment_token)
        device = self.device_management.get_device(assignment.device)
        inv_token = mint_token("inv")
        event_ts = int(ts_s if ts_s is not None else now_s())
        payload = _json.dumps({
            "deviceToken": device.token,
            "type": "commandinvocation",
            "request": {
                "commandToken": str(command_token),
                "assignmentToken": assignment_token,
                "parameterValues": dict(parameter_values or {}),
                "initiator": initiator,
                "initiatorId": initiator_id,
                "invocationToken": inv_token,
                # crash replay re-decodes this payload: without the
                # eventDate the recovered row would be stamped 1970 and
                # immediately TTL-pruned
                "eventDate": event_ts,
            },
        }).encode()
        self.dispatcher.ingest(DecodedRequest(
            kind=RequestKind.COMMAND_INVOCATION,
            device_token=device.token,
            ts_s=event_ts,
            # the invocation row carries the invocation handle so its
            # responses (correlated by the same token) query directly
            originating_event=inv_token,
        ), payload)
        self.dispatcher.flush()
        return {"queued": True, "token": inv_token,
                "deviceToken": device.token,
                "host": self.instance_id}

    def invoke_command(self, assignment_token: str, command_token: str,
                       parameter_values: Optional[Dict[str, str]] = None,
                       initiator: str = "REST",
                       initiator_id: Optional[str] = None,
                       ts_s: Optional[int] = None) -> dict:
        """Federated invocation: run locally when this host owns the
        assignment, otherwise route over the fabric to the owner (the
        reference's web-rest demuxing management calls to the owning
        service instance, SURVEY.md §3.3-3.4).  An unreachable peer makes
        the outcome AMBIGUOUS (it may have queued before dying) — that
        surfaces as a 5xx-class ServiceError, never a definitive 404 that
        would invite a double-delivering retry."""
        from sitewhere_tpu.services.common import EntityNotFound, ServiceError

        kwargs = dict(command_token=command_token,
                      parameter_values=parameter_values,
                      initiator=initiator, initiator_id=initiator_id,
                      ts_s=ts_s)
        try:
            return self.create_command_invocation(assignment_token, **kwargs)
        except EntityNotFound:
            from sitewhere_tpu.rpc.channel import RpcError

            ambiguous = False
            for _p, demux in sorted(self._peer_demuxes.items()):
                if demux is None:
                    continue
                try:
                    # short per-peer timeout: one hung peer must not
                    # stall the caller's thread for the 30s default
                    # times the fleet size
                    result, _ = demux.call("command.invoke", {
                        "assignmentToken": assignment_token,
                        "commandToken": command_token,
                        "parameterValues": dict(parameter_values or {}),
                        "initiator": initiator,
                        "initiatorId": initiator_id,
                        "ts": ts_s,
                    }, timeout_s=5.0)
                    return result
                except RpcError as e:
                    if e.error != "not_found":
                        raise
                except Exception:
                    ambiguous = True   # peer may have queued before dying
            if ambiguous:
                raise ServiceError(
                    f"assignment {assignment_token} not found locally and "
                    "a peer was unreachable — invocation state unknown; "
                    "retrying may double-deliver")
            raise

    def cluster_topology(self) -> dict:
        """Every host's topology, aggregated over the fabric (reference:
        ``TopologyStateAggregator.java:40-113`` consumes all
        microservices' state heartbeats into one live cluster view).  A
        peer that doesn't answer reports as unreachable rather than
        failing the whole view."""
        import threading

        view = {"local": self.topology(), "peers": {}}

        def poll(p, demux):
            try:
                body, _ = demux.call("instance.topology", timeout_s=2.0)
                view["peers"][str(p)] = body
            except Exception as e:   # noqa: BLE001 — degraded view, not error
                view["peers"][str(p)] = {"unreachable": str(e)}

        # concurrent polls: k dead peers cost ONE timeout, not k — the
        # endpoint exists to diagnose exactly that outage
        threads = [threading.Thread(target=poll, args=(p, d), daemon=True)
                   for p, d in sorted(self._peer_demuxes.items())
                   if d is not None]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=5.0)
        return view
