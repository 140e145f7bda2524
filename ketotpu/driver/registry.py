"""Registry: lazy dependency injection for every component.

The reference's `RegistryDefault` (`internal/driver/registry_default.go:
53-87`) is an interface-soup singleton factory; this is the same shape with
Python duck typing:

* every provider method (`store`, `namespace_manager`, `check_engine`,
  `expand_engine`, `mapper`, `metrics`, `tracer`, `logger`) is a lazy
  singleton;
* the engine seam (`check.EngineProvider`, `internal/check/engine.go:29-31`)
  is the ``engine.kind`` config key: ``tpu`` wires the batched device engine,
  ``oracle`` the sequential host engine — handlers never know which;
* `ketoctx`-style embedder options (`ketoctx/options.go:18-35`) are
  constructor keyword arguments: a custom logger, tracer, metrics registry,
  extra readiness checks, or a pre-built tuple store can be injected.

`Registry.init()` mirrors `RegistryDefault.Init` (`registry_default.go:
314-356`): resolve the namespace manager from config, build the store,
determine the network id, warm the engine snapshot.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
import weakref
from typing import Any, Callable, Dict, Optional

import numpy as np

from ketotpu import __version__, compilewatch, hostwaits
from ketotpu.api.mapper import Mapper
from ketotpu.api.uuid_map import UUIDMapper
from ketotpu.driver.config import ConfigError, Provider
from ketotpu.engine.coalesce import CoalescingEngine
from ketotpu.engine.oracle import CheckEngine, ExpandEngine
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.observability import Metrics, Tracer, make_logger
from ketotpu.opl.ast import Namespace
from ketotpu.storage.memory import InMemoryTupleStore
from ketotpu.storage.namespaces import (
    DirectoryNamespaceManager,
    OPLFileNamespaceManager,
    StaticNamespaceManager,
)

# networkx DetermineNetwork analog: single-tenant default network id; a
# Contextualizer can swap it per request (ketoctx/contextualizer.go)
DEFAULT_NETWORK_ID = uuid.UUID("00000000-0000-0000-0000-000000000001")


class Registry:
    """Lazy singletons over a validated config (RegistryDefault analog)."""

    def __init__(
        self,
        config: Optional[Provider] = None,
        *,
        logger=None,
        tracer: Optional[Tracer] = None,
        metrics: Optional[Metrics] = None,
        store: Optional[InMemoryTupleStore] = None,
        namespace_manager=None,
        readiness_checks: Optional[Dict[str, Callable[[], None]]] = None,
        network_id: uuid.UUID = DEFAULT_NETWORK_ID,
        options: Optional["KetoOptions"] = None,
    ):
        from ketotpu.ctx import KetoOptions

        self.config = config if config is not None else Provider()
        self.options = options if options is not None else KetoOptions()
        self._lock = threading.RLock()
        self._logger = logger if logger is not None else self.options.logger
        self._tracer = tracer
        self._metrics = metrics
        self._store = store
        self._namespace_manager = namespace_manager
        self._check_engine = None
        self._expand_engine = None
        self._list_engine = None
        self._oracle_engine = None
        self._watch_hub = None
        self._result_cache = None
        self._flight_recorder = None
        self._wave_ledger = None
        self._trace_store = None
        self._trace_store_built = False
        self._shadow = None
        self._shadow_built = False
        self._slo = None
        self._slo_built = False
        self._watchdog = None
        self._watchdog_built = False
        self._profiler = None
        self._compile_watch = None
        self._admission = None
        self._door_pools: "weakref.WeakSet" = weakref.WeakSet()
        self._overload = None
        self._overload_built = False
        self._session_broker = None
        self._mapper = None
        self._ro_mapper = None
        self._uuid_mapper = None
        self._durability_gate = None
        self._tenant_plane = None
        self._tenant_plane_built = False
        # warm-standby seams (ketotpu/standby.py): the follower installs
        # its state snapshot here so /debug/projection and status --debug
        # show standby rows; the REST /debug/handoff route triggers a
        # deliberate takeover through handoff_fn (409 when unset)
        self.standby_state_fn: Optional[Callable[[], dict]] = None
        self.handoff_fn: Optional[Callable[[str], dict]] = None
        self.network_id = network_id
        self.readiness_checks = dict(readiness_checks or {})
        self.readiness_checks.update(self.options.readiness_checks)
        self.version = __version__
        # per-tenant derived registries (Contextualizer targets), LRU order
        from collections import OrderedDict

        self._tenants: "OrderedDict[str, Registry]" = OrderedDict()

    # -- cross-cutting ------------------------------------------------------

    def logger(self):
        with self._lock:
            if self._logger is None:
                self._logger = make_logger(
                    level=str(self.config.get("log.level", "info"))
                )
            return self._logger

    def metrics(self) -> Metrics:
        with self._lock:
            if self._metrics is None:
                self._metrics = Metrics()
            return self._metrics

    def tracer(self) -> Tracer:
        with self._lock:
            if self._tracer is None:
                provider = str(self.config.get("tracing.provider", "") or "")
                endpoint = str(
                    self.config.get("tracing.otlp.server_url", "") or ""
                )
                if provider in ("otlp", "otel") and not endpoint:
                    # the operator asked for export; silently building the
                    # local-only tracer would drop every span on the floor
                    raise ConfigError(
                        "tracing.otlp.server_url",
                        f"tracing.provider={provider!r} requires a non-empty"
                        " otlp server_url",
                    )
                if provider in ("otlp", "otel") and endpoint:
                    from ketotpu.otlp import OTLPTracer

                    t = OTLPTracer(
                        endpoint,
                        metrics=self.metrics(),
                        logger=self.logger(),
                        flush_interval=float(
                            self.config.get(
                                "tracing.otlp.flush_interval_ms", 2000
                            )
                        ) / 1000.0,
                    )
                else:
                    t = Tracer(self.metrics(), self.logger())
                if self.options.tracer_wrapper is not None:
                    t = self.options.tracer_wrapper(t)
                self._tracer = t
            return self._tracer

    def flight_recorder(self):
        """Lazy ring buffer of the slowest recent requests with their
        per-stage latency vectors (ketotpu/flightrec.py); served by the
        metrics port's /debug/flight-recorder endpoint."""
        with self._lock:
            if self._flight_recorder is None:
                from ketotpu.flightrec import FlightRecorder

                # observability.* is the schema'd home; the legacy
                # log.flight_recorder_size key still wins when set so
                # existing deployments keep their sizing
                cap = self.config.get("log.flight_recorder_size")
                if cap is None:
                    cap = self.config.get(
                        "observability.flight_recorder_size", 32
                    )
                self._flight_recorder = FlightRecorder(
                    capacity=int(cap or 32),
                    max_age_s=float(
                        self.config.get(
                            "observability.flight_recorder_max_age_s", 600
                        ) or 600
                    ),
                )
            return self._flight_recorder

    def wave_ledger(self):
        """Lazy ring of the last N dispatched waves (ketotpu/waveledger.py):
        the coalescer files one entry per wave; served by /debug/waves and
        ``keto-tpu status --debug``."""
        with self._lock:
            if self._wave_ledger is None:
                from ketotpu.waveledger import WaveLedger

                self._wave_ledger = WaveLedger(
                    capacity=int(
                        self.config.get("observability.wave_ledger_size", 256)
                        or 256
                    ),
                )
            return self._wave_ledger

    def trace_store(self):
        """Lazy tail-sampled trace store (ketotpu/tracing.py): promoted
        request anatomies behind GET /debug/trace.  None when
        ``observability.trace.enabled`` is false — flightrec then skips
        the span buffer entirely."""
        with self._lock:
            if not self._trace_store_built:
                self._trace_store_built = True
                if bool(self.config.get("observability.trace.enabled", True)):
                    from ketotpu.tracing import TraceStore

                    self._trace_store = TraceStore(
                        slow_ms=float(
                            self.config.get("observability.trace.slow_ms", 25.0)
                        ),
                        store_size=int(
                            self.config.get(
                                "observability.trace.store_size", 64
                            ) or 64
                        ),
                        recent_size=int(
                            self.config.get(
                                "observability.trace.recent_size", 512
                            ) or 512
                        ),
                        metrics=self.metrics(),
                        tracer=self.tracer(),
                    )
            return self._trace_store

    def shadow(self):
        """Lazy shadow-verification plane (ketotpu/shadow.py).  None when
        disabled or when the engine is a worker-side relay (kind
        ``remote``): workers forward checks to the owner, and the owner —
        which holds the authoritative store + oracle — shadows them."""
        with self._lock:
            if not self._shadow_built:
                self._shadow_built = True
                enabled = bool(
                    self.config.get("observability.shadow.enabled", True)
                )
                kind = str(self.config.get("engine.kind", "oracle"))
                if enabled and kind != "remote":
                    from ketotpu.shadow import ShadowVerifier

                    self._shadow = ShadowVerifier(
                        self,
                        sample_rate=int(
                            self.config.get(
                                "observability.shadow.sample_rate", 1000
                            ) or 1000
                        ),
                        queue_cap=int(
                            self.config.get(
                                "observability.shadow.queue_cap", 1024
                            ) or 1024
                        ),
                        ledger_size=int(
                            self.config.get(
                                "observability.shadow.ledger_size", 256
                            ) or 256
                        ),
                    )
            return self._shadow

    def slo(self):
        """Lazy multi-window SLO burn-rate engine (ketotpu/slo.py): the
        windowed availability/latency SLIs behind GET /debug/slo, the
        keto_slo_* gauges, and the fleet digest's burn numbers.  None
        when ``observability.slo.enabled`` is false."""
        with self._lock:
            if not self._slo_built:
                self._slo_built = True
                if bool(self.config.get("observability.slo.enabled", True)):
                    from ketotpu.slo import SLOEngine

                    self._slo = SLOEngine(
                        self.metrics(),
                        latency_target_ms=float(
                            self.config.get(
                                "observability.slo.latency_target_ms", 25.0
                            )
                        ),
                        fast_window_s=float(
                            self.config.get(
                                "observability.slo.fast_window_s", 300
                            ) or 300
                        ),
                        slow_window_s=float(
                            self.config.get(
                                "observability.slo.slow_window_s", 3600
                            ) or 3600
                        ),
                        availability_objective=float(
                            self.config.get(
                                "observability.slo.availability_objective",
                                0.999,
                            )
                        ),
                        latency_objective=float(
                            self.config.get(
                                "observability.slo.latency_objective", 0.99
                            )
                        ),
                    )
            return self._slo

    def watchdog(self):
        """Lazy regression watchdog (ketotpu/watchdog.py): the background
        rule evaluator behind GET /debug/incidents.  None when
        ``observability.watchdog.enabled`` is false; started by
        :meth:`init` (daemon boot), stopped by :meth:`close_engines`."""
        with self._lock:
            if not self._watchdog_built:
                self._watchdog_built = True
                if bool(
                    self.config.get("observability.watchdog.enabled", True)
                ):
                    from ketotpu.watchdog import Watchdog

                    self._watchdog = Watchdog(
                        self,
                        interval_s=float(
                            self.config.get(
                                "observability.watchdog.interval_s", 5.0
                            ) or 5.0
                        ),
                        baseline_waves=int(
                            self.config.get(
                                "observability.watchdog.baseline_waves", 32
                            ) or 32
                        ),
                        drift_pct=float(
                            self.config.get(
                                "observability.watchdog.drift_pct", 75.0
                            ) or 75.0
                        ),
                        incident_cap=int(
                            self.config.get(
                                "observability.watchdog.incident_cap", 64
                            ) or 64
                        ),
                        burn_threshold=float(
                            self.config.get(
                                "observability.watchdog.burn_threshold", 2.0
                            ) or 2.0
                        ),
                        auto_profile=bool(
                            self.config.get(
                                "observability.watchdog.auto_profile", False
                            )
                        ),
                        profile_cooldown_s=float(
                            self.config.get(
                                "observability.watchdog.profile_cooldown_s",
                                600,
                            ) or 600
                        ),
                    )
            return self._watchdog

    def hostlink(self):
        """The multi-host DCN lane of the BUILT serving engine, or None
        (single host, or the engine is not built yet) — a fleet/health
        probe must never trigger the lazy engine build."""
        with self._lock:
            outer = self._check_engine
        eng = getattr(outer, "inner", outer)
        return getattr(eng, "hostlink", None)

    def health_digest(self) -> dict:
        """The compact per-host health digest that rides every heartbeat
        (both directions) and heads the local half of GET /debug/fleet:
        SLO burn rates, wave device-ms p50, after-warm compile count,
        shed/divergence counters, standby lag, incident count.  Built
        only from already-built components — it runs on the heartbeat
        cadence and must stay cheap."""
        link = self.hostlink()
        metrics = self.metrics()
        # counter_total: the shed counter is labelled by transport AND
        # priority class — sum the whole family, not one exact series
        shed = metrics.counter_total("keto_requests_shed_total")
        with self._lock:
            shadow = self._shadow
            ledger = self._wave_ledger
            watchdog = self._watchdog
            admission = self._admission
            standby_fn = self.standby_state_fn
        digest = {
            "host": int(link.host_id) if link is not None else 0,
            "pid": os.getpid(),
            "ts": round(time.time(), 3),
            "shed_total": int(shed),
            "overload_stage": int(
                admission.stage if admission is not None else 0
            ),
            "admission_limit": int(
                admission.limit if admission is not None else 0
            ),
            "divergences": int(
                getattr(shadow, "divergences", 0) if shadow else 0
            ),
            "compiles_after_warm": int(
                compilewatch.get().compiles_after_warm
            ),
            "incidents": int(
                watchdog.stats()["incidents_filed"] if watchdog else 0
            ),
        }
        slo = self.slo()
        if slo is not None:
            digest["burn"] = slo.digest()
        if ledger is not None:
            digest["wave_device_ms_p50"] = (
                ledger.stats()["device_ms_p50"]
            )
        if standby_fn is not None:
            try:
                digest["standby_lag_entries"] = int(
                    standby_fn().get("lag_entries", 0)
                )
            except Exception:  # noqa: BLE001 - health must not raise
                pass
        return digest

    def compile_watch(self):
        """The process-global XLA compile observatory
        (ketotpu/compilewatch.py), bound to THIS registry's metrics/logger
        so compile events land in keto_xla_compiles_total{fn} and
        after-warm compiles warn loudly (last bind wins — one serving
        registry per process)."""
        with self._lock:
            if self._compile_watch is None:
                from ketotpu import compilewatch

                w = compilewatch.get()
                w.bind(
                    self.metrics(), self.logger(),
                    warn_after_warm=bool(
                        self.config.get(
                            "observability.warm_compile_warning", True
                        )
                    ),
                    log_size=int(
                        self.config.get("observability.compile_log_size", 128)
                        or 128
                    ),
                )
                self._compile_watch = w
            return self._compile_watch

    def profiler(self):
        """Lazy on-demand device profiler (ketotpu/profiler.py) behind
        POST /debug/profile; disabled unless observability.profiler.enabled
        arms it."""
        with self._lock:
            if self._profiler is None:
                from ketotpu.profiler import DeviceProfiler

                self._profiler = DeviceProfiler(
                    enabled=bool(
                        self.config.get(
                            "observability.profiler.enabled", False
                        )
                    ),
                    out_dir=str(
                        self.config.get("observability.profiler.dir", "")
                        or ""
                    ),
                    max_seconds=float(
                        self.config.get(
                            "observability.profiler.max_seconds", 60
                        ) or 60
                    ),
                )
            return self._profiler

    # -- multi-tenancy (ketoctx Contextualizer seam) ------------------------

    def tenant_plane(self):
        """The shared-engine tenant plane (ketotpu/tenancy/) — built when
        ``tenancy.enabled`` is on and the store is the in-memory fused
        store.  SQL dsns keep the legacy per-network store handles (their
        ``nid`` rows already scope natively); the plane path is the
        device-engine one: ONE compiled program, per-tenant qualified
        namespaces, generation-swap lifecycle.  None when inactive."""
        with self._lock:
            if self._tenant_plane_built:
                return self._tenant_plane
            self._tenant_plane_built = True
            if not bool(self.config.get("tenancy.enabled", False)):
                return None
            from ketotpu.ctx import HeaderContextualizer, StaticContextualizer

            # make the edge resolution live: unless the embedder supplied
            # its own Contextualizer, X-Keto-Network now routes tenants —
            # on the plane path AND on the SQL per-network fallback below
            if isinstance(self.options.contextualizer, StaticContextualizer):
                self.options.contextualizer = HeaderContextualizer()
            if self.config.dsn() != "memory":
                self.logger().warning(
                    "tenancy.enabled with dsn=%r: SQL stores scope rows by"
                    " nid natively; falling back to per-network store"
                    " handles instead of the fused device plane",
                    self.config.dsn(),
                )
                return None
            from ketotpu.tenancy import TenantPlane
            # an explicitly-injected manager (embedder / bench / synth
            # graph) becomes the base every tenant inherits; the plane's
            # qualified union then supersedes it as the ROOT manager so
            # the shared device engine sees every tenant's namespaces
            base_manager = (
                self._namespace_manager
                if self._namespace_manager is not None
                else self._config_namespace_manager()
            )
            self._tenant_plane = TenantPlane(
                self.store(),
                base_manager,
                default_network=str(
                    self.config.get("tenancy.default_network", "default")
                    or "default"
                ),
                max_tenants=int(
                    self.config.get("tenancy.max_tenants", 1024) or 1024
                ),
                quota_inflight=int(
                    self.config.get("tenancy.quota.inflight", 0) or 0
                ),
                quota_write_rate=float(
                    self.config.get("tenancy.quota.write_rate", 0) or 0
                ),
                quota_max_tuples=int(
                    self.config.get("tenancy.quota.max_tuples", 0) or 0
                ),
                metrics_top_k=int(
                    self.config.get("tenancy.metrics_top_k", 8) or 8
                ),
                logger=self.logger(),
            )
            self._namespace_manager = self._tenant_plane.manager
            return self._tenant_plane

    def resolve(self, metadata: Optional[Dict[str, str]] = None) -> "Registry":
        """Per-request registry: the options' Contextualizer maps request
        metadata (HTTP headers / gRPC metadata, lower-cased keys) to a
        network id; non-default ids get a derived registry whose store and
        engines live on that network (`registry_default.go:121-126`).
        With the tenant plane active, EVERY request routes through a
        tenant registry — the default network is just another tenant."""
        plane = self.tenant_plane()
        if plane is not None:
            nid = self.options.contextualizer.network(
                metadata or {}, plane.default_network
            )
            return self.for_network(nid)
        nid = self.options.contextualizer.network(
            metadata or {}, str(self.network_id)
        )
        if nid == str(self.network_id):
            return self
        return self.for_network(nid)

    #: bound on cached tenant registries — the contextualizer key may be
    #: client-influenced, so the cache must not grow without limit
    MAX_TENANTS = 256

    def for_network(self, nid: str) -> "Registry":
        """Derived registry sharing config/observability/namespaces but
        with tenant-scoped storage, engines, and UUID mapping.  Bounded
        LRU: beyond MAX_TENANTS the least-recently-used tenant is evicted
        (its store closed); its durable rows are untouched and it rebuilds
        on next use."""
        plane = self.tenant_plane()
        with self._lock:
            reg = self._tenants.pop(nid, None)
            if reg is None:
                if plane is not None:
                    reg = self._build_tenant_registry(plane, nid)
                else:
                    reg = Registry(
                        self.config,
                        logger=self.logger(),
                        tracer=self.tracer(),
                        metrics=self.metrics(),
                        namespace_manager=self.namespace_manager(),
                        store=self._build_store(nid),
                        readiness_checks=self.readiness_checks,
                        network_id=uuid.uuid5(self.network_id, nid),
                        options=self.options,
                    )
            self._tenants[nid] = reg  # reinsert = most recently used
            while len(self._tenants) > self.MAX_TENANTS:
                _, evicted = self._tenants.popitem(last=False)
                # stop the coalescer worker eagerly (frees the thread and
                # the device snapshot), but DEFER the store close until the
                # evicted registry is unreachable: a request on another
                # thread may still hold it mid-flight, and closing its
                # sqlite connection under it would 500 that request.  The
                # finalizer holds the store (not the registry), so the close
                # runs exactly when the last in-flight reference drops.
                eng_close = getattr(evicted._check_engine, "close", None)
                if eng_close is not None:
                    eng_close()
                close = getattr(evicted._store, "close", None)
                if close is not None:
                    import weakref

                    weakref.finalize(evicted, close)
            return reg

    def _build_tenant_registry(self, plane, nid: str) -> "Registry":
        """Assemble a tenant registry over the shared plane: every engine
        is PRESET as a qualifying facade (or a host engine over the
        tenant's store view) so no lazy builder can ever wrap the shared
        device engine unqualified."""
        view = plane.view_for(nid)
        reg = Registry(
            self.config,
            logger=self.logger(),
            tracer=self.tracer(),
            metrics=self.metrics(),
            namespace_manager=plane.manager_for(nid),
            store=view,
            readiness_checks=self.readiness_checks,
            network_id=uuid.uuid5(self.network_id, nid),
            options=self.options,
        )
        # the plane is the root's; a derived registry must never build
        # a second one from the same config
        reg._tenant_plane_built = True
        reg._check_engine = plane.engine_for(nid, self.check_engine())
        reg._expand_engine = ExpandEngine(
            view, max_depth=self.config.max_read_depth()
        )
        dev = self._device_engine()
        if dev is not None:
            reg._list_engine = plane.list_engine_for(nid, dev)
        else:
            from ketotpu.leopard import HostListEngine

            reg._list_engine = HostListEngine(view)
        if bool(self.config.get("cache.enabled", True)):
            from ketotpu.cache import ResultCache

            # private per-tenant cache over the view: unqualified keys,
            # and a constant fence scope so only THIS tenant's writes
            # (the only entries its view's changelog delivers) invalidate
            rc = ResultCache(
                max_entries=int(
                    self.config.get("cache.max_entries", 65536) or 65536
                ),
                shards=int(self.config.get("cache.shards", 8) or 8),
                max_staleness_ms=int(
                    self.config.get("cache.max_staleness_ms", 100)
                ),
                hot_threshold=int(
                    self.config.get("cache.hot_threshold", 0) or 0
                ),
                top_k=int(self.config.get("cache.top_k", 16) or 16),
                metrics=self.metrics(),
                scope_fn=lambda _ns: "",
            )
            rc.attach_store(view)
            reg._result_cache = rc
        return reg

    # -- storage + namespaces ----------------------------------------------

    def store(self):
        """Build the tuple store from ``dsn`` (pop_connection.go analog):
        ``memory`` | ``sqlite://<path>`` (durable, WAL; migrate with
        `keto-tpu migrate up` unless the path is ``:memory:``)."""
        with self._lock:
            if self._store is None:
                self._store = self._build_store(str(self.network_id))
            self._wire_overflow(self._store)
            return self._store

    def _wire_overflow(self, store) -> None:
        """Surface bounded-changelog eviction (instead of readers silently
        full-rebuilding): keto_changelog_overflow_total counts evicted
        entries, and the log warns once per overflow episode.  Idempotent;
        also covers stores injected via the constructor."""
        if getattr(store, "overflow_hook", "absent") is not None:
            return  # store has no hook seam, or one is already installed
        metrics, logger = self.metrics(), self.logger()

        def hook(n: int, first: bool) -> None:
            metrics.counter(
                "keto_changelog_overflow_total", float(n),
                help="bounded change-log entries evicted before every"
                     " reader drained them",
            )
            if first:
                logger.warning(
                    "change log overflowed (cap reached): %d entries"
                    " evicted; lagging readers and watch resumes will"
                    " need a full rebuild/resync", n,
                )

        store.overflow_hook = hook

    def watch_hub(self):
        """Lazy change-watch hub (ketotpu/consistency/watch.py) over this
        registry's store — shared by the gRPC WatchService stream and the
        REST SSE route.  Watch streams are exempt from in-flight admission
        control (a stream parked on a heartbeat would pin a slot forever);
        the hub's own ``watch.max_subscribers`` cap bounds them instead."""
        with self._lock:
            if self._watch_hub is None:
                from ketotpu.consistency.watch import WatchHub

                self._watch_hub = WatchHub(
                    self.store(),
                    metrics=self.metrics(),
                    queue_cap=int(
                        self.config.get("watch.queue_cap", 1024) or 1024
                    ),
                    max_subscribers=int(
                        self.config.get("watch.max_subscribers", 256) or 256
                    ),
                )
            return self._watch_hub

    def result_cache(self):
        """Lazy hot-spot shield (ketotpu/cache/): the snapshot-versioned
        result cache shared by the check engine, the coalescer, and the
        expand handler of this registry.  None when ``cache.enabled`` is
        off.  Follows this registry's store changelog via the same
        listener hook the WatchHub uses."""
        with self._lock:
            if self._result_cache is None:
                if not bool(self.config.get("cache.enabled", True)):
                    return None
                from ketotpu.cache import ResultCache

                scope_fn = None
                if self.tenant_plane() is not None:
                    # keys are tenant-qualified on the shared path: fence
                    # per tenant prefix, so one tenant's write never
                    # invalidates another tenant's entries
                    from ketotpu.tenancy import SEP

                    def scope_fn(ns, _sep=SEP):
                        return ns.split(_sep, 1)[0]

                rc = ResultCache(
                    max_entries=int(
                        self.config.get("cache.max_entries", 65536) or 65536
                    ),
                    shards=int(self.config.get("cache.shards", 8) or 8),
                    max_staleness_ms=int(
                        self.config.get("cache.max_staleness_ms", 100)
                    ),
                    hot_threshold=int(
                        self.config.get("cache.hot_threshold", 0) or 0
                    ),
                    top_k=int(self.config.get("cache.top_k", 16) or 16),
                    metrics=self.metrics(),
                    scope_fn=scope_fn,
                )
                rc.attach_store(self.store())
                self._result_cache = rc
            return self._result_cache

    def _build_store(self, nid: str):
        """One dsn-dispatch path for the default network and every tenant
        (a tenant must never silently land on a different backend)."""
        dsn = self.config.dsn()
        # sql-conn-query spans per statement (pop_connection.go:26-31):
        # a trace of one Check shows engine + storage nested, and
        # queries-per-check becomes measurable.  Only when tracing is
        # actually configured — the default Tracer's span still costs a
        # contextmanager + metrics lock per SQL statement, which the
        # oracle hot path would pay on every query.
        traced = bool(
            self.config.get("tracing.provider", "")
            or self.options.tracer_wrapper is not None
        )
        tracer = self.tracer() if traced else None
        if dsn == "memory":
            return InMemoryTupleStore()  # per-registry: tenants isolated
        if dsn.startswith(("sqlite://", "sqlite:")):
            from ketotpu.storage.sqlite import SQLiteTupleStore

            path = dsn.split("://", 1)[-1] if "://" in dsn \
                else dsn.split(":", 1)[1]
            return SQLiteTupleStore(
                path or ":memory:",
                network_id=nid,
                extra_migrations=self.options.extra_migrations,
                tracer=tracer,
            )
        if dsn.startswith(("postgres://", "postgresql://", "cockroach://")):
            from ketotpu.storage.postgres import PostgresTupleStore

            # CockroachDB speaks the Postgres wire protocol and accepts
            # the same DDL this persister emits — the reference selects
            # it by DSN scheme the same way (dsn_testutils.go:106-160)
            if dsn.startswith("cockroach://"):
                dsn = "postgres://" + dsn[len("cockroach://"):]
            return PostgresTupleStore(
                dsn,
                network_id=nid,
                extra_migrations=self.options.extra_migrations,
                tracer=tracer,
            )
        if dsn.startswith(("mysql://", "mysql:")):
            from ketotpu.storage.mysql import MySQLTupleStore

            return MySQLTupleStore(
                dsn,
                network_id=nid,
                extra_migrations=self.options.extra_migrations,
                tracer=tracer,
            )
        raise ConfigError("dsn", f"unsupported dsn {dsn!r}")

    def namespace_manager(self):
        """Resolve the namespace manager: the tenant plane's qualified
        union when the plane is active (the shared device engine must see
        every tenant's namespaces under their qualified names), otherwise
        the plain config-resolved manager."""
        with self._lock:
            plane = self.tenant_plane()
            if plane is not None:
                # tenant_plane() folded any injected manager into the
                # plane as the per-tenant base; the qualified union IS
                # the root manager from here on
                return plane.manager
            if self._namespace_manager is None:
                self._namespace_manager = self._config_namespace_manager()
            return self._namespace_manager

    def _config_namespace_manager(self):
        """The polymorphic namespaces config (provider.go:311-342):
        literal list | {location: opl-file} | URI string."""
        ns_cfg = self.config.namespaces_config()
        if isinstance(ns_cfg, dict):
            loc = _strip_file_uri(ns_cfg.get("location", "") or "")
            if not loc:
                # {experimental_strict_mode: ...} with no location is
                # valid config (config.py); an empty manager beats a
                # raw FileNotFoundError("") at boot
                return StaticNamespaceManager([])
            return _uri_manager(loc)
        if isinstance(ns_cfg, str):
            return _uri_manager(_strip_file_uri(ns_cfg))
        return StaticNamespaceManager(
            [_namespace_from_config(d) for d in (ns_cfg or [])]
        )

    # -- engines (the EngineProvider seam) ----------------------------------

    def _build_hostlink(self):
        """The multi-host DCN lane (parallel/peerlink.py) from the
        ``engine.mesh.hosts`` block, bound and heartbeating — or None
        when ``peers`` is empty (single-host mesh, lane off).  The
        engine attaches itself in the MeshCheckEngine constructor and
        stops the link in its close()."""
        peers = self.config.get("engine.mesh.hosts.peers") or []
        if len(peers) < 2:
            return None
        from ketotpu.parallel import HostLink

        hid = int(self.config.get("engine.mesh.hosts.host_id") or 0)
        link = HostLink(
            hid, list(peers),
            str(self.config.get("engine.mesh.hosts.secret") or ""),
            heartbeat_ms=float(
                self.config.get("engine.mesh.hosts.heartbeat_ms", 500)
            ),
            miss_budget=int(
                self.config.get("engine.mesh.hosts.heartbeat_misses", 3)
            ),
            rpc_timeout_ms=float(
                self.config.get("engine.mesh.hosts.rpc_timeout_ms", 2000)
            ),
            max_frame_mb=int(
                self.config.get("engine.mesh.hosts.max_frame_mb", 64)
            ),
            metrics=self.metrics(),
            breaker_config=self.breaker_config(),
        )
        listen = str(self.config.get("engine.mesh.hosts.listen") or "")
        if listen:
            link.set_peer_addr(hid, listen)
        # fleet-health seams: inbound frontier checks record under the
        # caller's trace id (span shipping), and every heartbeat carries
        # this host's health digest
        link.registry = self
        link.digest_fn = self.health_digest
        link.bind()
        link.start()
        return link

    def check_engine(self):
        with self._lock:
            if self._check_engine is None:
                kind = self.config.get("engine.kind")
                if kind == "remote":
                    # SO_REUSEPORT worker process: forward batches to the
                    # device-owner process over its unix socket
                    # (server/workers.py)
                    from ketotpu.server.workers import RemoteCheckEngine

                    sock = str(self.config.get("engine.socket") or "")
                    if not sock:
                        raise ConfigError(
                            "engine.socket",
                            "engine.kind=remote needs engine.socket",
                        )
                    self._check_engine = RemoteCheckEngine(
                        sock, rpc_timeout=self._request_timeout(),
                        cache=self.result_cache(), metrics=self.metrics(),
                        shm_threshold=int(
                            self.config.get("engine.wire_shm_threshold")
                            or 262144
                        ),
                        breaker_config=self.breaker_config(),
                        retry_budget_ratio=float(self.config.get(
                            "overload.retry_budget_ratio", 0.1
                        )),
                        logger=self.logger(),
                    )
                elif kind == "tpu":
                    common = dict(
                        max_depth=self.config.max_read_depth(),
                        max_width=self.config.max_read_width(),
                        strict_mode=self.config.strict_mode(),
                        frontier=int(self.config.get("engine.frontier")),
                        arena=int(self.config.get("engine.arena")),
                        max_batch=int(self.config.get("engine.max_batch")),
                        retry_scale=int(self.config.get("engine.retry_scale")),
                        # serving default ON (schema default true): the
                        # constructor default is off for directly-built
                        # engines, the config decides for the daemon
                        fused_dispatch=bool(
                            self.config.get("engine.fused_dispatch", True)
                        ),
                        fused_retry_lanes=int(
                            self.config.get("engine.fused_retry_lanes", 1)
                        ),
                        metrics=self.metrics(),
                        result_cache=self.result_cache(),
                        leopard={
                            "enabled": bool(
                                self.config.get("leopard.enabled", True)
                            ),
                            "max_pairs": int(
                                self.config.get(
                                    "leopard.max_pairs", 4_000_000
                                )
                            ),
                            "rebuild_delta_pairs": int(
                                self.config.get(
                                    "leopard.rebuild_delta_pairs", 4096
                                )
                            ),
                            "rebuild_dirty_sets": int(
                                self.config.get(
                                    "leopard.rebuild_dirty_sets", 512
                                )
                            ),
                        },
                        compaction={
                            "fold": bool(
                                self.config.get(
                                    "engine.compaction.fold", True
                                )
                            ),
                            "background": bool(
                                self.config.get(
                                    "engine.compaction.background", False
                                )
                            ),
                            "fold_max_pairs": int(
                                self.config.get(
                                    "engine.compaction.fold_max_pairs",
                                    200_000,
                                )
                            ),
                            "catchup_rounds": int(
                                self.config.get(
                                    "engine.compaction.catchup_rounds", 8
                                )
                            ),
                        },
                    )
                    n_mesh = int(self.config.get("engine.mesh_devices") or 0)
                    if n_mesh > 0:
                        # graph-sharded serving over an n-device mesh
                        # (parallel/meshengine.py, BASELINE config #5)
                        from ketotpu.parallel import MeshCheckEngine

                        dev = MeshCheckEngine(
                            self.store(), self.namespace_manager(),
                            hostlink=self._build_hostlink(),
                            mesh_devices=n_mesh,
                            mesh_axis=str(
                                self.config.get("engine.mesh_axis") or "shard"
                            ),
                            replicate_hot=bool(self.config.get(
                                "engine.mesh.replicate_hot", True
                            )),
                            hot_min=int(self.config.get(
                                "engine.mesh.hot_min", 64
                            )),
                            replica_max_keys=int(self.config.get(
                                "engine.mesh.replica_max_keys", 32
                            )),
                            rebalance_skew=float(self.config.get(
                                "engine.mesh.rebalance_skew", 4.0
                            )),
                            rebalance_interval_ms=float(self.config.get(
                                "engine.mesh.interval_ms", 0
                            ) or 0),
                            failover=bool(self.config.get(
                                "engine.mesh.failover", True
                            )),
                            **common,
                        )
                    else:
                        dev = DeviceCheckEngine(
                            self.store(), self.namespace_manager(), **common
                        )
                    import jax

                    devices = jax.devices()
                    self.logger().info(
                        "engine.kind=tpu runs on platform=%s device_kind=%s "
                        "count=%d%s",
                        devices[0].platform, devices[0].device_kind,
                        len(devices),
                        f" (mesh over {n_mesh})" if n_mesh > 0 else "",
                    )
                    ms = float(self.config.get("engine.coalesce_ms") or 0)
                    # concurrent single checks ride one device dispatch
                    # (engine/coalesce.py); 0 disables
                    self._check_engine = (
                        CoalescingEngine(
                            dev, window=ms / 1000.0,
                            batch_max=int(
                                self.config.get("engine.coalesce_batch_max")
                                or 0
                            ),
                            default_timeout=self._request_timeout(),
                            cache=self.result_cache(),
                            metrics=self.metrics(),
                            ledger=self.wave_ledger(),
                            pipeline=bool(
                                self.config.get(
                                    "engine.coalesce_pipeline", True
                                )
                            ),
                        )
                        if ms > 0 else dev
                    )
                else:
                    self._check_engine = self.oracle_engine()
            return self._check_engine

    def _request_timeout(self) -> float:
        """Default per-request budget in seconds (limit.request_timeout_ms):
        the fallback deadline for callers that set none; <= 0 disables."""
        return float(
            self.config.get("limit.request_timeout_ms", 30000) or 0
        ) / 1000.0

    def admission(self):
        """Shared in-flight admission controller (limit.max_inflight):
        both REST handler threads and the gRPC interceptors of every port
        draw from this one budget; 0 disables shedding."""
        with self._lock:
            if self._admission is None:
                from ketotpu.server.admission import AdmissionController

                self._admission = AdmissionController(
                    int(self.config.get("limit.max_inflight", 1024) or 0)
                )
            return self._admission

    def front_door_pool(self, door: str, max_workers: int,
                        thread_name_prefix: str):
        """The thread pool of one port's ``door`` (``grpc`` or ``rest``)
        front door: stamped, so a request's wait for a thread is its
        ``pool_wait`` stage, and counted on the scrape for as long as its
        server lives (``keto_frontdoor_pool_busy|max{door}``)."""
        pool = hostwaits.StampedPool(
            max_workers, door=door, thread_name_prefix=thread_name_prefix,
        )
        self._door_pools.add(pool)
        return pool

    def overload(self):
        """The adaptive overload-control plane (server/overload.py):
        AIMD admission limit, brownout ladder, Retry-After hints.  None
        when disabled (overload.enabled false) or when admission itself
        is off (limit.max_inflight 0)."""
        ctl = self.admission()
        with self._lock:
            if not self._overload_built:
                self._overload_built = True
                enabled = bool(self.config.get("overload.enabled", True))
                if enabled and ctl.enabled:
                    from ketotpu.server.overload import OverloadController

                    cfg = self.config
                    self._overload = OverloadController(
                        self, ctl,
                        floor=int(cfg.get("overload.floor", 64)),
                        ceiling=int(cfg.get("overload.ceiling", 8192)),
                        increase=int(cfg.get("overload.increase", 64)),
                        decrease=float(cfg.get("overload.decrease", 0.8)),
                        target_wait_ms=float(
                            cfg.get("overload.target_wait_ms", 25.0)
                        ),
                        interval_s=float(
                            cfg.get("overload.interval_ms", 500)
                        ) / 1000.0,
                        burn_enter=float(
                            cfg.get("overload.burn_enter", 2.0)
                        ),
                        burn_exit=float(cfg.get("overload.burn_exit", 1.0)),
                        hold_s=float(
                            cfg.get("overload.hold_ms", 10000)
                        ) / 1000.0,
                        retry_after_max_s=int(
                            cfg.get("overload.retry_after_max_s", 30)
                        ),
                    )
            return self._overload

    def session_broker(self):
        """Shared streaming-session broker (server/session.py): one per
        ROOT registry — the raw TCP lane and the gRPC StreamCheck
        servicer admit/dispatch through the same object, so session caps
        and credits hold across transports.  None when disabled."""
        if not bool(self.config.get("session.enabled", True)):
            return None
        with self._lock:
            if self._session_broker is None:
                from ketotpu.server.session import SessionBroker

                self._session_broker = SessionBroker(self)
            return self._session_broker

    def retry_after_hint(self) -> str:
        """Load-derived, jittered Retry-After seconds for 429/503
        responses (str, for direct header use); "1" when the overload
        plane is off — the old static hint."""
        try:
            ov = self.overload()
        except Exception:  # noqa: BLE001 - a hint must never fail a shed
            ov = None
        return str(ov.retry_after()) if ov is not None else "1"

    def breaker_lanes(self) -> list:
        """Every live circuit breaker in this process — the worker wire
        (RemoteCheckEngine.breaker) and the per-peer DCN lanes
        (HostLink.breakers()).  Collected from BUILT components only, so
        scrapes and debug probes never trigger an engine build."""
        with self._lock:
            outer = self._check_engine
        out = []
        br = getattr(outer, "breaker", None)
        if br is not None:
            out.append(br)
        link = self.hostlink()
        if link is not None:
            fn = getattr(link, "breakers", None)
            if fn is not None:
                out.extend(fn())
        return out

    def breaker_config(self) -> dict:
        """Shared circuit-breaker knobs for the worker wire and DCN peer
        lanes (overload.breaker.*)."""
        cfg = self.config
        return {
            "window_s": float(
                cfg.get("overload.breaker.window_ms", 10000)
            ) / 1000.0,
            "min_volume": int(cfg.get("overload.breaker.min_volume", 8)),
            "failure_ratio": float(
                cfg.get("overload.breaker.failure_ratio", 0.5)
            ),
            "cooldown_s": float(
                cfg.get("overload.breaker.cooldown_ms", 2000)
            ) / 1000.0,
        }

    def _device_engine(self) -> Optional[DeviceCheckEngine]:
        """The underlying device engine, unwrapping the coalescer facade."""
        eng = self.check_engine()
        inner = getattr(eng, "inner", eng)
        return inner if isinstance(inner, DeviceCheckEngine) else None

    def projection_stats(self) -> dict:
        """Projection/compaction counters for /debug/projection and
        `status --debug`; {} for engine kinds without a device snapshot.
        When this process replicates (owner with a gate engaged, or a
        warm standby), a ``replication`` / ``standby`` sub-dict rides
        along so the same surfaces show the follower's lag and state."""
        dev = self._device_engine()
        fn = getattr(dev, "projection_stats", None) if dev is not None else None
        out = fn() if callable(fn) else {}
        with self._lock:
            gate = self._durability_gate
            standby_fn = self.standby_state_fn
        if gate is not None:
            out = dict(out, replication=gate.stats())
        if standby_fn is not None:
            try:
                out = dict(out, standby=standby_fn())
            except Exception:  # noqa: BLE001 - debug surface must not 500
                pass
        return out

    def durability_gate(self):
        """Lazy write-path replication gate (server/workers.py
        ReplicationGate).  Built on first use — the standby's tail poll
        acks through it, and semi-sync writes wait on it."""
        with self._lock:
            if self._durability_gate is None:
                from ketotpu.server.workers import ReplicationGate

                self._durability_gate = ReplicationGate(
                    str(self.config.get("durability.replication", "async")
                        or "async"),
                    ack_timeout_ms=float(
                        self.config.get("durability.ack_timeout_ms", 2000)
                        or 2000
                    ),
                    metrics=self.metrics(),
                )
            return self._durability_gate

    def oracle_engine(self) -> CheckEngine:
        with self._lock:
            if self._oracle_engine is None:
                self._oracle_engine = CheckEngine(
                    self.store(),
                    self.namespace_manager(),
                    max_depth=self.config.max_read_depth(),
                    max_width=self.config.max_read_width(),
                    strict_mode=self.config.strict_mode(),
                )
            return self._oracle_engine

    def expand_engine(self):
        with self._lock:
            if self._expand_engine is None:
                if self.config.get("engine.kind") == "remote":
                    from ketotpu.server.workers import (
                        RemoteCheckEngine,
                        RemoteExpandEngine,
                    )

                    check = self.check_engine()
                    self._expand_engine = RemoteExpandEngine(
                        str(self.config.get("engine.socket")),
                        check if isinstance(check, RemoteCheckEngine)
                        else None,
                    )
                    return self._expand_engine
                dev = self._device_engine()
                if dev is not None:
                    # device-batched expand with host DFS reassembly
                    # (engine/expand_device.py); oracle fallback inside
                    self._expand_engine = _DeviceExpandAdapter(dev)
                else:
                    self._expand_engine = ExpandEngine(
                        self.store(), max_depth=self.config.max_read_depth()
                    )
            return self._expand_engine

    def list_engine(self):
        """Listing-engine seam for the Leopard reverse-query APIs
        (ListObjects / ListSubjects): the device engine answers from its
        closure index (host-oracle fallback inside), worker processes
        relay to the device owner, and the oracle kind enumerates the
        live store directly."""
        with self._lock:
            if self._list_engine is None:
                if self.config.get("engine.kind") == "remote":
                    from ketotpu.server.workers import (
                        RemoteCheckEngine,
                        RemoteListEngine,
                    )

                    check = self.check_engine()
                    self._list_engine = RemoteListEngine(
                        str(self.config.get("engine.socket")),
                        check if isinstance(check, RemoteCheckEngine)
                        else None,
                    )
                    return self._list_engine
                dev = self._device_engine()
                if dev is not None:
                    self._list_engine = dev
                else:
                    from ketotpu.leopard import HostListEngine

                    self._list_engine = HostListEngine(self.store())
            return self._list_engine

    # -- mapping ------------------------------------------------------------

    def uuid_mapper(self, read_only: bool = False) -> UUIDMapper:
        with self._lock:
            if self._uuid_mapper is None:
                # durable stores expose a persistent reverse store
                # (keto_uuid_mappings, sqlite.py); otherwise the
                # process-wide per-network ReverseStore is used
                maker = getattr(self.store(), "uuid_reverse_store", None)
                self._uuid_mapper = UUIDMapper(
                    self.network_id,
                    reverse_store=maker() if maker is not None else None,
                )
            if read_only:
                # shares the writable mapper's reverse store: read-only
                # skips writes but must resolve what others persisted
                return UUIDMapper(
                    self.network_id, read_only=True,
                    reverse_store=self._uuid_mapper._store,
                )
            return self._uuid_mapper

    def mapper(self) -> Mapper:
        """Writable mapper: interns strings into the reverse store (the
        reference's Mapper(), used on write paths)."""
        with self._lock:
            if self._mapper is None:
                self._mapper = Mapper(self.uuid_mapper(), self.namespace_manager())
            return self._mapper

    def read_only_mapper(self) -> Mapper:
        """ReadOnlyMapper() analog (uuid_mapping.go:60-71): namespace checks
        and forward hashing without populating the reverse store — the
        check/expand/list paths must not grow process memory per request."""
        with self._lock:
            if self._ro_mapper is None:
                self._ro_mapper = Mapper(
                    self.uuid_mapper(read_only=True), self.namespace_manager()
                )
            return self._ro_mapper

    # -- lifecycle ----------------------------------------------------------

    def init(self) -> "Registry":
        """Eager init (RegistryDefault.Init analog): resolve config into
        live components and warm the device snapshot — resuming from the
        configured projection checkpoint when it is still valid, and
        refreshing it after the warm build otherwise."""
        self.namespace_manager()
        self.store()
        # bind the compile observatory before the first jit fires so the
        # warm-boot compiles are already attributed and counted
        self.compile_watch()
        # host pauses (gc, scheduler, store lock) count into THIS
        # registry's metrics from here on; one watch a process, last
        # bind wins (ketotpu/hostwaits.py)
        hostwaits.pauses().bind(self.metrics(), self.logger())
        eng = self._device_engine()
        if eng is not None:
            ckpt_path = str(self.config.get("engine.checkpoint") or "")
            if ckpt_path:
                resumed = eng.load_checkpoint(ckpt_path)
                # every full rebuild from here on refreshes the checkpoint
                eng.checkpoint_path = ckpt_path
                self.logger().info(
                    "projection checkpoint %s: %s", ckpt_path,
                    "resumed" if resumed else "stale/absent, will refresh",
                )
            eng.snapshot()
        # a bulk-loaded store's own indexes, before anything queries it
        build_indexes = getattr(self.store(), "build_indexes", None)
        if build_indexes is not None:
            build_indexes()
        # arm the fleet health plane: the SLO engine pre-registers its
        # gauge vocabulary, the watchdog starts its rule-evaluation loop
        self.slo()
        wd = self.watchdog()
        if wd is not None:
            wd.start()
        # the overload plane (server/overload.py) is built lazily via
        # overload() and its 2Hz control thread is started by the
        # serving daemon (server/daemon.py), not here: a bare registry
        # (tests, tooling, bench probes) must not spawn — and leak — a
        # background ticker per instance
        return self

    def sample_engine_metrics(self) -> None:
        """Refresh device-engine gauges (scraped via /metrics/prometheus):
        the SURVEY §5.5 'per-batch device metrics' — fallbacks, retries,
        rebuilds, overlay applies, checkpoint errors."""
        with self._lock:
            outer = self._check_engine
            rc = self._result_cache
            plane = self._tenant_plane
        if plane is not None:
            try:
                plane.publish(self.metrics())
            except Exception:  # noqa: BLE001 - scrape must not fail
                pass
        if rc is not None:
            cs = rc.stats()
            m = self.metrics()
            m.gauge("keto_cache_entries", cs["entries"],
                    help="result-cache entries resident")
            m.gauge("keto_cache_hit_ratio", cs["hit_ratio"],
                    help="lifetime cache hit ratio (hits / probes)")
        with self._lock:
            trace = self._trace_store
            shadow = self._shadow
        if trace is not None:
            ts = trace.stats()
            m = self.metrics()
            m.gauge("keto_trace_store_promoted", ts["promoted_held"],
                    help="traces currently held in the promoted store")
            m.gauge("keto_trace_store_recent", ts["recent_held"],
                    help="unpromoted traces parked in the recent ring")
        if shadow is not None:
            ss = shadow.stats()
            m = self.metrics()
            m.gauge("keto_shadow_queue_depth", ss["queued"],
                    help="shadow samples awaiting oracle replay")
            m.gauge("keto_shadow_divergence_ledger_size",
                    len(shadow.ledger()),
                    help="divergence records currently held")
        # SLO plane: advance the delta ring and refresh keto_slo_* gauges
        # on every scrape, so burn rates stay live without request-path work
        slo = self.slo()
        if slo is not None:
            try:
                slo.publish()
            except Exception:  # noqa: BLE001 - scrape must not fail
                pass
        # front doors: threads inside a call and the most there can be,
        # added up over the ports' pools of each door
        doors: Dict[str, list] = {}
        for pool in list(self._door_pools):
            tally = doors.setdefault(pool.door, [0, 0])
            tally[0] += pool.busy
            tally[1] += pool.ceiling
        m = self.metrics()
        for door, (busy, ceiling) in doors.items():
            m.gauge("keto_frontdoor_pool_busy", busy, door=door,
                    help="front-door pool threads inside a call")
            m.gauge("keto_frontdoor_pool_max", ceiling, door=door,
                    help="most threads the front door's pools will start")
        # overload plane: adaptive limit + ladder stage gauges stay live
        # even between ticks; breaker lanes publish their state codes
        with self._lock:
            admission = self._admission
            overload = self._overload
        if admission is not None and admission.enabled:
            m = self.metrics()
            m.gauge("keto_admission_limit", float(admission.limit),
                    help="current adaptive in-flight admission limit")
            m.gauge("keto_admission_inflight", float(admission.inflight),
                    help="units of work currently admitted")
            m.gauge("keto_overload_stage", float(admission.stage),
                    help="brownout ladder stage (0=normal .. 3=full shed)")
        lanes = (
            overload.breakers() if overload is not None
            else self.breaker_lanes()
        )
        if lanes:
            m = self.metrics()
            for br in lanes:
                m.gauge(
                    "keto_breaker_state", float(br.state_code()),
                    help="circuit breaker state "
                         "(0=closed 1=open 2=half_open)",
                    lane=br.lane,
                )
        # fleet view: how many DCN peers are reporting health digests and
        # the worst fast-window burn heard across them via heartbeats
        link = self.hostlink()
        if link is not None:
            m = self.metrics()
            reporting = 0
            peer_burn = 0.0
            for row in link.peer_rows():
                digest = row.get("digest")
                if isinstance(digest, dict):
                    reporting += 1
                    burn = digest.get("burn")
                    if isinstance(burn, dict):
                        try:
                            peer_burn = max(
                                peer_burn, float(burn.get("fast", 0.0))
                            )
                        except (TypeError, ValueError):
                            pass
            m.gauge("keto_fleet_peers_reporting", reporting,
                    help="DCN peers whose heartbeats carry a health digest")
            m.gauge("keto_fleet_peer_burn_fast_max", peer_burn,
                    help="worst fast-window SLO burn reported by any peer")
        eng = getattr(outer, "inner", outer)
        if not isinstance(eng, DeviceCheckEngine):
            return
        m = self.metrics()
        if isinstance(outer, CoalescingEngine):
            # the states the wave threads are in right now hand over their
            # seconds, so two scrapes' delta adds up to the time between
            outer.flush_thread_states()
            m.gauge("keto_engine_coalesced_waves", outer.waves,
                    help="coalesced check dispatch waves")
            m.gauge("keto_coalescer_waves_ahead_total", outer.waves_ahead,
                    help="waves submitted to the device while an earlier "
                         "wave was not yet collected")
            m.gauge("keto_engine_coalesced_checks", outer.coalesced,
                    help="single checks served via coalesced waves")
            m.gauge("keto_singleflight_collapsed", outer.singleflight_collapsed,
                    help="checks collapsed onto an identical pending slot")
            m.gauge("keto_coalescer_cache_hits", outer.cache_hits,
                    help="checks served from the cache before admission")
            m.gauge("keto_engine_batch_ingested", outer.batch_ingested,
                    help="batch items ridden on coalesced waves")
        m.gauge("keto_engine_oracle_fallbacks", eng.fallbacks,
                help="queries answered by the host oracle")
        m.gauge("keto_engine_device_failures", eng.device_failures,
                help="device faults the host path covered for")
        m.gauge("keto_engine_device_retries", eng.retries,
                help="queries re-run at wider device capacity")
        m.gauge("keto_engine_snapshot_rebuilds", eng.rebuilds,
                help="full device snapshot projections")
        m.gauge("keto_engine_overlay_applies", eng.overlay_applies,
                help="O(delta) overlay write applications")
        m.gauge("keto_engine_checkpoint_errors", eng.checkpoint_errors,
                help="projection checkpoint save failures")
        m.gauge("keto_engine_dispatches", eng.dispatches,
                help="device batch dispatches")
        # how submit cut its batches (engine/wave.py) and what the waves'
        # capacities left unanswered on the first pass, before any retry
        m.gauge("keto_engine_tickets_total", eng.tickets,
                help="batches submitted to the device engine")
        m.gauge("keto_engine_ticket_waves_total", eng.ticket_waves,
                help="waves the submitted batches were cut into")
        for tier, rows in eng.overflow_rows.items():
            m.gauge("keto_engine_overflow_rows_total", rows,
                    help="rows a wave's capacity overflowed on, per tier",
                    tier=tier)
        for rung, roots in eng.expand_roots.items():
            m.gauge("keto_engine_expand_roots_total", roots,
                    help="subject-set Expand roots by what answered them: "
                         "the first rung of level capacities, the full "
                         "rung, or the host oracle",
                    rung=rung)
        # fused tiered dispatch (engine/fused.py): whole-cascade waves
        # and per-tier row attribution from the returned device masks
        m.gauge("keto_fused_waves_total", eng.fused_waves,
                help="waves dispatched as one fused device program")
        m.gauge("keto_fused_d2h_fetches_total", eng.fused_d2h_fetches,
                help="device-to-host fetches for fused waves (1 per wave)")
        m.gauge("keto_fused_general_rows_total", eng.fused_general_rows,
                help="fused-wave rows that needed the general tier")
        m.gauge("keto_fused_general_lanes_total", eng.fused_general_lanes,
                help="lanes the fused waves ran the general tier at")
        for table, gathers in eng.fused_probe_gathers.items():
            m.gauge("keto_fused_probe_gathers_total", gathers,
                    help="element gathers one lookup of the table cost, "
                         "added a fused wave at its collect (over "
                         "keto_fused_waves_total: gathers a lookup)",
                    table=table)
        for rung, levels in eng.fast_rung_levels.items():
            m.gauge("keto_fused_fast_rung_levels_total", levels,
                    help="folded levels of the fast BFS by the rung they "
                         "ran at: a quarter of the wave's rows, its rows, "
                         "or the level's full size (a narrow rung's redo "
                         "counts as full; counted at collect)",
                    rung=rung)
        for tier, rows in eng.fused_tier_rows.items():
            m.gauge("keto_fused_tier_rows_total", rows,
                    help="fused-wave rows attributed per answering tier",
                    tier=tier)
        for what, seconds in hostwaits.LAZY_BUILD_SECONDS.items():
            m.gauge("keto_host_lazy_build_seconds_total", seconds,
                    help="seconds spent building a host index on first use "
                         "(the vocabulary's bulk form, the store's forward "
                         "index), in whatever thread met it first",
                    what=what)
        m.gauge("keto_host_sched_lag_seconds_total",
                hostwaits.SCHED_LAG_SECONDS,
                help="seconds the scheduling probe's 20 ms sleeps woke "
                     "late, every tick, collections taken out")
        m.gauge("keto_host_sched_ticks_total", hostwaits.SCHED_TICKS,
                help="ticks of the scheduling probe")
        m.gauge("keto_engine_projection_build_seconds",
                eng.projection_build_s,
                help="host-side snapshot projection build wall time")
        m.gauge("keto_engine_projection_upload_seconds",
                eng.projection_upload_s,
                help="device snapshot upload wall time")
        # write-path compaction gauges (engine/tpu.py): how each overlay
        # escape resolved (fold vs full rebuild vs background swap) and
        # how full the overlay is against its thresholds
        proj_fn = getattr(eng, "projection_stats", None)
        if proj_fn is not None:
            ps = proj_fn()
            m.gauge("keto_projection_generation", ps["generation"],
                    help="snapshot generations published")
            m.gauge("keto_projection_rebuilds_total", ps["rebuilds"],
                    help="full snapshot re-projections")
            m.gauge("keto_projection_folds_total", ps["folds"],
                    help="incremental CSR folds of the changelog slice")
            m.gauge("keto_projection_compactions_total", ps["compactions"],
                    help="background generation swaps published")
            m.gauge("keto_projection_compaction_errors_total",
                    ps["compaction_errors"],
                    help="background compactor failures (serving unaffected)")
            m.gauge("keto_projection_compaction_in_flight",
                    int(ps["compaction_in_flight"]),
                    help="1 while a background generation build is running")
            m.gauge("keto_projection_pending_changes", ps["pending_changes"],
                    help="drained writes not yet covered by the served view")
            m.gauge("keto_projection_overlay_pairs", ps["overlay_pairs"],
                    help="membership pairs resident in the delta overlay")
            m.gauge("keto_projection_overlay_dirty", ps["overlay_dirty"],
                    help="CSR rows marked dirty in the delta overlay")
            cap = max(1, ps["overlay_pair_cap"])
            m.gauge("keto_projection_overlay_occupancy",
                    ps["overlay_pairs"] / cap,
                    help="overlay pair fill fraction against its threshold")
            for table, st in ps["tables"].items():
                m.gauge("keto_projection_table_rounds", st["rounds"],
                        help="probe rounds a lookup of the table unrolls",
                        table=table)
                m.gauge("keto_projection_table_lookup_gathers",
                        st["lookup_gathers"],
                        help="element gathers one lookup of the table issues",
                        table=table)
                m.gauge("keto_projection_table_tag_salt",
                        int(np.max(st["tag_salt"])),
                        help="tag salt index of the table (a mesh: the "
                             "largest shard's); above 0 the tag invariant "
                             "walked it",
                        table=table)
                m.gauge("keto_projection_table_split_buckets",
                        st["split_buckets"],
                        help="buckets deeper than the probe rounds, split "
                             "in place so that every lookup probes the "
                             "same rounds (0 for the overlay's tables)",
                        table=table)
                m.gauge("keto_projection_table_split_level_max",
                        st["split_level_max"],
                        help="deepest split level of the table's buckets "
                             "(a bucket of level s has 2^s parts)",
                        table=table)
                m.gauge("keto_projection_table_pad_slots", st["pad_slots"],
                        help="slots left empty between the parts of split "
                             "buckets",
                        table=table)
            for group, sizes in ps["device_bytes"].items():
                for kind, nbytes in sizes.items():
                    m.gauge("keto_projection_device_bytes", nbytes,
                            help="device bytes of the served projection by "
                                 "group of arrays, reckoned from its counts: "
                                 "padded as built, live at exact lengths",
                            group=group, kind=kind)
            for op, times in ps["tag_rejects"].items():
                m.gauge("keto_projection_tag_rejects_total", times,
                        help="times a table's layout refused its keys: two "
                             "of one bucket shared a tag (a build or an "
                             "overlay build took another tag salt, a splice "
                             "fell back to a full build), or no split level "
                             "separated a bucket (another split salt)",
                        op=op)
        # demand-adaptive scheduling state: EMA frontier occupancy per BFS
        # level (units of active roots), for the fast path and the general
        # (AND/NOT) tier's skeleton + fast-leaf sub-runs
        for path, ema in (
            ("fast", eng._occ_ema),
            ("general", eng._gen_occ_ema),
            ("gen_fast_bfs", eng._gen_fast_occ_ema),
        ):
            if ema is None:
                continue
            for lvl, val in enumerate(np.asarray(ema).ravel()):
                m.gauge("keto_engine_occupancy", float(val),
                        help="EMA per-level frontier occupancy",
                        path=path, level=str(lvl))
        # Leopard closure-index gauges (ketotpu/leopard/): index size,
        # delete-dirtied sets, and how often a check or listing had to be
        # answered by the host oracle instead of the index
        leo_fn = getattr(eng, "leopard_stats", None)
        if leo_fn is not None:
            ls = leo_fn()
            m.gauge("keto_leopard_pairs", ls["pairs"],
                    help="closure (set, element) pairs resident "
                         "(base + delta)")
            m.gauge("keto_leopard_dirty_sets", ls["dirty_sets"],
                    help="closure set ids dirtied by deletions")
            m.gauge("keto_leopard_fallbacks_total",
                    ls["fallbacks"] + ls["list_fallbacks"],
                    help="index declines answered by the host oracle")
            m.gauge("keto_leopard_answered", ls["answered"],
                    help="checks answered from the closure index")
            m.gauge("keto_leopard_builds", ls["builds"],
                    help="closure index full builds")
            m.gauge("keto_leopard_build_seconds", ls["build_s"],
                    help="last closure build wall time")
        for outcome, rows in eng.leopard_rows.items():
            m.gauge("keto_leopard_rows_total", rows,
                    help="check rows the closure index was asked about, by "
                         "what became of them: answered, or declined as "
                         "tainted, dirty, ineligible, or hit beyond the "
                         "depth budget (counted at collect)",
                    outcome=outcome)
        if eng._gen_fast_ema is not None:
            m.gauge("keto_engine_occupancy", float(eng._gen_fast_ema),
                    help="EMA per-level frontier occupancy",
                    path="gen_fast_leaves", level="0")
        # per-shard serving gauges: the mesh engine attributes batches /
        # fallbacks / overlay pressure / occupancy per shard; the
        # single-device engine reports the same vocabulary as shard "0"
        # so dashboards need one query either way
        stats_fn = getattr(eng, "shard_stats", None)
        if stats_fn is not None:
            rows = stats_fn()
        else:
            ov = eng._overlay.size() if eng._overlay is not None else (0, 0)
            rows = [{
                "shard": 0,
                "batches": eng.dispatches,
                "fallbacks": eng.fallbacks,
                "overlay_pairs": ov[0],
                "overlay_dirty": ov[1],
                "nodes": int(getattr(eng._snap, "n_nodes", 0) or 0)
                if eng._snap is not None else 0,
                "gen_occupancy": 0.0,
            }]
        for row in rows:
            s = str(row["shard"])
            m.gauge("keto_mesh_shard_batches", row["batches"],
                    help="device batch dispatches seen by this shard",
                    shard=s)
            m.gauge("keto_mesh_shard_fallbacks", row["fallbacks"],
                    help="oracle fallbacks attributed to this shard",
                    shard=s)
            m.gauge("keto_mesh_shard_overlay_pairs", row["overlay_pairs"],
                    help="overlay pairs resident on this shard", shard=s)
            m.gauge("keto_mesh_shard_overlay_dirty", row["overlay_dirty"],
                    help="overlay-dirtied CSR rows on this shard", shard=s)
            m.gauge("keto_mesh_shard_nodes", row["nodes"],
                    help="projected graph nodes on this shard", shard=s)
            m.gauge("keto_mesh_shard_gen_occupancy", row["gen_occupancy"],
                    help="last general dispatch's BFS occupancy partial",
                    shard=s)
            m.gauge("keto_mesh_replica_keys", row.get("replica_keys", 0),
                    help="hot keys replicated ONTO this shard", shard=s)
            m.gauge("keto_mesh_shard_down", int(row.get("down", False)),
                    help="1 while this shard is degraded to fallback "
                         "serving after a device fault", shard=s)
        # engine-level replication / rebalance / failover counters (the
        # single-device engine reports the same names at zero so the
        # vocabulary is scrape-stable across engine kinds)
        mesh_fn = getattr(eng, "mesh_stats", None)
        ms = mesh_fn() if mesh_fn is not None else {}
        m.gauge("keto_mesh_replica_routed", ms.get("replica_routed", 0),
                help="root queries served by a non-owner replica")
        m.gauge("keto_mesh_replications", ms.get("replications", 0),
                help="hot keys replicated by the controller")
        m.gauge("keto_mesh_rebalances", ms.get("rebalances", 0),
                help="skew-triggered repartition publishes")
        m.gauge("keto_mesh_shard_recoveries", ms.get("shard_recoveries", 0),
                help="faulted shards recovered and re-shipped")
        m.gauge("keto_mesh_load_skew", ms.get("skew", 1.0),
                help="max/mean per-shard routed-root load ratio")
        # multi-host topology gauges (parallel/peerlink.py): emitted only
        # when a hostlink is attached — a single-host mesh scrapes none
        # of the keto_mesh_peer_* / keto_mesh_host_down family
        peers_fn = getattr(eng, "peer_stats", None)
        peer_rows = peers_fn() if peers_fn is not None else []
        for row in peer_rows:
            h = str(row["peer"])
            m.gauge("keto_mesh_host_down", int(row["down"]),
                    help="1 while this peer host is marked down by "
                         "heartbeat loss", host=h)
            m.gauge("keto_mesh_peer_heartbeat_age_seconds",
                    max(row["heartbeat_age_s"], 0.0),
                    help="seconds since this peer last answered or sent "
                         "a heartbeat", host=h)
            m.gauge("keto_mesh_peer_frontier_roundtrips",
                    row["frontier_roundtrips"],
                    help="completed cross-host frontier exchanges with "
                         "this peer", host=h)
            m.gauge("keto_mesh_peer_routed", row["routed"],
                    help="root queries shipped to this peer host",
                    host=h)
            m.gauge("keto_mesh_peer_fallbacks", row["fallbacks"],
                    help="oracle fallbacks attributed to this peer "
                         "(host down, call failed, or budget expired)",
                    host=h)
        if peer_rows:
            m.gauge("keto_mesh_peer_frontier_rtt_ms_p50",
                    ms.get("peer_frontier_rtt_p50_ms", 0.0),
                    help="median cross-host frontier round-trip time")
            m.gauge("keto_mesh_peer_deadline_total",
                    ms.get("peer_deadline_degrades", 0),
                    help="cross-host rows degraded to the oracle because "
                         "the wave's deadline budget expired")
            m.gauge("keto_mesh_peer_recoveries",
                    ms.get("peer_recoveries", 0),
                    help="peer hosts that answered again after being "
                         "marked down")

    def health(self) -> Dict[str, str]:
        """Readiness probe results per check: "ok", a returned string
        (``"degraded: ..."`` keeps the daemon SERVING but surfaced), or
        the raised exception's message (down)."""
        out = {}
        for name, check in self.readiness_checks.items():
            try:
                value = check()
                out[name] = str(value) if isinstance(value, str) else "ok"
            except Exception as e:  # noqa: BLE001 - reported, not raised
                out[name] = str(e)
        # built-in: a device engine serving off the CPU oracle is degraded.
        # Only consult an engine that is already BUILT — a health probe
        # must never trigger a multi-second lazy snapshot build.
        with self._lock:
            outer = self._check_engine
        eng = getattr(outer, "inner", outer)
        degraded = getattr(eng, "is_degraded", None)
        if degraded is not None and degraded():
            out["engine"] = (
                "degraded: device dispatch failing "
                f"({eng.device_failures} failures), serving on CPU oracle"
            )
        return out

    def close_engines(self) -> None:
        """Retire engine workers (the coalescer's wave thread and any
        pending slots) ahead of daemon shutdown; tenants included."""
        with self._lock:
            engines = [self._check_engine] + [
                t._check_engine for t in self._tenants.values()
            ]
            hubs = [self._watch_hub] + [
                t._watch_hub for t in self._tenants.values()
            ]
            shadows = [self._shadow] + [
                t._shadow for t in self._tenants.values()
            ]
            watchdogs = [self._watchdog, self._overload]
            broker = self._session_broker
            self._session_broker = None
        hostwaits.pauses().unbind(self.metrics())
        if broker is not None:
            try:
                broker.shutdown()
            except Exception:  # noqa: BLE001 - shutdown must not raise
                pass
        for eng in engines + hubs + shadows + watchdogs:
            close = getattr(eng, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001 - shutdown must not raise
                    pass


class _DeviceExpandAdapter:
    """ExpandEngine facade over DeviceCheckEngine.batch_expand so the
    handler's build_tree seam (expand/engine.go:43) stays engine-agnostic."""

    def __init__(self, engine: DeviceCheckEngine):
        self._engine = engine

    def build_tree(self, subject, rest_depth: int = 0):
        return self._engine.batch_expand([subject], rest_depth)[0]


def _uri_manager(path: str):
    """URI namespace flavor (provider.go:315-342): a directory is the
    legacy per-file watcher, a file is an OPL document."""
    if os.path.isdir(path):
        return DirectoryNamespaceManager(path)
    return OPLFileNamespaceManager(path)


def _strip_file_uri(location: str) -> str:
    if location.startswith("file://"):
        return location[len("file://"):]
    return location


def _namespace_from_config(d: Dict[str, Any]) -> Namespace:
    """Literal namespace entry: {"name": ..., ["id": legacy int]}."""
    return Namespace(name=str(d["name"]), relations=[])
