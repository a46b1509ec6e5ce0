"""Composition root: wire holder + executor + API + HTTP + observability.

Reference: ``server.go`` (SURVEY.md §3.3) — functional options
assembling holder/cluster/executor/handlers/stats/tracing, lifecycle
``Open``/``Close``, and background loops.  Here the wiring input is the
:class:`pilosa_tpu.cli.config.Config` dataclass.
"""

from __future__ import annotations

from pilosa_tpu.api import API, Server as HttpServer
from pilosa_tpu.cli.config import Config
from pilosa_tpu.exec import Executor
from pilosa_tpu.obs import Stats, get_logger
from pilosa_tpu.store import Holder


class PilosaTPUServer:
    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.logger = get_logger(verbose=cfg.verbose,
                                 fmt=cfg.log_format or None)
        if cfg.stats_backend == "statsd":
            # statsd emission rides ON TOP of the in-process registry
            # (subclass): /metrics keeps serving Prometheus text while
            # every count/gauge/timing also emits a UDP statsd packet
            from pilosa_tpu.obs import StatsdStats
            host, _, port = cfg.statsd_address.rpartition(":")
            self.stats = StatsdStats(host or "127.0.0.1",
                                     int(port or 8125))
            self.logger.info("stats: statsd emission to %s",
                             cfg.statsd_address)
        elif cfg.stats_backend not in ("", "prometheus"):
            raise ValueError(
                f"unknown stats_backend {cfg.stats_backend!r} "
                "(expected '', 'prometheus' or 'statsd')")
        else:
            self.stats = Stats()
        self.holder = Holder(cfg.data_dir, fsync=cfg.fsync)
        self.executor: Executor | None = None
        self.api: API | None = None
        self.http: HttpServer | None = None
        self.grpc = None
        self.cluster = None
        self.diagnostics = None
        self.scrubber = None

    def open(self) -> "PilosaTPUServer":
        if self.cfg.faults:
            # arm configured failpoints BEFORE any subsystem opens, so
            # boot-time seams (oplog replay, mmap registration) are
            # already injectable; a bad spec fails the boot loudly
            from pilosa_tpu import fault
            fault.configure(self.cfg.faults, logger=self.logger)
        if self.cfg.jax_coordinator:
            # multi-host pod slice: one process per host joins the jax
            # runtime before any device use; jax.devices() then spans
            # every chip and the mesh placement shards across the full
            # slice with collectives over ICI/DCN (SURVEY.md §3.6)
            import jax
            jax.distributed.initialize(
                coordinator_address=self.cfg.jax_coordinator,
                num_processes=self.cfg.jax_num_processes or None,
                process_id=(self.cfg.jax_process_id
                            if self.cfg.jax_process_id >= 0 else None))
            self.logger.info("jax.distributed: process %d of %d",
                             jax.process_index(), jax.process_count())
        from pilosa_tpu.store import syswrap
        syswrap.GLOBAL.set_max(self.cfg.max_map_count)
        # disk-health governor (r19): wire stats + knobs BEFORE the
        # holder opens, so boot-time snapshot verification already
        # quarantines (and counts) through the configured registry
        self.holder.storage_health.configure(
            base=self.cfg.data_dir, stats=self.stats, logger=self.logger,
            min_free_bytes=self.cfg.disk_min_free_bytes,
            probe_seconds=self.cfg.disk_probe_seconds)
        self.holder.open()
        placement = None
        if self.cfg.mesh:
            from pilosa_tpu.parallel import local_placement
            placement = local_placement()
            if placement is not None:
                self.logger.info("mesh: sharding over %d devices",
                                 placement.n_devices)
        self.executor = Executor(
            self.holder, placement=placement, stats=self.stats,
            plane_budget=self.cfg.plane_budget_bytes,
            count_batch_window=self.cfg.count_batch_window,
            max_concurrent=self.cfg.max_concurrent_queries,
            plane_sidecars=self.cfg.plane_sidecars,
            delta_cells=self.cfg.delta_buffer_cells,
            delta_compact_fraction=self.cfg.delta_compact_fraction,
            tree_fusion=self.cfg.tree_fusion,
            dispatch_pipeline_depth=self.cfg.dispatch_pipeline_depth,
            solo_fastlane=self.cfg.solo_fastlane,
            dispatch_watchdog_seconds=self.cfg.dispatch_watchdog_seconds,
            device_health_probe_seconds=(
                self.cfg.device_health_probe_seconds),
            plane_paging=self.cfg.plane_paging,
            plane_page_bytes=self.cfg.plane_page_bytes,
            tenant_byte_quota=self.cfg.tenant_byte_quota,
            tenant_qps_quota=self.cfg.tenant_qps_quota,
            tenant_slot_quota=self.cfg.tenant_slot_quota,
            fused_warmup=self.cfg.fused_warmup)
        self._log_boot(placement)
        self.api = API(self.holder, self.executor,
                       query_timeout=self.cfg.query_timeout,
                       trace_sample_rate=self.cfg.trace_sample_rate,
                       slow_query_threshold=self.cfg.slow_query_threshold)
        from pilosa_tpu.api import tls as tlsmod
        from pilosa_tpu.cli.config import tls_of
        tls_cfg = tls_of(self.cfg)
        ssl_ctx = tlsmod.server_context(tls_cfg)
        if ssl_ctx is not None:
            self.logger.info(
                "tls: serving HTTPS%s; internode calls use TLS",
                " with required client certs"
                if tls_cfg.enable_client_auth else "")
        # construct (binds the socket; resolves port 0) before the
        # cluster needs the advertised address, then serve
        self.http = HttpServer(self.api, self.cfg.host, self.cfg.port,
                               stats=self.stats, logger=self.logger,
                               ssl_context=ssl_ctx)
        if self.cfg.seeds or self.cfg.replicas > 1 or self.cfg.cluster_enabled:
            from pilosa_tpu.cluster import Cluster
            self.cluster = Cluster(self.cfg, self.api, stats=self.stats,
                                   logger=self.logger,
                                   port=self.http.address[1])
            self.api.cluster = self.cluster
        self.http.start()
        if self.cfg.grpc_bind:
            from pilosa_tpu.api.grpc import GrpcServer
            ghost, _, gport = self.cfg.grpc_bind.rpartition(":")
            self.grpc = GrpcServer(
                self.api, ghost or "127.0.0.1", int(gport),
                credentials=tlsmod.grpc_server_credentials(tls_cfg),
            ).start()
            self.logger.info("grpc: listening on %s:%d",
                             ghost or "127.0.0.1", self.grpc.port)
        if self.cluster is not None:
            self.cluster.open()
        # background scrubber (r19): re-verifies every on-disk
        # checksum at the configured byte budget; corrupt fragments
        # quarantine and — in cluster mode — repair from a healthy
        # replica through the AAE data path.  scrub_bytes_per_second=0
        # restores the pre-r19 contract (no thread at all).
        from pilosa_tpu.store.scrub import Scrubber
        self.scrubber = Scrubber(
            self.holder, interval=self.cfg.scrub_interval_seconds,
            bytes_per_second=self.cfg.scrub_bytes_per_second,
            stats=self.stats, logger=self.logger,
            on_corrupt=(self.cluster.repair_quarantined
                        if self.cluster is not None else None)).start()
        self.api.scrubber = self.scrubber
        from pilosa_tpu.obs.diagnostics import Diagnostics
        self.diagnostics = Diagnostics(
            self.holder, self.cluster,
            interval=self.cfg.diagnostics_interval,
            logger=self.logger, stats=self.stats,
            slow_log=self.api.slow_log,
            executor=self.executor).start()
        return self

    def _log_boot(self, placement) -> None:
        """One line naming what this process actually serves on — the
        facts a fallback would otherwise hide (chip_smoke.py and the
        operator read them here instead of assuming)."""
        import importlib.metadata as md

        import jax

        from pilosa_tpu.engine import _jaxcfg
        from pilosa_tpu.store import native

        def version(pkg: str) -> str:
            try:
                return md.version(pkg)
            except md.PackageNotFoundError:
                return "absent"

        devs = jax.devices()
        self.logger.info(
            "boot: platform=%s device_kind=%r devices=%d serving=%s "
            "native_codec=%s compile_cache=%s "
            "jax=%s jaxlib=%s libtpu=%s",
            devs[0].platform, devs[0].device_kind, len(devs),
            (f"mesh({placement.n_devices})" if placement is not None
             else "single"),
            "loaded" if native.available() else "python-fallback",
            _jaxcfg.compile_cache_dir(),
            version("jax"), version("jaxlib"), version("libtpu"))

    def close(self) -> None:
        if self.diagnostics is not None:
            self.diagnostics.close()
        if self.scrubber is not None:
            self.scrubber.close()
        if self.cluster is not None:
            self.cluster.close()
        if self.grpc is not None:
            self.grpc.close()
        if self.http is not None:
            self.http.close()
        if self.executor is not None:
            self.executor.translate.close()
        self.holder.close()

    @property
    def port(self) -> int:
        return self.http.address[1]
