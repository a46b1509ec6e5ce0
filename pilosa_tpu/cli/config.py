"""Layered configuration: TOML file ⊕ ``PILOSA_*`` env vars ⊕ CLI flags.

Reference: ``server/config.go`` with cobra+viper layering (SURVEY.md
§3.3, §6): flags override env, env overrides file, file overrides
defaults.  One typed dataclass; ``effective()`` dumps the resolved
config the way the reference's startup log does.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field as dc_field

ENV_PREFIX = "PILOSA_"


@dataclass
class Config:
    bind: str = "127.0.0.1:10101"
    data_dir: str = "~/.pilosa_tpu"
    verbose: bool = False
    # "text" (key=value lines) or "json": one JSON object per line
    # with the active trace id injected as ``traceId`` — the
    # correlated-logs leg of the observability pane (a latency
    # exemplar, its /internal/traces tree, and its log lines join
    # on one id)
    log_format: str = "text"
    fsync: bool = False
    # cluster
    name: str = ""                      # node id; default derived from bind
    seeds: list[str] = dc_field(default_factory=list)  # host:port of peers
    replicas: int = 1
    cluster_enabled: bool = False       # force cluster mode without seeds
                                        # (single seed node of a new cluster)
    anti_entropy_interval: float = 600.0  # seconds; 0 disables
    heartbeat_interval: float = 2.0
    # read availability (serving through failure):
    # replica-failover hops a fan-out read leg may take after a
    # transport-class failure before the query fails (reads are
    # idempotent by the internode contract; writes never fail over)
    failover_max_depth: int = 2
    # hedge a straggling fan-out leg onto a live replica after this
    # many seconds — first answer wins, the loser is abandoned.
    # 0 disables (default); 0.15 is the documented starting point for
    # sub-second read SLOs (≈ a few p99s of a healthy internode leg)
    hedge_after: float = 0.0
    # consecutive transport failures that OPEN a peer's circuit
    # breaker (open peers are skipped at read-routing time; half-open
    # probes ride the heartbeat loop)
    breaker_threshold: int = 3
    # write availability (durable hinted handoff): a write that finds
    # a replica down is applied on the live replicas and durably
    # hinted for the dead one, then replayed in order on rejoin.
    # hint_max_age bounds the handoff window (seconds): once a peer's
    # oldest pending hint outlives it, strict writes (Clear/ClearRow/
    # Store) flip back to loud 503 refusal and Set falls back to
    # AAE-only repair — the hint log cannot grow without bound.
    # <= 0 disables handoff entirely (the pre-r13 fail-fast contract).
    hint_max_age: float = 300.0
    # ops per replay POST when draining a peer's hint log
    hint_replay_batch: int = 256
    diagnostics_interval: float = 0.0   # opt-in usage snapshot; 0 = off
    # observability backends
    stats_backend: str = ""             # "" = in-process /metrics only;
                                        # "statsd" also emits UDP statsd
    statsd_address: str = "127.0.0.1:8125"
    # always-on tracing: every query runs under a per-request span tree
    # (X-Pilosa-Trace-Id on each response); this fraction of ordinary
    # queries is RETAINED in the /internal/traces ring without the
    # caller asking (profile=true and slow queries always retain)
    trace_sample_rate: float = 0.01
    # queries slower than this (seconds) are captured — PQL, shards,
    # duration, full span tree — behind GET /debug/slow; 0 disables
    slow_query_threshold: float = 1.0
    # fault injection (chaos testing): JSON list of failpoint specs,
    # armed at boot — see pilosa_tpu.fault.configure.  Usually set via
    # PILOSA_FAULTS; live arming uses POST /internal/fault instead.
    faults: str = ""
    # device
    # Cross-request coalescing window for concurrent dense reads
    # (Count, BSI aggregates, dense TopN, Distinct): "adaptive"
    # (default) grows the window under queue pressure and shrinks it to
    # 0 when traffic is solo; a number fixes the window in seconds;
    # 0/"off" disables coalescing entirely.
    count_batch_window: str = "adaptive"
    query_timeout: float = 0.0         # seconds per query; 0 = unlimited
                                       # (?timeout= overrides per request)
    # The plane cache's TOTAL over every device it places planes on,
    # not one chip's share: under the mesh placement (mesh=true on a
    # multi-chip host) a plane's bytes are spread evenly over the chips,
    # so a four-chip v5e host that should hold 12 GiB of planes a chip
    # sets 48 GiB (51539607552).  A plane estimated above the budget
    # is never built: its queries take the streaming path.
    plane_budget_bytes: int = 4 << 30
    # Ingest delta planes (r15): writes to a resident whole-view plane
    # absorb into a bounded device-side overlay the query kernels
    # merge at dispatch time (base⊕delta) — reads keep serving at the
    # ceiling with zero generation-stale rebuild stalls.
    # delta_buffer_cells bounds the overlay (changed 32-bit plane
    # words per plane; 0 disables = pre-r15 incremental scatter);
    # past delta_compact_fraction of that, a background compactor
    # folds the overlay into the base and swaps generations.
    delta_buffer_cells: int = 65536
    delta_compact_fraction: float = 0.5
    # Whole-tree query compilation (r16): compound boolean PQL
    # (Intersect/Union/Difference/Xor/Not/UnionRows trees, BSI range
    # leaf filters) compiles to ONE fused XLA program — rows gathered
    # from the resident plane as traced operands, ops folded as a
    # postfix program — with concurrent requests sharing one memory
    # pass per plane through the batcher window.  False restores the
    # pre-r16 op-at-a-time/generic path (the bench baseline).
    tree_fusion: bool = True
    # Persistent dispatch pipeline (r17): how many dispatched-but-
    # unread collection windows the batcher may run ahead — window N's
    # device compute overlaps window N-1's packed device→host read.
    # <=1 restores the serial dispatch→read loop.
    dispatch_pipeline_depth: int = 2
    # Solo fast lane (r17): width-1 requests with no queue pressure
    # skip window formation and dispatch inline on the caller thread
    # over donated ping-pong chains (pre-bound slot operands, standing
    # output slots) — the attack on the one-RPC-per-query solo floor.
    # False restores the always-windowed pre-r17 path.
    solo_fastlane: bool = True
    # Pipeline watchdog (r18): per-stage age bound (seconds) on every
    # in-flight batcher window.  A window stalled past it — hung XLA
    # compile, stalled dispatch, wedged device→host read — is
    # QUARANTINED: its items fail with a structured error naming the
    # stage, its pipeline slot is reclaimed, and the wedged stage
    # worker is superseded so unrelated queries keep serving.  Keep it
    # well above worst-case legitimate compiles (seconds at full
    # scale).  0 disables the monitor entirely (the pre-r18 contract:
    # no watchdog thread, unbounded dispatch waits).
    dispatch_watchdog_seconds: float = 30.0
    # Device health governor (r18): after consecutive dispatch faults
    # or a watchdog trip flip serving to DEGRADED (fast lane off,
    # pipelining off, windows executed inline per item on the proven
    # op-at-a-time fallback path), then — every this-many seconds —
    # admit ONE window back onto the fused pipeline as a probe;
    # success restores healthy serving.
    device_health_probe_seconds: float = 5.0
    # Compile-ladder warm-up (r24): when a plane becomes resident, a
    # background single-flight warmer pre-compiles the delta-aware
    # fused program ladder (one program per pow2 overlay bucket per
    # family) OFF the serving path, so the first post-ingest query
    # hits a warm cache.  Compile seconds book into the cost ledger
    # under "warmup".  Single-device only (mesh placement disables).
    fused_warmup: bool = False
    # Storage integrity (r19).  Background scrubber: re-verify every
    # on-disk checksum (snapshot frames, op-log records, dense
    # sidecars, hint logs) each scrub_interval_seconds, reading at
    # most scrub_bytes_per_second (a strictly-lower-priority I/O
    # budget).  scrub_bytes_per_second=0 disables the scrubber
    # entirely (the pre-r19 contract: no thread, no re-verification).
    # A corrupt fragment is quarantined — reads serve from replicas,
    # local strict writes refuse with a structured 503 storageFault —
    # and auto-repaired from a healthy replica in cluster mode.
    scrub_interval_seconds: float = 600.0
    scrub_bytes_per_second: int = 32 << 20
    # Disk-health governor: write-path ENOSPC flips the node to
    # READ-ONLY degraded serving (strict writes refuse with a
    # structured writeUnavailable{disk_full}; peers hint the missed
    # copies); every disk_probe_seconds a probe (statvfs headroom >=
    # disk_min_free_bytes + a real probe write) checks whether space
    # freed and restores healthy serving.
    disk_min_free_bytes: int = 64 << 20
    disk_probe_seconds: float = 5.0
    # Multi-tenant HBM economy (r17 — tenant = index name).
    # plane_paging: a plane past the HBM budget (or its tenant's byte
    # quota) serves PAGED — fixed-byte shard pages resident on device,
    # the host oracle covering the rest, bit-exact; single-device only
    # (a mesh placement disables it).  plane_page_bytes sizes one page
    # (smaller = finer residency control, more page-ins).
    plane_paging: bool = True
    plane_page_bytes: int = 64 << 20
    # Per-tenant quotas, all 0 = off.  tenant_byte_quota caps one
    # tenant's resident plane/page bytes (page-ins evict the tenant's
    # OWN coldest entries first, then fall back to the oracle).
    # tenant_qps_quota / tenant_slot_quota shed an over-quota tenant's
    # queries with a structured tenantThrottled 503 + Retry-After
    # BEFORE they take an executor slot — other tenants keep their
    # admission floors.
    tenant_byte_quota: int = 0
    tenant_qps_quota: float = 0.0
    tenant_slot_quota: int = 0
    # Warm dense-plane cache: cold plane builds persist generation-
    # keyed dense sidecar images (<fragment>.dense) so a restarted
    # node re-expands at near raw-copy speed instead of re-decoding
    # roaring containers; any write/compaction/restore invalidates.
    plane_sidecars: bool = True
    # Queries EXECUTING at once; extras queue at the executor (bounds
    # concurrent device scratch; 0 = off).  Size against HBM headroom:
    # resident planes (plane_budget_bytes) + slots × ~0.5 GB scratch
    # must fit the chip — at an 8 GB budget on a 16 GB chip, 16 slots
    # measurably OOM'd and 6 served cleanly (r5).
    max_concurrent_queries: int = 8
    max_map_count: int = 32768          # live snapshot mmaps before LRU
                                        # heap demotion (syswrap parity)
    grpc_bind: str = ""                 # host:port; "" disables gRPC
    mesh: bool = True                   # shard planes over all local devices
    # tls (reference: server/config.go [tls] section) — one block turns
    # on HTTPS, TLS internode fan-out, and gRPC TLS together; the
    # node's certificate doubles as its client cert for mTLS when
    # enable_client_auth requires peers to authenticate
    tls_certificate: str = ""           # PEM cert path; "" = plaintext
    tls_key: str = ""                   # PEM private key path
    tls_ca_certificate: str = ""        # CA bundle for verifying peers
    tls_skip_verify: bool = False       # outbound: skip server-cert check
    tls_enable_client_auth: bool = False  # inbound: require client certs
    # multi-host jax (one process per host of a pod slice; the host-level
    # cluster layer above is independent of this)
    jax_coordinator: str = ""           # host:port of process 0; "" = single
    jax_num_processes: int = 0
    jax_process_id: int = -1

    @property
    def host(self) -> str:
        return self.bind.rsplit(":", 1)[0]

    @property
    def port(self) -> int:
        return int(self.bind.rsplit(":", 1)[1])

    def effective(self) -> dict:
        return dataclasses.asdict(self)


_BOOL_TRUE = {"1", "true", "yes", "on"}


def _coerce(value: str, typ):
    if typ is bool:
        return value.lower() in _BOOL_TRUE
    if typ is int:
        return int(value)
    if typ is float:
        return float(value)
    if typ == list[str]:
        return [s.strip() for s in value.split(",") if s.strip()]
    return value


def load(path: str | None = None, env: dict | None = None,
         overrides: dict | None = None) -> Config:
    """defaults ← TOML file ← PILOSA_* env ← explicit overrides."""
    cfg = Config()
    fields = {f.name: f.type for f in dataclasses.fields(Config)}

    if path:
        import tomllib
        with open(path, "rb") as f:
            data = tomllib.load(f)
        for k, v in data.items():
            k = k.replace("-", "_")
            if k == "tls" and isinstance(v, dict):
                # [tls] table, upstream-style: certificate = "...", ...
                for tk, tv in v.items():
                    tk = "tls_" + tk.replace("-", "_")
                    if tk not in fields:
                        raise ValueError(
                            f"unknown [tls] key {tk[4:]!r} in {path}")
                    setattr(cfg, tk, tv)
                continue
            if k not in fields:
                raise ValueError(f"unknown config key {k!r} in {path}")
            setattr(cfg, k, v)

    env = env if env is not None else os.environ
    for k in fields:
        ev = env.get(ENV_PREFIX + k.upper())
        if ev is not None:
            setattr(cfg, k, _coerce(ev, _resolve_type(fields[k])))

    for k, v in (overrides or {}).items():
        if v is not None:
            setattr(cfg, k, v)

    cfg.data_dir = os.path.expanduser(cfg.data_dir)
    for k in ("tls_certificate", "tls_key", "tls_ca_certificate"):
        v = getattr(cfg, k)
        if v:
            setattr(cfg, k, os.path.expanduser(v))
    if not cfg.name:
        cfg.name = cfg.bind
    return cfg


def tls_of(cfg: Config):
    """The resolved tls block as an :class:`pilosa_tpu.api.tls.TLSConfig`."""
    from pilosa_tpu.api.tls import TLSConfig
    return TLSConfig(
        certificate=cfg.tls_certificate, key=cfg.tls_key,
        ca_certificate=cfg.tls_ca_certificate,
        skip_verify=cfg.tls_skip_verify,
        enable_client_auth=cfg.tls_enable_client_auth)


def client_ssl_of(cfg: Config):
    """Outbound TLS context for this config (internode fan-out, CLI
    client), or None when the tls block is off — the single recipe
    every surface shares."""
    from pilosa_tpu.api.tls import client_context
    return client_context(tls_of(cfg))


def _resolve_type(t):
    # dataclass field types may be strings under future annotations
    if t in ("bool", bool):
        return bool
    if t in ("int", int):
        return int
    if t in ("float", float):
        return float
    if t in ("list[str]",) or t == list[str]:
        return list[str]
    return str
