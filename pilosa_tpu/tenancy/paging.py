"""Paged plane residency: partial planes as first-class cache entries.

A plane bigger than the HBM budget (or than its tenant's byte quota)
never materializes whole.  Its shard axis splits into fixed-byte
*pages* — consecutive shard groups sized so one page's slab stays under
``page_bytes`` — and each page is an ordinary :class:`PlaneCache` entry
(key ``("page", index, field, view, page_shards)``) with its OWN row
union and slot map, leased/evicted/delta-overlaid like any whole-view
plane.  The Count serving path answers resident pages on device
(selected-row gather or whole-page scan through the batcher) and covers
non-resident pages with the host oracle (``Fragment.row_cardinalities``
— directory sums, no bit expansion), summing per row across pages:
bit-exact by construction, device-speed in proportion to residency.

Page-ins ride the warm ``.dense`` sidecar path (each fragment expands
once, against the page's full row union, so sidecars are both honored
and written) and deliberately do NOT count as plane *builds* — once
sidecars are warm, a churning cache pages in at near-memcpy speed with
zero full rebuilds (``tests/test_tenancy.py`` pins it).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from pilosa_tpu.engine.words import WORDS_PER_SHARD


class PlanePager:
    """Page partition + page residency + the non-resident oracle.

    Owns only the paging *mechanics*; policy (eviction order, tenant
    byte quotas) lives in the :class:`ResidencyGovernor` the cache and
    this pager share.  Single-device only — a partial page plane has no
    meaning under a mesh placement (the executor gates construction).
    """

    def __init__(self, cache, governor=None, page_bytes: int = 64 << 20,
                 stats=None, flight=None):
        from pilosa_tpu.obs import NULL_FLIGHT, NopStats
        self.cache = cache
        self.governor = governor
        self.page_bytes = max(1 << 20, int(page_bytes))
        self._stats = stats or NopStats()
        self.flight = flight or NULL_FLIGHT
        self._lock = threading.Lock()
        self.page_ins = 0
        self.page_in_seconds_total = 0.0
        self.oracle_serves = 0
        self.quota_denials = 0
        # per-tenant serving telemetry (tenant = index name):
        # hits = pages answered from residency, misses = page-in or
        # oracle coverage — the tenancy block's per-tenant hit ratio
        self._t_hits: dict[str, int] = {}
        self._t_misses: dict[str, int] = {}
        self._t_page_ins: dict[str, int] = {}

    # -- partition -----------------------------------------------------------

    def partition(self, field, view_name: str,
                  shards: tuple[int, ...]) -> list[tuple[int, ...]] | None:
        """Split ``shards`` into consecutive page groups sized to
        ``page_bytes`` (using the cached whole-plane estimate's
        per-shard slab).  None when the plane fits one page — plain
        whole-plane residency already handles that case."""
        if len(shards) < 2:
            return None
        est = self.cache.plane_bytes(field, view_name, shards)
        slab = max(1, est // len(shards))
        # a page must FIT in the cache (the insert path refuses
        # over-budget entries outright) with room left for a second
        # page — otherwise every "resident" page would be dropped on
        # insert and the warm path degrades to rebuild-per-query.
        # Same clamp against the tenant byte quota when one is set.
        eff = self.page_bytes
        if self.cache.budget > 0:
            eff = min(eff, max(slab, self.cache.budget // 2))
        g = self.governor
        if g is not None and g.byte_quota > 0:
            eff = min(eff, max(slab, g.byte_quota // 2))
        per = max(1, eff // slab)
        if per >= len(shards):
            return None
        return [tuple(shards[i:i + per])
                for i in range(0, len(shards), per)]

    @staticmethod
    def page_key(index: str, field, view_name: str,
                 page_shards: tuple[int, ...]) -> tuple:
        return ("page", index, field.name, view_name, page_shards)

    # -- residency -----------------------------------------------------------

    def resident_page(self, index: str, field, view_name: str,
                      page_shards: tuple[int, ...]):
        """The page's PlaneSet if it can serve from residency: fresh
        as-is, or stale with the write gap absorbed into its delta
        overlay / folded (the same machinery whole planes use — writes
        never force a page rebuild for an overlay-coverable gap).
        None = not resident, or refresh needs a re-read (the entry is
        dropped; the caller pages in against fragment truth)."""
        cache = self.cache
        key = self.page_key(index, field, view_name, page_shards)
        hit = cache._entries.get(key)  # GIL-atomic, lock-free
        if hit is None:
            return None
        if hit[0] == cache._gens_fast(field, view_name, page_shards):
            cache._touch(key)
            cache._lease_fast(key)
            cache.hits += 1
            self._note(self._t_hits, index)
            return hit[1]
        ps = cache._delta_update(key, field, view_name, page_shards, hit)
        if ps is not None:
            with cache._lock:
                cache._lease(key)
            cache.hits += 1
            self._note(self._t_hits, index)
            return ps
        # new rows / journal gap: the page's shape changed under it —
        # drop the entry so the page-in below re-reads fragment truth
        # (a sidecar-warm partial expansion, not a plane build)
        with cache._lock:
            if key in cache._entries and key not in cache._pinned():
                cache._evict_entry(key, "stale")
        return None

    def page_in(self, index: str, field, view_name: str,
                page_shards: tuple[int, ...]):
        """Materialize one page on device and cache it (leased to the
        calling query).  Admission runs the tenant's byte quota first,
        evicting the tenant's OWN coldest unpinned entries to make
        room; None when the quota still can't fit the page — the
        caller serves that page via the oracle instead."""
        cache = self.cache
        key = self.page_key(index, field, view_name, page_shards)
        gens = cache._gens(field, view_name, page_shards)
        row_ids = cache._union_row_ids(field, view_name, page_shards)
        r_pad = 1 << max(0, (max(1, len(row_ids)) - 1).bit_length())
        want = len(page_shards) * r_pad * WORDS_PER_SHARD * 4
        g = self.governor
        if g is not None and g.byte_quota > 0:
            resident = cache.tenant_bytes(index)
            if not g.admit_bytes(resident, want):
                over = resident + want - g.byte_quota
                cache.evict_tenant(index, over, reason="quota")
                if not g.admit_bytes(cache.tenant_bytes(index), want):
                    self.quota_denials += 1
                    self._note(self._t_misses, index)
                    return None
        t0 = time.perf_counter()
        ps = self._build_page(field, view_name, page_shards, row_ids)
        dt = time.perf_counter() - t0
        nbytes = ps.plane.size * 4
        cache._insert_entry(key, gens, ps, nbytes, lease=True)
        if g is not None:
            g.note_build(key, dt)
        self._stats.observe("plane_page_in_seconds", dt)
        self.flight.record("pagein", f"{index}/{field.name}",
                           f"{len(page_shards)} shards", dt)
        with self._lock:
            self.page_ins += 1
            self.page_in_seconds_total += dt
        self._note(self._t_page_ins, index)
        self._note(self._t_misses, index)
        return ps

    def _build_page(self, field, view_name: str,
                    page_shards: tuple[int, ...], row_ids: np.ndarray):
        """Partial-plane expansion over just the page's shards, via
        the sidecar-warm bulk path (each fragment expands once against
        the page's full row union, so ``.dense`` images are honored
        AND written).  Deliberately NOT counted in ``cache.builds`` —
        page-ins are residency churn, not plane rebuilds, and the
        zero-rebuild-once-warm acceptance bar reads that counter."""
        from concurrent.futures import ThreadPoolExecutor
        from functools import partial

        from pilosa_tpu.exec.planes import PAD_SHARD, PlaneSet
        cache = self.cache
        r_pad = 1 << max(0, (max(1, len(row_ids)) - 1).bit_length())
        host = np.zeros((len(page_shards), r_pad, WORDS_PER_SHARD),
                        dtype=np.uint32)
        slot_of = {int(r): i for i, r in enumerate(row_ids)}
        slots = np.arange(len(row_ids), dtype=np.uint64)
        view = field.view(view_name)
        tasks = []
        if view is not None and len(row_ids):
            for si, s in enumerate(page_shards):
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is None:
                    continue
                tasks.append(partial(
                    frag.expand_rows_into, row_ids, host[si], slots,
                    sidecar=cache.sidecars))
        if tasks:
            with ThreadPoolExecutor(
                    max_workers=cache.BUILD_WORKERS) as pool:
                cache._expand_tasks(pool, tasks)
        return PlaneSet(cache.place(host), page_shards, row_ids, slot_of)

    # -- non-resident oracle -------------------------------------------------

    def oracle_counts(self, field, view_name: str,
                      page_shards: tuple[int, ...],
                      row_ids: list) -> list[int]:
        """Per-row totals over a NON-resident page straight from host
        truth: ``Fragment.row_cardinalities`` directory sums — no bit
        expansion, no device transfer, exact by definition (it is the
        same oracle the plane builds are tested against).  ``None``
        entries in ``row_ids`` (absent rows) count 0."""
        from pilosa_tpu.exec.planes import PAD_SHARD
        totals = [0] * len(row_ids)
        view = field.view(view_name)
        if view is None:
            return totals
        want = [(i, int(r)) for i, r in enumerate(row_ids)
                if r is not None]
        if not want:
            return totals
        want_arr = np.asarray([r for _, r in want], np.uint64)
        for s in page_shards:
            if s == PAD_SHARD:
                continue
            frag = view.fragment(s)
            if frag is None:
                continue
            ids, cards = frag.row_cardinalities()
            if not len(ids):
                continue
            pos = np.searchsorted(ids, want_arr)
            ok = (pos < len(ids))
            pos = np.where(ok, pos, 0)
            match = ok & (ids[pos] == want_arr)
            for j, (i, _r) in enumerate(want):
                if match[j]:
                    totals[i] += int(cards[pos[j]])
        with self._lock:
            self.oracle_serves += 1
        return totals

    # -- telemetry -----------------------------------------------------------

    def _note(self, d: dict, tenant: str) -> None:
        with self._lock:
            d[tenant] = d.get(tenant, 0) + 1

    def tenant_breakdown(self) -> dict:
        """Per-tenant residency from the live cache: resident bytes,
        whole-plane entries, page entries."""
        cache = self.cache
        with cache._lock:
            items = [(k, v[2]) for k, v in cache._entries.items()]
        per: dict[str, dict] = {}
        for k, nb in items:
            d = per.setdefault(k[1], {"residentBytes": 0,
                                      "residentPages": 0,
                                      "residentEntries": 0})
            d["residentBytes"] += nb
            d["residentEntries"] += 1
            if k[0] == "page":
                d["residentPages"] += 1
        return per

    def payload(self) -> dict:
        """The /status tenancy block's paging half.  Also refreshes
        the ``plane_resident_pages`` gauge at scrape time (the
        mesh_stats idiom — the gauge is a snapshot of live cache
        state, not an incrementally maintained counter)."""
        per = self.tenant_breakdown()
        with self._lock:
            hits, misses = dict(self._t_hits), dict(self._t_misses)
            page_ins = dict(self._t_page_ins)
            totals = {"pageIns": self.page_ins,
                      "pageInSeconds": round(self.page_in_seconds_total,
                                             6),
                      "oracleServes": self.oracle_serves,
                      "quotaDenials": self.quota_denials}
        n_pages = sum(d["residentPages"] for d in per.values())
        self._stats.gauge("plane_resident_pages", n_pages)
        for t in set(hits) | set(misses) | set(page_ins):
            d = per.setdefault(t, {"residentBytes": 0,
                                   "residentPages": 0,
                                   "residentEntries": 0})
            h, m = hits.get(t, 0), misses.get(t, 0)
            d["pageHits"] = h
            d["pageMisses"] = m
            d["hitRatio"] = round(h / (h + m), 4) if h + m else 0.0
            d["pageIns"] = page_ins.get(t, 0)
        return {"pageBytes": self.page_bytes,
                "residentPages": n_pages, "tenants": per, **totals}
