"""The r10 plane-build pipeline: parallel roaring→dense expansion,
overlapped H2D transfer, and the warm dense-sidecar cache.

Correctness bar: every pipeline variant (shard-major, row-chunked,
warm-from-sidecar, pure-Python fallback) must be bit-exact against
``_build_plane`` — the untouched monolithic build over the pure-Python
``fragment.plane_rows`` oracle — and executor answers (Row / Count /
TopN) must match a fresh executor after any restart or corruption."""

import glob
import os

import numpy as np
import pytest

from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import Executor
from pilosa_tpu.store import Holder, native


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    yield holder, idx
    holder.close()


def _mixed_container_bits(rng, n_shards: int):
    """(row_ids, cols) hitting every roaring container type per shard:
    run (consecutive), array (sparse), bitmap (dense 65536-block)."""
    rows, cols = [], []
    for s in range(n_shards):
        base = s * SHARD_WIDTH
        # run containers: row 1, two consecutive ranges
        r = np.arange(5000, 5000 + 9000)
        rows.append(np.full(len(r), 1)), cols.append(base + r)
        # array containers: row 2, scattered sparse bits
        r = np.sort(rng.choice(SHARD_WIDTH, 700, replace=False))
        rows.append(np.full(len(r), 2)), cols.append(base + r)
        # bitmap containers: row 3, >4096 bits inside one 65536 block
        r = np.sort(rng.choice(65536, 9000, replace=False)) + 131072
        rows.append(np.full(len(r), 3)), cols.append(base + r)
        # and a high row id so the pow2 pad has a tail
        rows.append(np.array([41])), cols.append(np.array([base + 7]))
    return (np.concatenate(rows).astype(np.uint64),
            np.concatenate(cols).astype(np.uint64))


def _sidecars(holder):
    return sorted(glob.glob(os.path.join(
        holder.path, "i", "f", "views", "standard", "fragments",
        "*.dense")))


class TestParallelExpansionOracle:
    """Pipelined builds vs the pure-Python plane_rows oracle."""

    @pytest.mark.parametrize("seed", [3, 11, 29])
    def test_shard_major_bit_exact(self, env, seed):
        holder, idx = env
        rng = np.random.default_rng(seed)
        rows, cols = _mixed_container_bits(rng, n_shards=3)
        idx.field("f").import_bits(rows, cols)
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        ex = Executor(holder)
        oracle = ex.planes._build_plane(field, "standard", shards)
        got = ex.planes._build_plane_chunked(field, "standard", shards)
        np.testing.assert_array_equal(np.asarray(oracle.plane),
                                      np.asarray(got.plane))
        np.testing.assert_array_equal(oracle.row_ids, got.row_ids)
        assert got.slot_of == oracle.slot_of

    def test_row_chunked_bit_exact(self, env):
        holder, idx = env
        rng = np.random.default_rng(5)
        rows, cols = _mixed_container_bits(rng, n_shards=3)
        idx.field("f").import_bits(rows, cols)
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        ex = Executor(holder)
        oracle = ex.planes._build_plane(field, "standard", shards)
        # force row-block tiling: chunk smaller than one shard slab
        ex.planes.BUILD_CHUNK_BYTES = 3 * 16 * 32768 * 4
        got = ex.planes._build_plane_chunked(field, "standard", shards)
        np.testing.assert_array_equal(np.asarray(oracle.plane),
                                      np.asarray(got.plane))

    def test_pure_python_fallback_bit_exact(self, env, monkeypatch):
        """With the native codec absent the pipeline must still match
        the oracle (skip-if-unavailable is not enough: the FALLBACK is
        the claim here)."""
        holder, idx = env
        rng = np.random.default_rng(13)
        rows, cols = _mixed_container_bits(rng, n_shards=2)
        idx.field("f").import_bits(rows, cols)
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        ex = Executor(holder)
        oracle = ex.planes._build_plane(field, "standard", shards)
        monkeypatch.setattr(native, "_lib", None)
        assert not native.available()
        got = ex.planes._build_plane_chunked(field, "standard", shards)
        np.testing.assert_array_equal(np.asarray(oracle.plane),
                                      np.asarray(got.plane))

    def test_overlay_rows_beat_stale_snapshot(self, env):
        """Rows materialized (mutated) AFTER the snapshot was written
        must come from the overlay, not the stale blob — the partition
        the bulk expansion inherits from plane_rows."""
        holder, idx = env
        rng = np.random.default_rng(23)
        rows, cols = _mixed_container_bits(rng, n_shards=2)
        idx.field("f").import_bits(rows, cols)
        view = idx.field("f").standard_view()
        for frag in view.fragments.values():
            frag.snapshot()  # everything snapshot-resident
        # mutate row 2 post-snapshot: overlay now differs from the blob
        idx.field("f").import_bits(np.array([2, 2], np.uint64),
                                   np.array([123, SHARD_WIDTH + 9],
                                            np.uint64))
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        ex = Executor(holder)
        oracle = ex.planes._build_plane(field, "standard", shards)
        got = ex.planes._build_plane_chunked(field, "standard", shards)
        np.testing.assert_array_equal(np.asarray(oracle.plane),
                                      np.asarray(got.plane))


class TestMidBuildWrite:
    def test_mid_build_write_leaves_entry_stale(self, env):
        """A write while the background build is in flight: the entry
        is inserted with the PRE-build generations (stale), and the
        next query refreshes — answers always include the write."""
        import threading
        import time

        holder, idx = env
        rng = np.random.default_rng(31)
        rows, cols = _mixed_container_bits(rng, n_shards=2)
        idx.field("f").import_bits(rows, cols)
        ex = Executor(holder)
        ex.planes.SYNC_BUILD_MAX = 0  # background path for any size
        gate = threading.Event()
        real = ex.planes._build_plane_chunked

        def gated(*a, **k):
            gate.wait(120)
            return real(*a, **k)

        ex.planes._build_plane_chunked = gated
        ex.execute("i", "TopN(f, n=4)")  # spawns the gated build
        assert ex.planes._building
        # the mid-build write (a brand-new column of row 2)
        new_col = 2 * SHARD_WIDTH - 3
        ex.execute("i", f"Set({new_col}, f=2)")
        gate.set()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and ex.planes._building:
            time.sleep(0.02)
        assert not ex.planes._building, "build never finished"
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        key = ("plane", "i", "f", "standard", shards)
        hit = ex.planes._entries.get(key)
        assert hit is not None
        assert hit[0] != ex.planes._gens(field, "standard", shards), \
            "mid-build write must leave the entry generation-stale"
        (got,) = ex.execute("i", "Count(Row(f=2))")
        (want,) = Executor(holder).execute("i", "Count(Row(f=2))")
        assert got == want, "refreshed answer must include the write"


class TestWarmSidecarCache:
    def _seed_index(self, idx, n_shards=3, seed=47):
        rng = np.random.default_rng(seed)
        rows, cols = _mixed_container_bits(rng, n_shards)
        idx.field("f").import_bits(rows, cols)

    def test_restart_round_trip_oracle_exact(self, env, tmp_path):
        """Cold build writes sidecars; a restarted node warm-builds
        from them and serves Row/Count/TopN oracle-exact."""
        holder, idx = env
        self._seed_index(idx)
        ex = Executor(holder)
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        cold = ex.planes._build_plane_chunked(field, "standard", shards)
        assert ex.planes.warm_hits == 0
        assert len(_sidecars(holder)) == len(shards)
        want = {
            "topn": [(p.id, p.count) for p in
                     ex.execute("i", "TopN(f)")[0].pairs],
            "count": ex.execute("i", "Count(Row(f=1))")[0],
            "row": ex.execute("i", "Row(f=3)")[0].columns.tolist(),
        }
        holder.close()

        h2 = Holder(str(tmp_path)).open()
        ex2 = Executor(h2)
        f2 = h2.index("i").field("f")
        warm = ex2.planes._build_plane_chunked(f2, "standard", shards)
        assert ex2.planes.warm_hits == len(shards), \
            "every fragment must load from its sidecar after restart"
        np.testing.assert_array_equal(np.asarray(cold.plane),
                                      np.asarray(warm.plane))
        # and the serving surface agrees end to end
        assert [(p.id, p.count) for p in
                ex2.execute("i", "TopN(f)")[0].pairs] == want["topn"]
        assert ex2.execute("i", "Count(Row(f=1))")[0] == want["count"]
        assert ex2.execute("i", "Row(f=3)")[0].columns.tolist() \
            == want["row"]
        h2.close()

    def test_compaction_restamps_still_valid_sidecar(self, env, tmp_path):
        """Op-log compaction (incl. the close-time snapshot) preserves
        content, so it re-stamps the sidecar instead of stranding every
        restart cold."""
        holder, idx = env
        self._seed_index(idx, n_shards=2)
        ex = Executor(holder)
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        ex.planes._build_plane_chunked(field, "standard", shards)
        holder.close()  # compacts every dirty fragment
        h2 = Holder(str(tmp_path)).open()
        ex2 = Executor(h2)
        ex2.planes._build_plane_chunked(h2.index("i").field("f"),
                                        "standard", shards)
        assert ex2.planes.warm_hits == len(shards)
        h2.close()

    def test_write_invalidates_then_next_build_is_cold_and_exact(
            self, env, tmp_path):
        holder, idx = env
        self._seed_index(idx, n_shards=2)
        ex = Executor(holder)
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        ex.planes._build_plane_chunked(field, "standard", shards)
        # a write AFTER the sidecar was written: the op-log grows, the
        # stamp mismatches, the next build must not serve stale bits —
        # but ONLY the written fragment goes cold (invalidation is
        # per fragment; untouched shards keep their warm images)
        idx.field("f").import_bits(np.array([1], np.uint64),
                                   np.array([99], np.uint64))
        ex2 = Executor(holder)
        got = ex2.planes._build_plane_chunked(field, "standard", shards)
        oracle = ex2.planes._build_plane(field, "standard", shards)
        np.testing.assert_array_equal(np.asarray(oracle.plane),
                                      np.asarray(got.plane))
        assert ex2.planes.warm_misses == 1
        assert ex2.planes.warm_hits == len(shards) - 1

    @pytest.mark.parametrize("damage", ["corrupt", "truncate", "garbage"])
    def test_damaged_sidecar_falls_back_cold(self, env, tmp_path, damage):
        holder, idx = env
        self._seed_index(idx, n_shards=2)
        ex = Executor(holder)
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        ex.planes._build_plane_chunked(field, "standard", shards)
        oracle = ex.planes._build_plane(field, "standard", shards)
        for p in _sidecars(holder):
            if damage == "corrupt":   # flip image bytes: crc must catch
                with open(p, "r+b") as f:
                    f.seek(70)
                    f.write(b"\xff" * 16)
            elif damage == "truncate":
                with open(p, "r+b") as f:
                    f.truncate(30)
            else:                     # not even a header
                with open(p, "wb") as f:
                    f.write(b"garbage")
        ex2 = Executor(holder)
        got = ex2.planes._build_plane_chunked(field, "standard", shards)
        np.testing.assert_array_equal(np.asarray(oracle.plane),
                                      np.asarray(got.plane))
        assert ex2.planes.warm_hits == 0
        assert ex2.planes.warm_misses == len(shards)

    def test_sidecars_off_writes_nothing(self, env):
        holder, idx = env
        self._seed_index(idx, n_shards=2)
        ex = Executor(holder, plane_sidecars=False)
        field = idx.field("f")
        shards = tuple(idx.available_shards())
        ex.planes._build_plane_chunked(field, "standard", shards)
        assert _sidecars(holder) == []

    def test_warm_serving_through_executor(self, env, tmp_path):
        """End to end: restart, then the QUERY path (background build +
        flip) serves from the warm cache with exact answers."""
        import time

        holder, idx = env
        self._seed_index(idx)
        ex = Executor(holder)
        ex.planes.SYNC_BUILD_MAX = 0
        ex.execute("i", "TopN(f)")
        ex.planes.wait_builds()
        want = [(p.id, p.count) for p in
                ex.execute("i", "TopN(f)")[0].pairs]
        holder.close()

        h2 = Holder(str(tmp_path)).open()
        ex2 = Executor(h2)
        ex2.planes.SYNC_BUILD_MAX = 0
        got = [(p.id, p.count) for p in
               ex2.execute("i", "TopN(f)")[0].pairs]  # streaming answer
        assert got == want
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and ex2.planes._building:
            time.sleep(0.02)
        got2 = [(p.id, p.count) for p in
                ex2.execute("i", "TopN(f)")[0].pairs]  # resident answer
        assert got2 == want
        assert ex2.planes.warm_hits > 0
        h2.close()


class TestCompilationCache:
    """The one cache rule (``engine/_jaxcfg.py``): an operator-placed
    ``JAX_COMPILATION_CACHE_DIR`` wins and no code sets another
    directory; otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``.  Decided at import, so each branch runs
    in a fresh interpreter."""

    PROBE = (
        "import jax, jax.numpy as jnp\n"
        "import pilosa_tpu.server\n"
        "from pilosa_tpu.engine import _jaxcfg, kernels\n"
        "int(kernels.count(jnp.ones((3, 64), jnp.uint32)).sum())\n"
        "assert jax.config.jax_compilation_cache_dir == "
        "_jaxcfg.compile_cache_dir()\n"
        "print(_jaxcfg.compile_cache_dir())\n")

    def _probe(self, env_dir):
        import os
        import subprocess
        import sys
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_ENABLE_COMPILATION_CACHE="true")  # conftest: off
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        out = subprocess.run([sys.executable, "-c", self.PROBE], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        return out.stdout.strip().splitlines()[-1]

    def test_env_placed_cache_wins(self, tmp_path):
        cache_dir = tmp_path / "jaxcache"
        assert self._probe(cache_dir) == str(cache_dir)
        assert any(cache_dir.iterdir()), \
            "the first compile must persist under the placed directory"

    def test_default_is_fixed_under_checkout(self):
        import os

        import pilosa_tpu
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(pilosa_tpu.__file__)))
        want = os.path.join(checkout, ".jax_cache")
        assert self._probe(None) == want
        assert os.listdir(want)


class TestBuildFailureObservability:
    def test_background_failure_counts_and_serving_continues(self, env):
        holder, idx = env
        rng = np.random.default_rng(3)
        idx.field("f").import_bits(
            rng.integers(1, 20, 2000).astype(np.uint64),
            rng.integers(0, 2 * SHARD_WIDTH, 2000).astype(np.uint64))
        ex = Executor(holder)
        ex.planes.SYNC_BUILD_MAX = 0

        def boom(*a, **k):
            raise RuntimeError("injected build failure")

        ex.planes._build_plane_chunked = boom
        (p,) = ex.execute("i", "TopN(f, n=3)")  # streams; build dies
        ex.planes.wait_builds()
        assert ex.planes.build_failures >= 1
        assert ex.planes.stats()["buildFailures"] >= 1
        # queries keep answering (streaming path), exactly
        assert [(x.id, x.count) for x in p.pairs] == \
            [(x.id, x.count) for x in
             Executor(holder).execute("i", "TopN(f, n=3)")[0].pairs]

    def test_status_surfaces_plane_build_block(self, env):
        from pilosa_tpu.api import API
        holder, idx = env
        idx.field("f").import_bits(np.array([1], np.uint64),
                                   np.array([2], np.uint64))
        ex = Executor(holder)
        api = API(holder, ex)
        ex.execute("i", "TopN(f)")
        st = api.status()
        pb = st["storage"]["planeBuild"]
        assert {"builds", "buildSeconds", "buildBytes", "buildFailures",
                "warmHits", "warmMisses"} <= set(pb)
        assert pb["builds"] >= 1
