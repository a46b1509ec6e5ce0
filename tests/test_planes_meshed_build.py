"""The chunked plane build under a placement (PR 28): one pipeline for
one device and for a mesh.  A chunk holds the same local shards of
every device's run of the shard axis, lands in one sharded
``device_put`` and is written by every device into its own part of the
plane — bit-exact against ``_build_plane`` (the monolithic
pure-Python oracle) over pad shards, narrow tail chunks, the row-chunk
variant and the 2D placement, with the host never staging more than
two chunks of a plane, and paying into the same build telemetry."""

import jax
import numpy as np
import pytest

from pilosa_tpu.engine.words import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.planes import PAD_SHARD
from pilosa_tpu.obs import Stats
from pilosa_tpu.parallel import MeshPlacement
from pilosa_tpu.parallel.mesh import MeshPlacement2D
from pilosa_tpu.store import Holder

N_SHARDS = 14       # shard 5 stays empty: 13 in use, never a multiple
SLAB = 8 * WORDS_PER_SHARD * 4      # five rows pad to eight


@pytest.fixture
def env(tmp_path, rng):
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    f = idx.create_field("f")
    n = 3000
    cols = rng.choice(N_SHARDS * SHARD_WIDTH, size=n,
                      replace=False).astype(np.uint64)
    # shard 5 stays empty: a hole in the middle of a device's run
    cols = cols[cols // SHARD_WIDTH != 5]
    rows = rng.integers(0, 5, size=len(cols)).astype(np.uint64)
    f.import_bits(rows, cols)
    # a dense block, so bitmap containers are on the path too
    dense = np.arange(70000, 70000 + 9000, dtype=np.uint64) \
        + 7 * SHARD_WIDTH
    f.import_bits(np.full(len(dense), 3, np.uint64), dense)
    yield holder, idx
    holder.close()


def _placements():
    devs = jax.devices()
    return {"mesh4": lambda: MeshPlacement(devs[:4]),
            "mesh8": lambda: MeshPlacement(devs),
            "mesh2x2": lambda: MeshPlacement2D(devs[:4], shard_size=2,
                                               words_size=2)}


@pytest.mark.parametrize("chunk_bytes", [SLAB * 4, SLAB * 8, SLAB * 1000,
                                         SLAB // 4],
                         ids=["1-shard-chunks", "tail-chunk", "one-chunk",
                              "row-chunks"])
@pytest.mark.parametrize("where", ["mesh4", "mesh8", "mesh2x2"])
def test_meshed_chunked_build_equals_the_monolithic_oracle(
        env, where, chunk_bytes):
    holder, idx = env
    placement = _placements()[where]()
    ex = Executor(holder, placement=placement)
    field = idx.field("f")
    shards = placement.pad_shards(tuple(idx.available_shards()))
    assert PAD_SHARD in shards and len(shards) % placement.n_devices == 0
    ex.planes.BUILD_CHUNK_BYTES = chunk_bytes
    oracle = ex.planes._build_plane(field, "standard", shards)
    got = ex.planes._build_plane_chunked(field, "standard", shards)
    assert got.plane.shape == oracle.plane.shape == (len(shards), 8,
                                                     WORDS_PER_SHARD)
    assert np.array_equal(np.asarray(got.plane), np.asarray(oracle.plane))
    assert np.array_equal(got.row_ids, oracle.row_ids)
    assert got.slot_of == oracle.slot_of and got.shards == shards
    # born sharded as the placement places: no gather, no second copy
    assert got.plane.sharding.is_equivalent_to(placement.sharding(3), 3)
    assert len(got.plane.addressable_shards) == len(placement.mesh.devices
                                                    .flat)


def test_the_host_never_stages_more_than_two_chunks(env, monkeypatch):
    """A plane of any size goes through two staging buffers of at most
    ``BUILD_CHUNK_BYTES`` (a narrower pair for a tail): the whole-plane
    host slab of the old meshed build is gone."""
    holder, idx = env
    placement = MeshPlacement(jax.devices()[:4])
    ex = Executor(holder, placement=placement)
    shards = placement.pad_shards(tuple(idx.available_shards()))   # 16
    ex.planes.BUILD_CHUNK_BYTES = SLAB * 4     # one local shard a chunk
    staged = []
    real_zeros = np.zeros

    def zeros(shape, *a, **kw):
        out = real_zeros(shape, *a, **kw)
        if isinstance(shape, tuple) and len(shape) == 3:
            staged.append(out.nbytes)
        return out

    monkeypatch.setattr(np, "zeros", zeros)
    got = ex.planes._build_plane_chunked(idx.field("f"), "standard", shards)
    monkeypatch.undo()
    plane_bytes = got.plane.size * 4
    assert staged and max(staged) <= ex.planes.BUILD_CHUNK_BYTES
    # four chunks of one local shard of each device went through the
    # two buffers of the double buffer, and nothing else was staged
    assert staged == [SLAB * 4] * 2
    assert sum(staged) * 2 == plane_bytes


def test_a_meshed_query_builds_through_the_pipeline_and_pays_its_telemetry(
        env):
    holder, idx = env
    stats = Stats()
    placement = MeshPlacement(jax.devices()[:4])
    ex = Executor(holder, placement=placement, stats=stats)
    plain = Executor(holder)
    assert ex.execute("i", "TopN(f)")[0].pairs \
        == plain.execute("i", "TopN(f)")[0].pairs
    assert ex.execute("i", "TopN(f, Row(f=3), n=2)")[0].pairs \
        == plain.execute("i", "TopN(f, Row(f=3), n=2)")[0].pairs
    pc = ex.planes.stats()
    assert pc["meshed"] is True and pc["builds"] >= 1
    assert pc["buildBytes"] >= 16 * SLAB and pc["buildSeconds"] > 0
    counters = stats.snapshot()["counters"]
    assert sum(counters["plane_build_bytes_total"].values()) \
        == pc["buildBytes"]
    assert stats.histogram_summary("plane_build_seconds")["total"]["count"] \
        == pc["builds"]
    assert pc["buildFailures"] == 0


def test_shards_that_do_not_divide_over_the_mesh_are_refused(env):
    holder, idx = env
    placement = MeshPlacement(jax.devices()[:4])
    ex = Executor(holder, placement=placement)
    shards = tuple(idx.available_shards())[:6]
    with pytest.raises(ValueError, match="do not divide"):
        ex.planes._build_plane_chunked(idx.field("f"), "standard", shards)
