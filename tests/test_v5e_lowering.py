"""The K-item Sum compiled for a v5e chip that is described, not
attached (no chip time): at ``dash_c1``'s size the program never copies
the resident BSI plane — the TPU lays ``u32[S, R, W]`` out row by row
(``{2,0,1}``), and a reduce or gather over the ``[S, R, W]`` view makes
the compiler copy all of it — and its temporaries are the items' masks.
"""

import os
import re

import pytest

S, R, W = 318, 12, 32768
ROW = S * W * 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args):
    import jax
    exe = jax.jit(fn).lower(*args).compile()
    return exe.as_text(), exe.memory_analysis().temp_size_in_bytes


def _plane_copies(hlo: str, s: int) -> list:
    """Instructions that write a whole plane (anything but the
    parameter itself and views of it)."""
    return [op for op in re.findall(
        rf"= u32\[{s},{R},{W}\]\S* ([\w-]+)\(", hlo)
        if op not in ("parameter", "get-tuple-element", "bitcast")]


@pytest.mark.parametrize("k,unfiltered", [(1, True), (1, False), (2, False),
                                          (10, False), (16, True)])
def test_the_sum_program_never_copies_the_plane(one_chip, k, unfiltered):
    import jax
    import jax.numpy as jnp
    from pilosa_tpu.engine import bsi
    sds = jax.ShapeDtypeStruct
    plane = sds((S, R, W), jnp.uint32, sharding=one_chip)
    filters = [sds((S, W), jnp.uint32, sharding=one_chip)] * k

    def program(p, *fs):
        items = list(fs)
        if unfiltered:
            items[0] = None
        return bsi.sum_pair_counts(p, items)
    hlo, temp = _compile(program, [plane, *filters])
    assert _plane_copies(hlo, S) == []
    # the K column masks and, for the sign side, K more: nothing the
    # size of the plane
    assert temp <= 2 * k * ROW + ROW // 2, temp


def test_the_overlay_sum_program_never_copies_the_plane(one_chip):
    """Under a ``BsiOverlay`` the mini side gathers the touched columns
    row by row; one gather over the [S, R, W] view copied the plane
    once an item (0.72 s of a 3 s capture in ``ingest_c1``, PR 33)."""
    import jax
    import jax.numpy as jnp
    from pilosa_tpu.exec.fused import FusedCache
    from pilosa_tpu.ingest.delta import BsiOverlay
    sds = jax.ShapeDtypeStruct
    s, k, lanes = S + 1, 10, 64
    built = {}

    def capture(key, build, **kw):
        built["program"] = build()
        return lambda *a: None
    fc = FusedCache()
    fc._cached = capture
    plane = sds((s, R, W), jnp.uint32, sharding=one_chip)
    filters = tuple(sds((s, W), jnp.uint32, sharding=one_chip)
                    for _ in range(k))
    delta = BsiOverlay(sds((lanes,), jnp.int32, sharding=one_chip),
                       sds((lanes,), jnp.int32, sharding=one_chip),
                       sds((lanes, R), jnp.uint32, sharding=one_chip),
                       sds((lanes, R), jnp.uint32, sharding=one_chip), 1, 1)
    fc.run_sum_plane_batch(plane, (True,) * k, filters, delta=delta)
    hlo, _ = _compile(built["program"],
                      [plane, *filters, delta.col_shard, delta.col_word,
                       delta.col_vals, delta.col_mask])
    assert _plane_copies(hlo, s) == []
