"""Config #5 (BASELINE.md): cluster Intersect+Count at 256 shards over
the device mesh.

This script assumes no multi-chip hardware, and — diagnosed in round 2 — the "simulated scaling" half can
never show real speedup either: the 8 virtual CPU devices
(``xla_force_host_platform_device_count``) share this host's cores, and
``nproc`` here is typically 1.  The 1-device baseline already uses every
core, so splitting the same arithmetic 8 ways measures collective/
partition overhead, not scaling (round 1's "2.6×" was threading noise
on tiny grains).  What the virtual mesh DOES validate — and what this
config asserts — is that the ``shard_map``/psum program partitions and
reduces EXACTLY (oracle-checked at every device count, both grain
sizes); scaling itself must come from real chips, which the same
compiled program targets unchanged (tested multi-process in
tests/test_multihost.py).

On the real chip (default env) this measures 256-shard Intersect+Count
throughput on one device.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import emit, log, time_p50


def main():
    import jax

    from pilosa_tpu.parallel import MeshPlacement, spmd

    rng = np.random.default_rng(5)
    n_shards = 256
    a = rng.integers(0, 1 << 32, size=(n_shards, 32768), dtype=np.uint32)
    b = rng.integers(0, 1 << 32, size=(n_shards, 32768), dtype=np.uint32)
    oracle = int(np.bitwise_count(a & b).sum(dtype=np.int64)) \
        if hasattr(np, "bitwise_count") else \
        int(np.unpackbits((a & b).view(np.uint8)).sum(dtype=np.int64))

    devs = jax.devices()
    platform = devs[0].platform
    if len(devs) > 1:
        cores = os.cpu_count() or 1
        log(f"virtual {len(devs)}-device CPU mesh on {cores} host "
            f"core(s): correctness validation, NOT a scaling proxy "
            f"(see module docstring)")
        results = {}
        for n_dev in (1, 2, 4, 8):
            if n_dev > len(devs):
                break
            p = MeshPlacement(devs[:n_dev])
            fn = spmd.make_intersect_count_psum(p.mesh)
            da, db = p.place(a), p.place(b)
            got = int(fn(da, db))
            assert got == oracle, (n_dev, got, oracle)
            p50 = time_p50(lambda: fn(da, db), 20)
            results[n_dev] = p50
            log(f"{n_dev} devices: {p50 * 1e3:.3f} ms — psum exact")
        emit(f"cluster_psum_exact_{max(results)}dev_{platform}",
             1.0, "bool", 1.0)
    else:
        da, db = jax.device_put(a), jax.device_put(b)
        got = int(spmd.intersect_count(da, db))
        assert got == oracle, (got, oracle)
        p50 = time_p50(lambda: spmd.intersect_count(da, db), 50)
        log(f"single device, 256 shards: {p50 * 1e3:.3f} ms, oracle ok")
        emit(f"intersect_count_qps_256shards_{platform}", 1 / p50, "qps",
             1.0)


if __name__ == "__main__":
    main()
