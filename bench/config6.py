"""Config #6 (extra): END-TO-END server throughput under concurrent
clients — REST parse + executor + device + JSON response, the number a
user of the reference would compare against its HTTP QPS.  8 client
threads issuing Count(Intersect(Row,Row)) against an in-process server
over a multi-shard index."""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import emit, log


def main():
    import tempfile

    import jax

    from pilosa_tpu.api import API, Client, Server
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    rng = np.random.default_rng(6)
    holder = Holder(tempfile.mkdtemp()).open()
    idx = holder.create_index("bench", track_existence=False)
    idx.create_field("f")
    idx.create_field("g")
    n, n_shards = 500_000, 16
    cols = rng.choice(n_shards << 20, n, replace=False).astype(np.uint64)
    idx.field("f").import_bits(np.ones(n, np.uint64), cols)
    idx.field("g").import_bits(np.ones(n // 2, np.uint64), cols[: n // 2])

    # cross-request batcher: any number of HTTP clients funnel through
    # ONE device stream (r1: 16 raw concurrent streams crashed the
    # backend; batched, 32 clients are safe and faster)
    api = API(holder, Executor(holder, count_batch_window=0.004))
    server = Server(api, "127.0.0.1", 0).start()
    expect = n // 2
    pql = "Count(Intersect(Row(f=1), Row(g=1)))"

    n_threads, reps = 32, 25
    clients = [Client("127.0.0.1", server.address[1])
               for _ in range(n_threads)]
    clients[0].query("bench", pql)  # warm compile
    errors = []
    barrier = threading.Barrier(n_threads + 1)

    def worker(cl):
        barrier.wait()
        for _ in range(reps):
            (got,) = cl.query("bench", pql)
            if got != expect:
                errors.append(got)

    def run_burst():
        ts = [threading.Thread(target=worker, args=(c,)) for c in clients]
        for t in ts:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in ts:
            t.join()
        return time.perf_counter() - t0

    warm = run_burst()  # batch-bucket program compiles land here
    dt = run_burst()
    assert not errors, errors[:3]
    qps = n_threads * reps / dt
    log(f"first burst incl. bucket compiles: "
        f"{n_threads * reps / warm:,.1f} qps")
    platform = jax.devices()[0].platform
    log(f"e2e HTTP server ({platform}): {qps:,.1f} qps, "
        f"{n_threads} clients x {reps} Count(Intersect) @ 16M cols, "
        f"all responses exact")
    emit(f"e2e_http_concurrent_qps_{platform}", qps, "qps", 1.0)
    server.close()
    holder.close()


if __name__ == "__main__":
    main()
