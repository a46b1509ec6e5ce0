"""Index write + oracle, shard by shard, in worker processes.

Each worker generates its shards from ``(seed, shard)``, writes their
fragment files, and sums what each shard contributes to every distinct
call of the cell's pool.  The host never holds a whole plane; the
workers import numpy only (spawned processes, no GIL shared with the
parent, no JAX)."""

from __future__ import annotations

import concurrent.futures
import multiprocessing
import os

import numpy as np

from benchmark import datasets, queries
from benchmark.bitmaps import WORDS, bsi_depth, bsi_rows, serialize_rows


def fragment_dir(data_dir: str, index: str, field: str, view: str) -> str:
    return os.path.join(data_dir, index, field, "views", view, "fragments")


def views(config: dict) -> list:
    """(field, view) of every fragment family the dataset writes."""
    ds = config["dataset"]
    out = [(f, "standard") for f in dataset_field_rows(config)]
    out += [(f, f"bsi_{f}") for f in ds.get("int_fields", {})]
    return out + [("_exists", "standard")]


def dataset_field_rows(config: dict) -> dict:
    """set field -> number of rows."""
    ds = config["dataset"]
    if "set_fields" in ds:
        return {f: len(s["shares"]) for f, s in ds["set_fields"].items()}
    return {ds["field"]: ds["rows"]}


def _load_chunk(task: tuple):
    config, data_dir, seed, shards, calls = task
    gen = datasets.generator(config["dataset"]["kind"])
    index = config["index"]
    int_fields = config["dataset"].get("int_fields", {})
    all_ones = serialize_rows(np.full((1, WORDS), 0xFFFFFFFF, np.uint32))
    totals, written = None, 0
    for shard in shards:
        data = gen(config["dataset"], seed, shard)
        blobs = {(f, "standard"): serialize_rows(rows)
                 for f, rows in data["sets"].items()}
        for f, vals in data["ints"].items():
            blobs[(f, f"bsi_{f}")] = serialize_rows(
                bsi_rows(vals, bsi_depth(int_fields[f]["max"])))
        blobs[("_exists", "standard")] = all_ones
        for (f, view), blob in blobs.items():
            with open(os.path.join(fragment_dir(data_dir, index, f, view),
                                   str(shard)), "wb") as fh:
                fh.write(blob)
            written += len(blob)
        totals = queries.combine(
            totals, [queries.partial(c, data) for c in calls])
    return totals, written


def load(config: dict, data_dir: str, seed: int, n_shards: int,
         calls: list, workers: int) -> tuple:
    """Write every shard's fragments and return (per-call totals,
    bytes written)."""
    for f, view in views(config):
        os.makedirs(fragment_dir(data_dir, config["index"], f, view),
                    exist_ok=True)
    step = max(1, -(-n_shards // (workers * 4)))
    tasks = [(config, data_dir, seed, list(range(lo, min(lo + step,
                                                         n_shards))), calls)
             for lo in range(0, n_shards, step)]
    totals, written = None, 0
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(workers,
                                                mp_context=ctx) as pool:
        for part, nbytes in pool.map(_load_chunk, tasks):
            written += nbytes
            totals = queries.combine(totals, part)
    return totals, written
