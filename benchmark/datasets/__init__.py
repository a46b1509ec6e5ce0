"""One module per kind of data; each has ``generate(dataset, seed,
shard)``, a pure function that returns one shard as numpy arrays."""

import importlib


def generator(kind: str):
    return importlib.import_module(f"benchmark.datasets.{kind}").generate
