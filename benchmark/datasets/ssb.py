"""``ssb``: the Star Schema Benchmark's ``lineorder``, one row per
column, with the attributes of its customer, supplier, part and order
date denormalised onto it (O'Neil, O'Neil, Chen, rev. 3, 2009; dbgen's
definitions, from memory).

Each column draws its customer, supplier and part key uniformly and its
order date uniformly from 1992-01-01 to 1998-08-02.  A dimension's
attributes are functions of the key, drawn once per table from the seed
(``_tables``), so two lineorders of one part share its brand: a city
lies in one nation and a nation in one region (TPC-H's 25 nations and
five regions), a brand in one category and a category in one
manufacturer.  Row ids stand for the spec's strings:

    region       0 AFRICA, 1 AMERICA, 2 ASIA, 3 EUROPE, 4 MIDDLE EAST
    nation       TPC-H's nation key (23 UNITED KINGDOM, 24 UNITED STATES)
    city         nation * 10 + the city's digit ("UNITED KI1" = 231)
    p_mfgr       m - 1 for MFGR#m
    p_category   (m - 1) * 5 + (c - 1) for MFGR#mc
    p_brand1     category * 40 + (b - 1) for MFGR#mcb
    d_year       year - 1992;  d_yearmonthnum (year - 1992) * 12 + month - 1
    d_weeknuminyear  week - 1, week = (day of the year - 1) // 7 + 1

Prices are in cents: ``retailprice(p) = 90000 + (p // 10) % 20001 + 100 *
(p % 1000)`` (TPC-H's, ``p`` from 1), ``extendedprice = quantity *
retailprice``, ``revenue = extendedprice * (100 - discount) // 100``,
``supplycost = 6 * retailprice // 10`` and ``xd = extendedprice *
discount``, the product Q1.x sums (PQL has no product aggregate)."""

from __future__ import annotations

import ctypes
import functools
import multiprocessing

import numpy as np

from benchmark.bitmaps import SHARD_WIDTH, WORDS

# TPC-H's nation key -> region key
NATION_REGION = (0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3,
                 4, 2, 3, 3, 1)
FIRST_DAY = np.datetime64("1992-01-01")
LAST_DAY = np.datetime64("1998-08-02")


def one_hot(codes: np.ndarray, n_rows: int) -> np.ndarray:
    """int[2^20] of row ids (under 2^15) -> uint32[n_rows, W]: row r
    holds the columns whose code is r.  The columns sorted by row, each
    word of a row is the OR of its run of bits: no buffer beyond the
    rows themselves and a few arrays of one entry a column."""
    order = np.argsort(codes.astype(np.int16), kind="stable")
    words = codes[order].astype(np.int64) * WORDS + (order >> 5)
    bits = np.left_shift(np.uint32(1), (order & 31).astype(np.uint32))
    starts = np.flatnonzero(np.r_[True, words[1:] != words[:-1]])
    out = np.zeros(n_rows * WORDS, np.uint32)
    out[words[starts]] = np.bitwise_or.reduceat(bits, starts)
    return out.reshape(n_rows, WORDS)


@functools.lru_cache(maxsize=2)
def _calendar() -> dict:
    """Per order day from FIRST_DAY: its year, month and week rows."""
    days = np.arange(FIRST_DAY, LAST_DAY + 1)
    years = days.astype("datetime64[Y]")
    months = days.astype("datetime64[M]")
    year = years.astype(np.int64) + 1970
    day_of_year = (days - years.astype("datetime64[D]")).astype(np.int64)
    return {"d_year": (year - 1992).astype(np.int16),
            "d_yearmonthnum": ((year - 1992) * 12
                               + months.astype(np.int64) % 12)
            .astype(np.int16),
            "d_weeknuminyear": (day_of_year // 7).astype(np.int16)}


@functools.lru_cache(maxsize=2)
def _tables(seed: int, customers: int, suppliers: int, parts: int) -> dict:
    """Every dimension attribute as an array over its table's keys."""
    rng = np.random.default_rng([seed, 30])
    c_nation = rng.integers(0, 25, customers)
    c_city = c_nation * 10 + rng.integers(0, 10, customers)
    s_nation = rng.integers(0, 25, suppliers)
    s_city = s_nation * 10 + rng.integers(0, 10, suppliers)
    p_mfgr = rng.integers(0, 5, parts)
    p_category = p_mfgr * 5 + rng.integers(0, 5, parts)
    p_brand1 = p_category * 40 + rng.integers(0, 40, parts)
    region = np.asarray(NATION_REGION)
    key = np.arange(1, parts + 1, dtype=np.int64)
    return {"c_region": region[c_nation], "c_nation": c_nation,
            "c_city": c_city, "s_region": region[s_nation],
            "s_nation": s_nation, "s_city": s_city, "p_mfgr": p_mfgr,
            "p_category": p_category, "p_brand1": p_brand1,
            "retailprice": 90000 + (key // 10) % 20001 + 100 * (key % 1000)}


@functools.cache
def _reuse_freed_memory() -> None:
    """Keep this process's freed arrays in its heap for the next shard.

    A shard is ~1 GB of arrays of up to 131 MB made and freed (p_brand1's
    1,000 rows, and the writer's and the oracle's copies of them); glibc
    gives any block over 32 MB back to the system on free, and a sealed
    machine whose kernel reclaims given-back memory lazily counted twelve
    loader workers' churn as use until its 40 GiB limit stopped the load
    (at ~120 of 172 shards).  Served from one heap that is never trimmed,
    each worker holds its peak (~1 GB) and reuses it.  Only in a
    worker process of the loader: a process that is nobody's child
    (the harness, a test) keeps the allocator as it was."""
    if multiprocessing.parent_process() is None:
        return
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        return
    m_trim_threshold, m_mmap_max = -1, -4
    libc.mallopt(m_mmap_max, 0)
    libc.mallopt(m_trim_threshold, 1 << 30)


def generate(dataset: dict, seed: int, shard: int) -> dict:
    _reuse_freed_memory()
    n = dataset["tables"]
    tables = _tables(seed, n["customers"], n["suppliers"], n["parts"])
    calendar = _calendar()
    rng = np.random.default_rng([seed, shard])
    keys = {"c": rng.integers(0, n["customers"], SHARD_WIDTH),
            "s": rng.integers(0, n["suppliers"], SHARD_WIDTH),
            "p": rng.integers(0, n["parts"], SHARD_WIDTH),
            "d": rng.integers(0, len(calendar["d_year"]), SHARD_WIDTH)}
    quantity = rng.integers(1, 51, SHARD_WIDTH)
    discount = rng.integers(0, 11, SHARD_WIDTH)
    sets = {}
    for field, spec in dataset["set_fields"].items():
        source = calendar if field.startswith("d_") else tables
        codes = source[field][keys[field[0]]]
        sets[field] = one_hot(codes, len(spec["shares"]))
    price = tables["retailprice"][keys["p"]]
    extended = quantity * price
    values = {"lo_quantity": quantity, "lo_discount": discount,
              "lo_revenue": extended * (100 - discount) // 100,
              "lo_supplycost": 6 * price // 10,
              "xd": extended * discount}
    return {"sets": sets,
            "ints": {f: values[f].astype(np.int32)
                     for f in dataset["int_fields"]}}
