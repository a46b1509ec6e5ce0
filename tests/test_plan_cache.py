"""Query-plan cache (r6 tentpole): repeat serving shapes skip parse
AND plan entirely; generation bumps invalidate; concurrent hit/miss
races stay exact.  The zero-parse property is asserted with a counting
lexer stub (``parse_cached``'s own memoization is cleared first, so
the only thing that can skip tokenization is the plan cache)."""

import threading

import pytest

import pilosa_tpu.pql.parser as parser_mod
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.executor import ExecutionError
from pilosa_tpu.pql.parser import parse_cached
from pilosa_tpu.store import FieldOptions, Holder


def _counters(ex, name):
    return sum(ex.stats.snapshot()["counters"].get(name, {}).values())


@pytest.fixture
def ex(tmp_path):
    from pilosa_tpu.obs import Stats
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("v", FieldOptions(type="int", min=-100, max=100))
    e = Executor(holder, stats=Stats())
    for c in range(20):
        e.execute("i", f"Set({c}, f={c % 4})")
        e.execute("i", f"Set({c}, v={c})")
    yield e
    holder.close()


def test_plan_cache_hit_skips_parsing(ex, monkeypatch):
    """A plan-cache hit performs ZERO PQL parsing: after the first
    request builds the plan, the lexer is never invoked again for that
    query string."""
    pql = "Count(Row(f=1)) Count(Row(f=2))"
    want = ex.execute("i", pql)
    assert want == [5, 5]
    # second request may still fall through (plane residency) — run
    # until the plan serves, then attach the counting stub
    assert ex.execute("i", pql) == want

    tokenize_calls = []
    real_tokenize = parser_mod.lx.tokenize

    def counting(src):
        tokenize_calls.append(src)
        return real_tokenize(src)

    monkeypatch.setattr(parser_mod.lx, "tokenize", counting)
    parse_cached.cache_clear()  # the lru must not mask a parse

    assert ex.execute("i", pql) == want
    assert tokenize_calls == [], \
        "plan-cache hit must not touch the parser"
    assert _counters(ex, "plan_cache_hits") >= 1


def test_generation_bump_serves_fresh_truth(ex):
    """A write must never let a cached plan serve a stale count.
    r15: unkeyed-plane entries SURVIVE the write (nothing in them can
    stale — row ids are literal integers and the PlaneSet revalidates
    its own generations via the delta overlay), so the fresh answer
    arrives withOUT an invalidation + re-plan per write — the property
    that keeps parse+plan off every request under sustained ingest."""
    pql = "Count(Row(f=0))"
    assert ex.execute("i", pql) == [5]
    assert ex.execute("i", pql) == [5]  # plan-cached
    hits_before = _counters(ex, "plan_cache_hits")
    ex.execute("i", "Set(100, f=0)")    # bumps the source generation
    assert ex.execute("i", pql) == [6], \
        "stale plan served a stale count"
    # the surviving entry keeps serving the new truth from the cache
    assert ex.execute("i", pql) == [6]
    assert _counters(ex, "plan_cache_hits") > hits_before, \
        "the unkeyed-plane plan should survive the write"


def test_field_recreated_as_keyed_drops_surviving_plan(ex):
    """The surviving unkeyed-plane entry must still die when the field
    is dropped and recreated with a different identity (keyed/BSI) —
    its literal row ids would otherwise probe the wrong namespace."""
    pql = "Count(Row(f=0))"
    assert ex.execute("i", pql) == [5]
    assert ex.execute("i", pql) == [5]  # plan-cached, write-surviving
    idx = ex.holder.index("i")
    idx.delete_field("f")
    ex.planes.invalidate("i")  # what API.delete_field does (plans NOT
    #                            dropped here: the hazard under test)
    idx.create_field("f", FieldOptions(keys=True))
    with pytest.raises(ExecutionError):
        # integer row on a keyed field must fail like a fresh plan
        # would — not serve the stale literal-row-id plan
        ex.execute("i", pql)


def test_missing_row_then_created(ex):
    """A row that planned as a zeros leaf must surface once created —
    the write bumps the view generation, which invalidates the plan."""
    pql = "Count(Row(f=9))"
    assert ex.execute("i", pql) == [0]
    assert ex.execute("i", pql) == [0]
    ex.execute("i", "Set(3, f=9)")
    assert ex.execute("i", pql) == [1]


def test_bsi_condition_plans(ex):
    """Count over a BSI condition rides the generic plan (predicate
    masks are cached as constants; the bit-plane leaf re-fetches)."""
    pql = "Count(Row(v > 10))"
    want = ex.execute("i", pql)
    assert want == [9]  # values 11..19
    assert ex.execute("i", pql) == want
    ex.execute("i", "Set(50, v=99)")
    assert ex.execute("i", pql) == [10]


def test_composed_tree_plans(ex):
    pql = "Count(Intersect(Row(f=1), Not(Row(f=2))))"
    want = ex.execute("i", pql)
    assert ex.execute("i", pql) == want
    # still exact after an invalidating write
    ex.execute("i", "Set(1, f=2)")
    got = ex.execute("i", pql)
    assert got == [want[0] - 1]


def test_tree_plan_hit_skips_parsing(ex, monkeypatch):
    """r16: a repeated COMPOUND request rides a tree-kind plan entry —
    parse AND plan skipped, answered by the whole-tree program."""
    pql = ("Count(Intersect(Row(f=1), Union(Row(f=2), Row(f=3)), "
           "Not(Row(f=0))))")
    want = ex.execute("i", pql)
    assert ex.execute("i", pql) == want  # plan + plane settled

    tokenize_calls = []
    real_tokenize = parser_mod.lx.tokenize

    def counting(src):
        tokenize_calls.append(src)
        return real_tokenize(src)

    monkeypatch.setattr(parser_mod.lx, "tokenize", counting)
    parse_cached.cache_clear()
    hits_before = _counters(ex, "plan_cache_hits")
    assert ex.execute("i", pql) == want
    assert tokenize_calls == [], \
        "tree-plan hit must not touch the parser"
    assert _counters(ex, "plan_cache_hits") > hits_before
    # and the serving entry really is the tree kind
    assert any(getattr(e, "kind", None) == "tree"
               for e in ex._plans.values())


def test_tree_plan_survives_writes_via_delta_overlay(ex):
    """r16: tree entries over unkeyed set fields skip the per-hit
    generation compare (nothing in them can stale — row ids are
    literal ints, slots re-resolve, the plane absorbs writes into its
    delta overlay), so parse+plan stays off every request under
    sustained ingest AND every answer is fresh."""
    pql = "Count(Difference(Union(Row(f=1), Row(f=2)), Row(f=3)))"
    want = ex.execute("i", pql)
    assert ex.execute("i", pql) == want  # plan-cached
    hits_before = _counters(ex, "plan_cache_hits")
    ex.execute("i", "Set(150, f=1)")  # bumps the source generation
    assert ex.execute("i", pql) == [want[0] + 1], \
        "stale tree plan served a stale count"
    assert ex.execute("i", pql) == [want[0] + 1]
    assert _counters(ex, "plan_cache_hits") > hits_before, \
        "the unkeyed tree plan should survive the write"


def test_tree_plan_drops_on_field_recreation(ex):
    """The surviving tree entry must still die when a baked field is
    dropped and recreated with different options (keyed) — its
    literal row ids would otherwise probe the wrong namespace."""
    pql = "Count(Union(Row(f=1), Row(f=2)))"
    want = ex.execute("i", pql)
    assert ex.execute("i", pql) == want  # cached, write-surviving
    idx = ex.holder.index("i")
    idx.delete_field("f")
    ex.planes.invalidate("i")  # what API.delete_field does (plans NOT
    #                            dropped here: the hazard under test)
    idx.create_field("f", FieldOptions(keys=True))
    with pytest.raises(ExecutionError):
        ex.execute("i", pql)


def test_bsi_recreated_same_depth_drops_surviving_tree_plan(tmp_path):
    """A surviving tree plan bakes BSI predicate OFFSETS against the
    field's base (`to_stored(v) - base`); a drop + recreate with the
    SAME bit depth but a shifted base must still drop the plan — a
    depth-only validity check would let the stale offset serve a
    skewed predicate forever (review fix: validity compares the full
    predicate-relevant option signature)."""
    from pilosa_tpu.obs import Stats
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("v", FieldOptions(type="int", min=0, max=127))
    e = Executor(holder, stats=Stats())
    for c in range(10):
        e.execute("i", f"Set({c}, f=1)")
        e.execute("i", f"Set({c}, v={c * 10})")
    pql = "Count(Intersect(Row(f=1), Row(v > 50)))"
    assert e.execute("i", pql) == [4]  # 60, 70, 80, 90
    assert e.execute("i", pql) == [4]  # cached, write-surviving
    idx.delete_field("v")
    e.planes.invalidate("i")  # what API.delete_field does (plans NOT
    #                           dropped: the peer-node hazard)
    # same bit depth (span 127), base shifted to 100
    idx.create_field("v", FieldOptions(type="int", min=100, max=227))
    for c in range(10):
        e.execute("i", f"Set({c}, v={100 + c * 10})")
    # every value (100..190) is > 50; a stale offset (50 against the
    # old base 0) would answer v > 150 instead → 4
    assert e.execute("i", pql) == [10], \
        "stale BSI offset served a skewed predicate"
    holder.close()


def test_keyed_tree_plan_stays_generation_checked(tmp_path):
    """Tree entries with KEYED rows never take the survival shortcut:
    a write (e.g. creating a row key that planned as missing)
    invalidates through the generation compare, exactly like the
    generic kind."""
    from pilosa_tpu.obs import Stats
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("k", FieldOptions(keys=True))
    e = Executor(holder, stats=Stats())
    for c in range(16):
        e.execute("i", f'Set({c}, k="{"ab"[c % 2]}")')
    pql = 'Count(Union(Row(k="a"), Row(k="zzz")))'
    assert e.execute("i", pql) == [8]
    assert e.execute("i", pql) == [8]
    e.execute("i", 'Set(100, k="zzz")')  # the missing key appears
    assert e.execute("i", pql) == [9], \
        "keyed tree plan must re-plan after the key is created"
    holder.close()


def test_unplannable_shapes_fall_through(ex):
    """Writes and non-Count calls negative-cache and keep serving
    through the normal path, repeatedly and exactly — the pre-write
    Count sees the previous total, the post-write Count sees the new
    bit, every iteration."""
    for i in range(3):
        pre, changed, post = ex.execute(
            "i", f"Count(Row(f=1)) Set({200 + i}, f=1) Count(Row(f=1))")
        assert (pre, changed, post) == (5 + i, True, 6 + i)
    # TopN is not plan-cached but must stay exact alongside cached Counts
    pairs = ex.execute("i", "TopN(f, n=2)")[0].pairs
    assert len(pairs) == 2


def test_concurrent_hits_and_misses_are_exact(ex):
    """Racing threads over a mix of cached/uncached shapes: every
    answer exact, no torn plans."""
    queries = {f"Count(Row(f={r}))": [5 if r < 4 else 0]
               for r in range(8)}
    errors = []
    start = threading.Barrier(8)

    def worker(wid):
        try:
            start.wait()
            for pql, want in list(queries.items()):
                for _ in range(5):
                    assert ex.execute("i", pql) == want
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:3]
    assert _counters(ex, "plan_cache_hits") > 0


def test_explicit_shards_key_separately(ex):
    all_count = ex.execute("i", "Count(Row(f=0))")
    assert ex.execute("i", "Count(Row(f=0))", shards=[0]) == all_count
    # both keys live independently and keep answering
    assert ex.execute("i", "Count(Row(f=0))") == all_count


def test_index_delete_drops_plans(ex):
    pql = "Count(Row(f=1))"
    assert ex.execute("i", pql) == [5]
    assert len(ex._plans) > 0
    ex.invalidate_plans("i")
    assert all(k[0] != "i" for k in ex._plans)
    # and a full clear
    ex.execute("i", pql)
    ex.invalidate_plans()
    assert len(ex._plans) == 0


def test_bsi_depth_growth_outside_shard_subset(tmp_path):
    """bit_depth can grow via a write OUTSIDE a plan's shard subset —
    generations over the entry's shards never see it, so validity
    checks the depth itself (a stale plan would pair old-depth
    predicate masks with the new-depth bit plane)."""
    from pilosa_tpu.engine.words import SHARD_WIDTH
    from pilosa_tpu.obs import Stats

    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("w", FieldOptions(type="int"))  # depth grows
    e = Executor(holder, stats=Stats())
    e.execute("i", "Set(1, w=3) Set(2, w=5)")
    pql = "Count(Row(w > 2))"
    assert e.execute("i", pql, shards=[0]) == [2]
    assert e.execute("i", pql, shards=[0]) == [2]  # plan-cached
    old_depth = idx.field("w").options.bit_depth
    # depth-growing write in ANOTHER shard: shard-0 generations unchanged
    e.execute("i", f"Set({SHARD_WIDTH + 1}, w=1000)")
    assert idx.field("w").options.bit_depth > old_depth
    assert e.execute("i", pql, shards=[0]) == [2]
    assert e.execute("i", pql) == [3]  # full-shard query sees all
    holder.close()


# -- a cached plan that serves: the per-row form (PR 27) ----------------------
#
# A plane / tree entry whose whole-field plane is not resident — and, by
# the selectivity rule (a request touching under a quarter of the
# field's rows), never will be — answers by the per-row form it carries,
# instead of falling through to be planned a second time.

WIDE_ROWS = 32          # one or two of 32 rows: 4 or 8 < 32, per-row by rule


def _wide(tmp_path, keys: bool = False, n_shards: int = 2, **kw):
    """A ``WIDE_ROWS``-row field ``w`` (keyed: ``"k0"`` … ``"k31"``)
    over ``n_shards`` shards beside a four-row field ``f``; returns
    ``(holder, executor, truth)`` with ``truth[field][row]`` the set of
    columns — the op-at-a-time reference is Python's set algebra."""
    from pilosa_tpu.engine.words import SHARD_WIDTH
    from pilosa_tpu.obs import Stats
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("w", FieldOptions(keys=keys))
    idx.create_field("f")
    e = Executor(holder, stats=Stats(), **kw)
    truth = {"w": {}, "f": {}}
    sets = []
    for r in range(WIDE_ROWS):
        cols = {s * SHARD_WIDTH + (r * 7 + j * 5) % 97
                for s in range(n_shards) for j in range(1 + r % 3)}
        truth["w"][r] = cols
        name = f'"k{r}"' if keys else r
        sets += [f"Set({c}, w={name})" for c in sorted(cols)]
    for r in range(4):
        cols = {s * SHARD_WIDTH + r + 4 * j
                for s in range(n_shards) for j in range(6)}
        truth["f"][r] = cols
        sets += [f"Set({c}, f={r})" for c in sorted(cols)]
    e.execute("i", " ".join(sets))
    return holder, e, truth


def _row(keys: bool, r: int) -> str:
    return f'Row(w="k{r}")' if keys else f"Row(w={r})"


def _plan_counters(e) -> dict:
    return {n: _counters(e, n) for n in (
        "plan_cache_hits", "plan_cache_misses", "plan_cache_fallthrough_total",
        "plan_cache_row_serves_total", "plan_cache_invalidations")}


def _entry(e, pql: str):
    return e._plans[("i", pql, None, True)]


def _moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


@pytest.mark.parametrize("shape", ["row", "intersect", "two_counts"])
def test_tiny_slice_of_a_wide_field_is_served_by_the_cached_plan(
        shape, tmp_path, monkeypatch):
    """(a) One or two rows of a 32-row field: the second request is a
    plan-cache hit answered by the entry's per-row form — no
    fall-through, nothing planned or lowered again, the exact answer."""
    from pilosa_tpu.exec import tree as treemod
    holder, e, truth = _wide(tmp_path)
    w = truth["w"]
    pql, want = {
        "row": ("Count(Row(w=3))", [len(w[3])]),
        "intersect": ("Count(Intersect(Row(w=2), Row(w=5)))",
                      [len(w[2] & w[5])]),
        "two_counts": ("Count(Row(w=2)) Count(Row(w=8))",
                       [len(w[2]), len(w[8])]),
    }[shape]
    assert e.execute("i", pql) == want        # builds the plan, serves it
    assert e.serving_path() == "plan-cached per-row"
    planned, lowered = [], []
    real_plan, real_lower = e._plan, treemod.lower_count_tree
    monkeypatch.setattr(e, "_plan", lambda *a, **k: (
        planned.append(a), real_plan(*a, **k))[1])
    monkeypatch.setattr(treemod, "lower_count_tree", lambda *a, **k: (
        lowered.append(a), real_lower(*a, **k))[1])
    before = _plan_counters(e)
    assert e.execute("i", pql) == want
    assert _moved(before, _plan_counters(e)) == {
        "plan_cache_hits": 1, "plan_cache_row_serves_total": 1}
    assert planned == [] and lowered == []
    assert e.serving_path() == "plan-cached per-row"
    assert e.planes.builds == 0               # no whole-plane build, ever
    entry = _entry(e, pql)
    assert entry.kind == ("tree" if shape == "intersect" else "plane")
    assert entry.nodes and entry.leaf_specs
    holder.close()


@pytest.mark.parametrize("keys", [False, True], ids=["unkeyed", "keyed"])
@pytest.mark.parametrize("shape", ["row", "intersect"])
def test_a_set_between_two_row_served_hits_is_read_back(shape, keys,
                                                        tmp_path):
    """(b) An acknowledged write is read by the next hit.  The unkeyed
    entry skips the per-hit generation compare and survives the write
    (every leaf of its per-row form re-fetches through the plane cache,
    which revalidates the row's generations); the keyed one is
    generation-checked and re-plans."""
    from pilosa_tpu.engine.words import SHARD_WIDTH
    holder, e, truth = _wide(tmp_path, keys=keys)
    w = truth["w"]
    if shape == "row":
        pql, want = f"Count({_row(keys, 3)})", len(w[3])
    else:
        pql = f"Count(Intersect({_row(keys, 2)}, {_row(keys, 5)}))"
        want = len(w[2] & w[5])
    assert e.execute("i", pql) == [want]
    assert e.execute("i", pql) == [want]
    assert e.serving_path() == "plan-cached per-row"
    fresh = SHARD_WIDTH + 77                 # in neither row yet
    assert fresh not in (w[2] | w[3] | w[5])
    writes = " ".join(f"Set({fresh}, w={_row(keys, r)[6:-1]})"
                      for r in ((3,) if shape == "row" else (2, 5)))
    before = _plan_counters(e)
    assert e.execute("i", writes) == [True] * (1 if shape == "row" else 2)
    assert e.execute("i", pql) == [want + 1], "a hit served a stale count"
    assert e.execute("i", pql) == [want + 1]
    moved = _moved(before, _plan_counters(e))
    assert "plan_cache_fallthrough_total" not in moved
    if keys:    # generation-checked: the write drops the entry (that
        #         request is answered un-cached); the next one re-plans
        assert moved["plan_cache_invalidations"] == 1
        assert moved["plan_cache_misses"] == 2   # the write, the re-plan
        assert moved["plan_cache_row_serves_total"] == 1
    else:       # survives: both requests after the write are hits
        assert "plan_cache_invalidations" not in moved
        assert moved["plan_cache_hits"] >= 2
        assert moved["plan_cache_row_serves_total"] == 2
    holder.close()


def test_a_keyed_row_created_after_planning_surfaces(tmp_path):
    """A ``zeros`` leaf (a key absent at planning time) stays
    generation-checked in the per-row form: creating the key bumps the
    view's generations and the entry re-plans."""
    holder, e, truth = _wide(tmp_path, keys=True)
    pql = 'Count(Union(Row(w="k1"), Row(w="nobody")))'
    want = len(truth["w"][1])
    assert e.execute("i", pql) == [want]
    assert e.execute("i", pql) == [want]
    assert e.serving_path() == "plan-cached per-row"
    e.execute("i", 'Set(500, w="nobody")')
    assert e.execute("i", pql) == [want + 1]
    holder.close()


@pytest.mark.parametrize("shape", ["two_counts", "intersect"])
def test_a_quarter_of_the_rows_falls_through_once_then_runs_whole_plane(
        shape, tmp_path):
    """(c) A request touching at least a quarter of its field's rows is
    an admission decision: the hit falls through once, the un-cached
    path builds the whole-field plane, and from then on the entry's
    own plane / tree program answers — never its per-row form."""
    holder, e, truth = _wide(tmp_path)
    e.planes.SYNC_BUILD_MAX = 0     # build in the background, as at size
    f = truth["f"]
    pql, want = {
        "two_counts": ("Count(Row(f=1)) Count(Row(f=2))",
                       [len(f[1]), len(f[2])]),
        "intersect": ("Count(Union(Row(f=1), Row(f=2)))",
                      [len(f[1] | f[2])]),
    }[shape]
    before = _plan_counters(e)
    assert e.execute("i", pql) == want
    assert e.serving_path() == "generic per-row"
    e.planes.wait_builds()
    assert _moved(before, _plan_counters(e)) == {
        "plan_cache_misses": 1, "plan_cache_fallthrough_total": 1}
    assert e.planes.builds == 1              # the whole-field plane
    idx = holder.index("i")
    assert e.planes.has_plane("i", idx.field("f"), "standard",
                              e._shards_for(idx, None, None))
    before = _plan_counters(e)
    for _ in range(2):
        assert e.execute("i", pql) == want
        assert e.serving_path() == "fused"
    assert _moved(before, _plan_counters(e)) == {"plan_cache_hits": 2}
    holder.close()


def test_a_plane_past_the_budget_still_pages(tmp_path):
    """(d) est > budget: the rule does not apply, the hit falls through
    and the un-cached path serves paged — admission stays there."""
    holder, e, truth = _wide(tmp_path, n_shards=3,
                             plane_budget=1200 * 1024,
                             plane_page_bytes=1 << 20)
    pql, want = "Count(Row(w=3))", [len(truth["w"][3])]
    for _ in range(2):
        before = _plan_counters(e)
        assert e.execute("i", pql) == want
        assert e.serving_path() == "paged"
        moved = _moved(before, _plan_counters(e))
        assert moved["plan_cache_fallthrough_total"] == 1
        assert "plan_cache_row_serves_total" not in moved
    assert e.tenancy_status()["pageIns"] >= 1
    holder.close()


@pytest.mark.parametrize("pql", [
    "Count(Row(w=3))",
    "Count(Intersect(Row(w=2), Row(w=5)))",
    "Count(Union(Row(w=1), Not(Row(w=4))))",
    "Count(Row(w=2)) Count(Difference(Row(w=8), Row(w=9)))",
])
def test_cached_nodes_are_the_uncached_plan(pql, tmp_path):
    """(e) The cached ``nodes`` have the structure ``_plan`` gives the
    un-cached path, so the programs it compiled are the ones a hit
    runs: ``costs.compileCount`` does not move between the un-cached
    answers and the cached ones.  (Three of each: the solo fast lane's
    first dispatch has no retired output to donate, so one shape is two
    programs on either path.)"""
    from pilosa_tpu.exec.executor import _Ctx
    from pilosa_tpu.exec.fused import shift_leaves
    holder, e, _ = _wide(tmp_path)
    idx = holder.index("i")
    with_cache = e._execute_planned
    e._execute_planned = lambda *a, **k: None           # un-cached
    try:
        want = e.execute("i", pql)
        assert [e.execute("i", pql) for _ in range(2)] == [want] * 2
    finally:
        e._execute_planned = with_cache
    assert e.serving_path() == "fused"
    assert ("i", pql, None, True) not in e._plans
    compiles = e.cost_status()["compileCount"]
    assert compiles >= 1
    assert [e.execute("i", pql) for _ in range(3)] == [want] * 3
    assert _counters(e, "plan_cache_row_serves_total") == 3
    assert e.serving_path() == "plan-cached per-row"
    assert e.cost_status()["compileCount"] == compiles
    ctx = _Ctx(idx, e._shards_for(idx, None, None))
    nodes, n_leaves = [], 0
    for call in parse_cached(pql).calls:
        leaves: list = []
        nodes.append(shift_leaves(e._plan(ctx, call.children[0], leaves),
                                  n_leaves))
        n_leaves += len(leaves)
    entry = _entry(e, pql)
    assert entry.nodes == tuple(nodes)
    assert len(entry.leaf_specs) == n_leaves
    holder.close()
