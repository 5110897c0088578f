"""Per-partition standing-query counts from corner tables.

``geometry.corner_tables``/``count_overlaps`` and the grid routers'
registration and re-index built on them must give, for every live
partition (and, under keywords, every pivot bucket), exactly the
brute-force ``boxes_overlap`` count over every standing query: for
rects wider than a cell, zero-area rects, rects clipped at the grid's
edges, partitions on the borders, the layouts real SWARM splits leave,
any batching of the registrations, and after a checkpoint restore.
"""
import tempfile
import zlib

import numpy as np
import pytest

from repro.checkpoint import restore_stream, save_stream
from repro.core import geometry
from repro.queries import WorkloadSpec
from repro.streaming import (EngineConfig, QueryBatch,
                             RouterSpec, ScenarioSpec, StreamingEngine,
                             SwarmRouter, scenario)
from repro.streaming.baselines import (StaticUniformRouter,
                                       force_rebalance_round)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

G = 32
WORKLOADS = ["range", "spatial_keyword"]


def _rects(kind: str, n: int, rng) -> np.ndarray:
    """(n, 4) float32 rects of one shape family."""
    c = rng.random((n, 2))
    if kind == "wide":           # up to half the space on a side
        half = rng.random((n, 2)) * 0.25
    elif kind == "zero_area":    # points and lines: x0 == x1 or y0 == y1
        half = np.zeros((n, 2))
        a, b = n // 3, 2 * n // 3
        half[:a, 0] = rng.random(a) * 0.1
        half[a:b, 1] = rng.random(b - a) * 0.1
    elif kind == "clipped":      # spill over 0 and 1 on every side
        c = rng.random((n, 2)) * 1.4 - 0.2
        half = rng.random((n, 2)) * 0.3
    else:                        # campus-sized, the benchmark's shape
        half = np.full((n, 2), 0.0008)
    return np.concatenate([c - half, c + half], 1).astype(np.float32)


def _layout(kind: str, rng):
    """Partition boxes (pr0, pc0, pr1, pc1), inclusive cells."""
    if kind == "borders":
        e = G - 1
        boxes = [(0, 0, e, e), (0, 0, 0, 0), (e, e, e, e), (0, e, 0, e),
                 (e, 0, e, 0), (0, 0, 0, e), (e, 0, e, e), (0, 0, e, 0),
                 (0, e, e, e), (1, 1, e - 1, e - 1), (5, 0, 9, e)]
        return tuple(np.array(v) for v in zip(*boxes))
    r0 = rng.integers(0, G, 64)
    c0 = rng.integers(0, G, 64)
    return (r0, c0, np.minimum(r0 + rng.integers(0, G, 64), G - 1),
            np.minimum(c0 + rng.integers(0, G, 64), G - 1))


def _brute(rects, boxes, keys=None, n_keys=1) -> np.ndarray:
    """(n_keys, P) overlap counts by the direct Q × P test."""
    r0, c0, r1, c1 = geometry.rects_to_cells(rects, G)
    hit = geometry.boxes_overlap(r0[:, None], c0[:, None], r1[:, None],
                                 c1[:, None], *(b[None, :] for b in boxes))
    out = np.zeros((n_keys, hit.shape[1]), np.int64)
    qi, pi = np.nonzero(hit)
    np.add.at(out, (np.zeros_like(qi) if keys is None else keys[qi], pi), 1)
    return out


@pytest.mark.parametrize("keyed", [False, True])
@pytest.mark.parametrize("layout", ["borders", "random"])
@pytest.mark.parametrize("kind", ["wide", "zero_area", "clipped", "campus"])
def test_corner_table_counts_match_brute_force(kind, layout, keyed):
    rng = np.random.default_rng(
        zlib.crc32(f"{kind}/{layout}/{keyed}".encode()))
    rects = _rects(kind, 3000, rng)
    boxes = _layout(layout, rng)
    n_keys = 5 if keyed else 1
    keys = rng.integers(0, n_keys, len(rects)) if keyed else None
    tabs = geometry.corner_tables(*geometry.rects_to_cells(rects, G), G,
                                  keys, n_keys)
    np.testing.assert_array_equal(geometry.count_overlaps(tabs, *boxes),
                                  _brute(rects, boxes, keys, n_keys))


def _workload(name: str) -> WorkloadSpec:
    return WorkloadSpec(query_model=name)


def _queries(wl: WorkloadSpec, n: int, seed: int):
    """Mixed-shape rects (and subscription terms under keywords)."""
    rng = np.random.default_rng(seed)
    kinds = ["wide", "zero_area", "clipped", "campus"]
    rects = np.concatenate([_rects(k, n // 4, rng) for k in kinds])
    terms = None
    if wl.spec.keyword:
        src = scenario("hot_hashtags", horizon=40, query_burst=0, seed=seed)
        terms = src.sample_subscription_terms(len(rects), 0, wl.sub_terms)
    return rects, terms


def _assert_counts_exact(router) -> None:
    """qres (and qres_kw) hold the brute-force counts on live
    partitions and zero elsewhere."""
    p = router.index.parts
    live = p.live_ids()
    boxes = (p.r0[live], p.c0[live], p.r1[live], p.c1[live])
    kw = router.qres_kw is not None
    n_keys = router.qres_kw.shape[1] if kw else 1
    want = _brute(router.query_rects, boxes,
                  router.sub_pivots if kw else None, n_keys)
    np.testing.assert_array_equal(router.qres[live], want.sum(0))
    dead = np.setdiff1d(np.arange(len(router.qres)), live)
    assert not router.qres[dead].any()
    if kw:
        np.testing.assert_array_equal(router.qres_kw[live], want.T)
        assert not router.qres_kw[dead].any()


@pytest.mark.parametrize("wl", WORKLOADS)
def test_reindex_matches_brute_force_through_swarm_splits(wl):
    """Layouts from real SWARM splits: a moving hotspot, a forced
    rebalancing round after each move, a re-index after every round."""
    wl = _workload(wl)
    router = SwarmRouter(G, 4, beta=4, workload=wl)
    rects, terms = _queries(wl, 4000, seed=1)
    router.register_queries(rects, terms)
    rng = np.random.default_rng(2)
    n_alloc = router.index.parts.n_alloc
    for step in range(6):
        lo = 0.1 + 0.12 * step
        router.swarm.ingest_points(
            rng.uniform(lo, lo + 0.15, (3000, 2)).astype(np.float32))
        force_rebalance_round(router.swarm)
        router.reindex_all_queries()
        _assert_counts_exact(router)
    assert router.index.parts.n_alloc > n_alloc   # the rounds split


@pytest.mark.parametrize("wl", WORKLOADS)
def test_registration_one_batch_matches_small_batches(wl):
    """One large batch and many small ones (down to single queries)
    leave the same counts and tables; registration between rounds adds
    to the live partitions only."""
    wl = _workload(wl)
    rects, terms = _queries(wl, 4000, seed=3)
    one = SwarmRouter(G, 4, workload=wl)
    many = SwarmRouter(G, 4, workload=wl)
    one.register_queries(rects, terms)
    lo = 0
    for size in [1, 1, 7, 64, 500, 1000, 3000]:
        hi = min(lo + size, len(rects))
        many.register_queries(rects[lo:hi],
                              None if terms is None else terms[lo:hi])
        lo = hi
    assert lo == len(rects)
    for r in (one, many):
        _assert_counts_exact(r)
    np.testing.assert_array_equal(one.qres, many.qres)
    np.testing.assert_array_equal(one._qtab, many._qtab)
    if terms is not None:
        np.testing.assert_array_equal(one.qres_kw, many.qres_kw)
        np.testing.assert_array_equal(one.sub_pivots, many.sub_pivots)
    # a round retires and splits partitions; a batch registered after it
    # counts against the new layout
    one.swarm.ingest_points(np.random.default_rng(4).uniform(
        0.0, 0.2, (3000, 2)).astype(np.float32))
    force_rebalance_round(one.swarm)
    one.reindex_all_queries()
    more, more_terms = _queries(wl, 400, seed=5)
    one.register_queries(more, more_terms)
    _assert_counts_exact(one)


def test_static_router_counts_match_brute_force():
    """The static grid shares the tables (no rounds: registration only)."""
    router = StaticUniformRouter(G, 5)
    rects, _ = _queries(_workload("range"), 2000, seed=6)
    router.register_queries(rects[:1500])
    router.register_queries(rects[1500:])
    _assert_counts_exact(router)


@pytest.mark.parametrize("wl", WORKLOADS)
def test_checkpoint_restore_then_reindex(wl):
    """A restore sets the standing queries directly; the tables it
    rebuilds must hold queries registered after the preload too."""
    wl = _workload(wl)
    name = "hot_hashtags" if wl.spec.keyword else "uniform_normal"
    spec = ScenarioSpec(name, ticks=40, preload_queries=1000)

    def build():
        src = spec.build(seed=0, workload=wl)
        router = RouterSpec(kind="swarm", beta=4, grid_size=G).build(
            num_machines=4, workload=wl, data_plane="numpy")
        eng = StreamingEngine(router, src, EngineConfig(num_machines=4))
        router.ingest(eng.stream.preload(spec.preload_queries))
        return eng

    eng = build()
    eng.run(12)
    extra, extra_terms = _queries(wl, 800, seed=7)
    eng.router.ingest(QueryBatch(extra, terms=extra_terms))
    eng.run(12)
    saved = eng.router.qres.copy()
    with tempfile.TemporaryDirectory() as d:
        save_stream(d, eng)
        fresh = build()
        assert restore_stream(d, fresh) == eng.tick_no
    assert fresh.router.q_total == eng.router.q_total
    np.testing.assert_array_equal(fresh.router._qtab, eng.router._qtab)
    fresh.router.reindex_all_queries()
    np.testing.assert_array_equal(fresh.router.qres, saved)
    _assert_counts_exact(fresh.router)
