"""Spatio-textual pub/sub subsystem tests.

Covers the hashed term dimension (collision-bound property vs
brute-force per-term matching), the keyword_match kernel package
(NumPy↔JAX parity, interpret-mode Pallas vs ref), the keyword cost
path on both data planes, fused-window ≡ per-tick identity for
spatial-keyword workloads, the exact 0-keyword degradation to the
continuous-range golden behaviour, and the experiment-suite label
folding of the new keyword knobs.
"""
import numpy as np
import pytest

from repro.queries import (QueryModel, QueryModelSpec, SubscriptionIndex,
                           TermHasher, WorkloadSpec, all_workloads,
                           bucket_masks, get_query_model,
                           register_query_model)
from repro.queries.keywords import bucket_onehot, tokenize
from repro.streaming import (EngineConfig, EventStream, Experiment,
                             RouterSpec, ScenarioSpec, SwarmRouter,
                             TupleBatch, run, scenario)
from repro.streaming.planes import CostParams, JaxPlane, NumpyPlane

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # container without hypothesis: seeded sweep
    HAVE_HYPOTHESIS = False

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


# ---------------------------------------------------------------------------
# brute-force oracle
# ---------------------------------------------------------------------------

def _exact_hits(points, terms, rects, sub_terms):
    """(N, Q) bool: spatial containment AND exact per-term conjunction
    (no hashing) — the semantics hashed matching may only overcount."""
    n, q = len(points), len(rects)
    inside = ((points[:, None, 0] >= rects[None, :, 0])
              & (points[:, None, 0] <= rects[None, :, 2])
              & (points[:, None, 1] >= rects[None, :, 1])
              & (points[:, None, 1] <= rects[None, :, 3]))
    hit = inside.copy()
    for i in range(n):
        tset = set(int(t) for t in terms[i] if t >= 0)
        for j in range(q):
            sset = set(int(t) for t in sub_terms[j] if t >= 0)
            if not sset <= tset:
                hit[i, j] = False
    return hit


def _hashed_hits(hasher, points, terms, rects, sub_terms):
    """(N, Q) bool via the bucket-mask encoding (what the kernel and
    the cost model see)."""
    pm = bucket_masks(hasher.buckets(terms), hasher.n_buckets)
    sm = hasher.sub_masks(sub_terms)
    inside = ((points[:, None, 0] >= rects[None, :, 0])
              & (points[:, None, 0] <= rects[None, :, 2])
              & (points[:, None, 1] >= rects[None, :, 1])
              & (points[:, None, 1] <= rects[None, :, 3]))
    miss = (1.0 - pm) @ sm.T
    return inside & (miss < 0.5)


def _random_case(seed, n_buckets):
    rng = np.random.default_rng(seed)
    n, q = int(rng.integers(1, 40)), int(rng.integers(1, 40))
    vocab = int(rng.integers(2, 60))
    hasher = TermHasher(n_buckets)
    points = rng.random((n, 2)).astype(np.float32)
    lo = rng.random((q, 2)) * 0.8
    side = rng.random((q, 2)) * 0.4
    rects = np.concatenate([lo, np.minimum(lo + side, 1.0)],
                           axis=1).astype(np.float32)
    terms = rng.integers(0, vocab, (n, int(rng.integers(0, 4))))
    sub_terms = rng.integers(0, vocab, (q, int(rng.integers(0, 3))))
    return hasher, points, terms, rects, sub_terms


def _check_collision_bound(seed, n_buckets):
    hasher, points, terms, rects, sub_terms = _random_case(seed, n_buckets)
    exact = _exact_hits(points, terms, rects, sub_terms)
    hashed = _hashed_hits(hasher, points, terms, rects, sub_terms)
    # 1. conservative: hashing can only OVERcount, never drop a match
    assert (hashed | ~exact).all(), "hashed matching dropped a true match"
    # 2. tight up to collisions: when the bucket map is injective on
    # the vocabulary actually used, hashed == exact
    used = np.unique(np.concatenate(
        [terms.reshape(-1), sub_terms.reshape(-1)]))
    used = used[used >= 0]
    buckets = hasher.buckets(used)
    if len(np.unique(buckets)) == len(used):
        np.testing.assert_array_equal(hashed, exact)


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**32 - 1), st.sampled_from([4, 16, 64, 257]))
    def test_hashed_matching_collision_bound(seed, n_buckets):
        _check_collision_bound(seed, n_buckets)
else:
    @pytest.mark.parametrize("n_buckets", [4, 16, 64, 257])
    @pytest.mark.parametrize("seed", range(15))
    def test_hashed_matching_collision_bound(seed, n_buckets):
        _check_collision_bound(seed, n_buckets)


def test_subscription_index_candidates_are_superset():
    hasher, points, terms, rects, sub_terms = _random_case(7, 8)
    idx = SubscriptionIndex.build(hasher, rects, sub_terms)
    exact = _exact_hits(points, terms, rects, sub_terms)
    probes = hasher.tuple_buckets(terms)
    for i in range(len(points)):
        cand = set(idx.candidates(probes[i]).tolist())
        matched = set(np.nonzero(exact[i])[0].tolist())
        assert matched <= cand
    # posting lists partition the subscription set
    total = sum(len(idx.posting(b))
                for b in range(hasher.n_buckets + 1))
    assert total == len(rects)


def test_tokenize_and_token_buckets():
    toks = tokenize("BigSpatial #Data streams, big spatial!")
    assert "#data" in toks and "bigspatial" in toks
    h = TermHasher(16)
    b = h.token_buckets(toks)
    assert b.shape == (len(toks),) and (b >= 0).all() and (b < 16).all()


# ---------------------------------------------------------------------------
# keyword_match kernel package
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,q,t", [(1, 1, 4), (37, 53, 8), (200, 131, 32),
                                   (130, 257, 11)])
def test_keyword_kernel_interpret_matches_ref(n, q, t):
    import jax.numpy as jnp

    from repro.kernels.keyword_match import keyword_match, keyword_match_ref
    rng = np.random.default_rng(n * 1000 + q)
    pts = rng.random((n, 2)).astype(np.float32)
    lo = rng.random((q, 2)) * 0.7
    rects = np.concatenate([lo, lo + rng.random((q, 2)) * 0.5],
                           1).astype(np.float32)
    pm = (rng.random((n, t)) < 0.3).astype(np.float32)
    sm = (rng.random((q, t)) < 0.2).astype(np.float32)
    ref_p, ref_q = keyword_match_ref(jnp.asarray(pts), jnp.asarray(pm),
                                     jnp.asarray(rects), jnp.asarray(sm))
    ker_p, ker_q = keyword_match(jnp.asarray(pts), jnp.asarray(pm),
                                 jnp.asarray(rects), jnp.asarray(sm),
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(ker_p), np.asarray(ref_p))
    np.testing.assert_array_equal(np.asarray(ker_q), np.asarray(ref_q))


def test_plane_match_counts_numpy_jax_identical():
    rng = np.random.default_rng(3)
    h = TermHasher(16)
    pts = rng.random((150, 2)).astype(np.float32)
    lo = rng.random((60, 2)) * 0.6
    rects = np.concatenate([lo, lo + 0.3], 1).astype(np.float32)
    pm = bucket_masks(h.buckets(rng.integers(0, 99, (150, 3))), 16)
    sm = h.sub_masks(rng.integers(0, 99, (60, 2)))
    a = NumpyPlane().keyword_match_counts(pts, pm, rects, sm)
    b = JaxPlane().keyword_match_counts(pts, pm, rects, sm)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


# ---------------------------------------------------------------------------
# keyword cost path: plane parity + fused identity
# ---------------------------------------------------------------------------

def _keyword_cost_fixture(seed=0, t=16):
    rng = np.random.default_rng(seed)
    g, p, m = 8, 16, 4
    grid = rng.integers(0, p, (g, g)).astype(np.int32)
    owner = rng.integers(0, m, p).astype(np.int32)
    qres_kw = rng.integers(0, 25, (p, t + 1)).astype(np.float64)
    qm = rng.integers(0, 150, m).astype(np.float64)
    area = np.full(p, 1.0 / p)
    cp = CostParams(c0=0.2, kappa_probe=0.01, kappa_match=0.5, q_cache=64.0,
                    query_area=0.01, match_factor=1.0, tuple_driven=True,
                    store_cost=0.0, delivery_cost=0.05, keyword=True)
    xy = rng.random((300, 2)).astype(np.float32)
    kw = TermHasher(t).tuple_buckets(rng.integers(0, 400, (300, 3)))
    return grid, owner, qres_kw, qm, area, cp, xy, bucket_onehot(kw, t)


def test_keyword_costs_numpy_jax_parity():
    grid, owner, qres_kw, qm, area, cp, xy, oh = _keyword_cost_fixture()
    out_n = NumpyPlane().keyword_costs(xy, oh, grid, owner, qres_kw, qm,
                                       area, cp)
    out_j = JaxPlane().keyword_costs(xy, oh, grid, owner, qres_kw, qm,
                                     area, cp)
    np.testing.assert_array_equal(np.asarray(out_n[0]), np.asarray(out_j[0]))
    np.testing.assert_array_equal(np.asarray(out_n[1]), np.asarray(out_j[1]))
    np.testing.assert_allclose(np.asarray(out_n[2], np.float64),
                               np.asarray(out_j[2], np.float64), rtol=1e-5)
    np.testing.assert_allclose(out_n[3], np.asarray(out_j[3], np.float64),
                               rtol=1e-5)


def _pubsub_experiment(plane, fused_window=0, kind="swarm"):
    wl = WorkloadSpec(query_model="spatial_keyword")
    sc = ScenarioSpec("hot_hashtags", ticks=24, preload_queries=1500,
                      query_burst=0, hot_terms=2, term_peak=0.5)
    eng = EngineConfig(num_machines=8, lambda_max=500, cap_units=2e4,
                       fused_window=fused_window)
    return Experiment(router=RouterSpec(kind), scenario=sc, workload=wl,
                      engine=eng, data_plane=plane)


def test_fused_equals_per_tick_keyword_numpy_exact():
    a = run(_pubsub_experiment("numpy")).metrics
    b = run(_pubsub_experiment("numpy", fused_window=8)).metrics
    for name in ("units_of_work", "throughput", "latency", "deliveries",
                 "wire_bytes", "migration_bytes", "transfers"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name), float),
            np.asarray(getattr(b, name), float), err_msg=name)
    assert float(np.sum(a.deliveries)) > 0


def test_fused_equals_per_tick_keyword_jax():
    a = run(_pubsub_experiment("jax")).metrics
    b = run(_pubsub_experiment("jax", fused_window=8)).metrics
    np.testing.assert_allclose(np.asarray(a.throughput, float),
                               np.asarray(b.throughput, float), rtol=1e-3)
    np.testing.assert_allclose(np.asarray(a.deliveries, float),
                               np.asarray(b.deliveries, float),
                               rtol=1e-3, atol=1e-6)


def test_zero_keyword_degrades_to_continuous_range_exactly():
    kw = WorkloadSpec(query_model="spatial_keyword", tuple_terms=0,
                      sub_terms=0, delivery_cost=0.0, delivery_bytes=0)
    rg = WorkloadSpec()
    sc = ScenarioSpec("uniform_normal", ticks=16, preload_queries=800,
                      query_burst=100)
    eng = EngineConfig(num_machines=6, lambda_max=400, cap_units=2e4)
    for plane in ("numpy", "jax"):
        a = run(Experiment(router=RouterSpec("swarm"), scenario=sc,
                           workload=kw, engine=eng, data_plane=plane)).metrics
        b = run(Experiment(router=RouterSpec("swarm"), scenario=sc,
                           workload=rg, engine=eng, data_plane=plane)).metrics
        for name in ("units_of_work", "throughput", "latency", "wire_bytes",
                     "migration_bytes", "transfers"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, name), float),
                np.asarray(getattr(b, name), float),
                err_msg=f"{plane}/{name}")


# ---------------------------------------------------------------------------
# event/decision wiring + delivery billing
# ---------------------------------------------------------------------------

def test_event_stream_attaches_terms_and_buckets():
    wl = WorkloadSpec(query_model="spatial_keyword")
    src = scenario("hot_hashtags", horizon=40, query_burst=0)
    es = EventStream(src, wl)
    bt = es.tuples(64, 12)
    assert bt.terms is not None and bt.terms.shape == (64, wl.tuple_terms)
    assert bt.buckets is not None
    assert bt.buckets.shape == (64, wl.tuple_terms + 1)
    assert (bt.buckets[:, -1] == es.hasher.wildcard).all()
    qb = es.preload(32)
    assert qb.terms is not None and qb.terms.shape == (32, wl.sub_terms)
    # pure-spatial workloads stay term-free (and RNG-identical: terms
    # are only sampled when the spec asks for them)
    es2 = EventStream(scenario("uniform_normal", horizon=40), WorkloadSpec())
    bt2 = es2.tuples(64, 12)
    assert bt2.terms is None and bt2.buckets is None


def test_router_decision_carries_deliveries_and_bills_wire():
    from repro.core.cost_model import delivery_wire_bytes
    wl = WorkloadSpec(query_model="spatial_keyword")
    src = scenario("hot_hashtags", horizon=40, query_burst=0)
    es = EventStream(src, wl)
    router = SwarmRouter(32, 4, workload=wl)
    router.ingest(es.preload(500))
    d = router.ingest(es.tuples(128, 5))
    assert d.deliveries is not None and d.deliveries.shape == (128,)
    assert (d.deliveries >= 0).all()
    assert delivery_wire_bytes(float(d.deliveries.sum()),
                               wl.delivery_bytes) >= 0
    # wildcard-only batch (no term annotations) still matches
    # keyword-free subscriptions, never keyworded ones
    d2 = router.ingest(TupleBatch(np.random.default_rng(0)
                                  .random((16, 2)).astype(np.float32)))
    assert d2.deliveries is not None
    assert delivery_wire_bytes(0.0, wl.delivery_bytes) == 0


def test_bulk_subscription_indexing_matches_loop():
    wl = WorkloadSpec(query_model="spatial_keyword")
    src = scenario("hot_hashtags", horizon=40, query_burst=0, seed=4)
    rects = src.sample_queries(6000)
    terms = src.sample_subscription_terms(6000, 0, wl.sub_terms)
    bulk = SwarmRouter(32, 4, workload=wl)
    loop = SwarmRouter(32, 4, workload=wl)
    assert len(rects) >= 4096
    bulk.register_queries(rects, terms)           # bulk path (one batch)
    for lo in range(0, len(rects), 500):          # loop path (small batches)
        loop.register_queries(rects[lo:lo + 500], terms[lo:lo + 500])
    np.testing.assert_array_equal(bulk.qres, loop.qres)
    np.testing.assert_array_equal(bulk.qres_kw, loop.qres_kw)
    np.testing.assert_array_equal(bulk.sub_pivots, loop.sub_pivots)


# ---------------------------------------------------------------------------
# registry / experiment-suite integration
# ---------------------------------------------------------------------------

def test_spatial_keyword_model_registered():
    spec = get_query_model(QueryModel.SPATIAL_KEYWORD)
    assert spec.keyword and spec.continuous and spec.tuple_driven
    assert not spec.snapshot


def test_all_workloads_keyword_opt_in():
    assert len(all_workloads()) == 6          # default matrix unchanged
    kw = [w for w in all_workloads(keyword=True)
          if w.spec.keyword]
    assert kw and all(w.query_model is QueryModel.SPATIAL_KEYWORD
                      for w in kw)


def test_registry_serves_custom_keyword_model():
    spec = QueryModelSpec("geo_tag", continuous=True, tuple_driven=True,
                          snapshot=False, keyword=True)
    register_query_model(spec)
    assert get_query_model("geo_tag") is spec
    assert get_query_model("geo_tag").keyword


def test_workload_label_folds_keyword_knobs():
    a = WorkloadSpec(query_model="spatial_keyword")
    b = WorkloadSpec(query_model="spatial_keyword", term_buckets=64)
    c = WorkloadSpec(query_model="spatial_keyword", tuple_terms=5)
    assert len({a.label, b.label, c.label}) == 3
    # keyword knobs never leak into pure-spatial labels
    assert "T=" not in WorkloadSpec().label


def test_scenario_key_folds_keyword_sweeps():
    """Pub/sub sweeps in run_suite cannot collide: hot-term count,
    peak and vocabulary all fold into ``ScenarioSpec.key`` (regression
    companion to test_api's label-folding test)."""
    base = ScenarioSpec("hot_hashtags", ticks=30)
    keys = {base.key,
            ScenarioSpec("hot_hashtags", ticks=30, hot_terms=2,
                         term_peak=0.5).key,
            ScenarioSpec("hot_hashtags", ticks=30, hot_terms=3,
                         term_peak=0.5).key,
            ScenarioSpec("hot_hashtags", ticks=30, hot_terms=2,
                         term_peak=0.3).key,
            ScenarioSpec("hot_hashtags", ticks=30, hot_terms=2,
                         term_peak=0.5, vocab=5000).key}
    assert len(keys) == 5
    labels = {Experiment(scenario=s).label
              for s in (base,
                        ScenarioSpec("hot_hashtags", ticks=30, hot_terms=2,
                                     term_peak=0.5))}
    assert len(labels) == 2
