"""The benchmark's one traffic generator, driven by a mix file.

A mix file (``bench/traffic/<name>.json``) holds parameters only: the
background city mixture, hotspot and hot-term timelines, the term law,
the timeline's period in ticks, and how the driver offers load (open
loop at a fixed rate, or closed loop).  The generators below are copies
of the program's own sound ones (``TwitterLikeSource``'s city mixture,
``Hotspot``, ``HotTerm``, the Zipf term sampler, and the pooling idea
of ``ReplaySource``), kept here so that a later change to the program's
sources cannot move the yardstick.

Everything random is drawn once, at set-up, into pools: ``--seed``
picks the points and terms in the pools, while the city mixture comes
from the mix's own ``mixture_seed``, so every seed offers the same
deployment, the same per-tick sizes and the same timeline.  A mix that
names a ``pool_seed`` draws the pools and the standing queries from it
as well, and ``--seed`` then draws only their order: each tick's events
rotated by a seed-drawn offset, the queries in a seed-drawn
permutation.  Every seed then does the same work: the same events in
each tick and the same queries, in another order.  A tick's batch is then assembled from the pools by the timeline
at copy cost, and is a pure function of the tick number: the plain
reference rebuilds any tick's events after the run.  The timeline is
periodic (``period_ticks``), so a faster program sees the same mix, not
a later phase of it.

The generator hands the system raw points and raw term ids: binning and
term hashing stay the system's work.
"""
from __future__ import annotations

import math

import numpy as np

# paper §6: the hotspot box side is 15 % of the space
HOTSPOT_SIDE = 0.15


def city_mixture(rng: np.random.Generator, n_cities: int):
    """Weights, centers and scales of the Twitter-like background (a
    copy of ``sources.make_city_mixture``)."""
    centers = rng.uniform(0.05, 0.95, size=(n_cities, 2))
    weights = rng.pareto(1.2, size=n_cities) + 0.05
    weights /= weights.sum()
    scales = rng.uniform(0.005, 0.04, size=n_cities)
    return weights, centers, scales


def normal_profile(t: int, start: int, duration: int, peak: float) -> float:
    """Share of the stream redirected at tick ``t`` by a timeline with a
    normal temporal profile (``Hotspot.fraction`` / ``HotTerm.fraction``)."""
    t = t - start
    if t < 0 or t >= duration:
        return 0.0
    mid, sig = duration / 2, duration / 6
    return peak * math.exp(-0.5 * ((t - mid) / sig) ** 2)


def _cyclic(pool: np.ndarray, start: int, n: int) -> np.ndarray:
    """``n`` consecutive rows of ``pool`` from ``start``, wrapping."""
    size = len(pool)
    start %= size
    if start + n <= size:
        return pool[start:start + n]
    return np.take(pool, np.arange(start, start + n) % size, axis=0)


class Traffic:
    """The source the engine pulls from, for one mix, one seed and one
    batch size (events per tick)."""

    def __init__(self, mix: dict, seed: int, batch: int,
                 queries: dict | None = None):
        self.mix = mix
        self.batch = int(batch)
        self.period = int(mix["period_ticks"])
        self.vocab = int(mix.get("vocab", 0))
        mrng = np.random.default_rng(int(mix["mixture_seed"]))
        self.weights, self.centers, self.scales = city_mixture(
            mrng, int(mix["n_cities"]))
        rng = np.random.default_rng(int(seed))
        # with a ``pool_seed`` the seed orders what that seed draws
        self.order_rng = None
        if mix.get("pool_seed") is not None:
            self.order_rng = rng
            self.shift = int(rng.integers(self.batch))
            rng = np.random.default_rng(int(mix["pool_seed"]))
        self._qperm = None
        pool_n = int(mix["pool_ticks"]) * self.batch
        self.background = self._mixture_points(rng, pool_n)
        self.hotspots = [dict(h) for h in mix.get("hotspots", ())]
        for h in self.hotspots:
            n = max(1, math.ceil(pool_n * h["peak_fraction"]))
            h["pool"] = self._hotspot_points(rng, h, n)
        self.hot_terms = [dict(h) for h in mix.get("hot_terms", ())]
        for h in self.hot_terms:
            n = max(1, math.ceil(pool_n * h["peak_fraction"]))
            h["offsets"] = rng.normal(0.0, h["radius"], size=(n, 2))
            h["tagged"] = rng.random(n) < h["term_prob"]
        k = int(mix.get("tuple_terms", 0))
        if k:
            self.term_pool = self.zipf_terms(rng, (pool_n, k))
        self.query_rng = rng
        self._layout: tuple[int, list] = (-1, [])

    # -- the copied generators -------------------------------------------
    def _mixture_points(self, rng, n: int) -> np.ndarray:
        idx = rng.choice(len(self.weights), size=n, p=self.weights)
        pts = self.centers[idx] + rng.normal(0.0, 1.0, size=(n, 2)) \
            * self.scales[idx, None]
        return np.clip(pts, 0.0, 0.999).astype(np.float32)

    @staticmethod
    def _hotspot_points(rng, h: dict, n: int) -> np.ndarray:
        cx, cy = h["corner"]
        side = h.get("side", HOTSPOT_SIDE)
        if h.get("spatial", "uniform") == "normal":
            var = 0.2 * side
            pts = rng.normal(0.0, var, size=(n, 2)) + np.array(
                [cx + side / 2, cy + side / 2])
            pts = np.clip(pts, [cx, cy], [cx + side, cy + side])
        else:
            pts = rng.uniform([cx, cy], [cx + side, cy + side], size=(n, 2))
        return pts.astype(np.float32)

    def zipf_terms(self, rng, shape) -> np.ndarray:
        """Zipf(``zipf_s``) vocabulary ids (``ScenarioSource._term_p``)."""
        ranks = np.arange(max(self.vocab, 1), dtype=np.float64)
        w = 1.0 / np.power(ranks + 1.0, float(self.mix.get("zipf_s", 1.05)))
        return rng.choice(self.vocab, size=shape,
                          p=w / w.sum()).astype(np.int64)

    # -- the timeline ------------------------------------------------------
    def hotspot_shares(self, tick: int) -> list[float]:
        ph = tick % self.period
        return [normal_profile(ph, h["start"], h["duration"],
                               h["peak_fraction"]) for h in self.hotspots]

    def hot_term_center(self, h: dict, tick: int) -> np.ndarray:
        ph = tick % self.period
        t = min(max((ph - h["start"]) / max(h["duration"] - 1, 1), 0.0), 1.0)
        (x0, y0), (x1, y1) = h["path"]
        return np.array([x0 + t * (x1 - x0), y0 + t * (y1 - y0)])

    def points(self, tick: int, n: int | None = None) -> np.ndarray:
        """The raw points of tick ``tick``: a pure function of the tick."""
        n = self.batch if n is None else int(n)
        fracs = self.hotspot_shares(tick)
        total = min(sum(fracs), 0.95)
        counts = [int(n * f / max(sum(fracs), 1e-9) * total) for f in fracs]
        parts = [_cyclic(self.background, tick * n, n - sum(counts))]
        for h, c in zip(self.hotspots, counts):
            if c > 0:
                parts.append(_cyclic(h["pool"], tick * c, c))
        pts = np.concatenate(parts) if len(parts) > 1 else parts[0].copy()
        # hot terms drag a share of the batch to their travelling focus
        layout, off = [], 0
        ph = tick % self.period
        for h in self.hot_terms:
            c = int(n * normal_profile(ph, h["start"], h["duration"],
                                       h["peak_fraction"]))
            if c <= 0:
                continue
            rows = _cyclic(h["offsets"], tick * c, c) \
                + self.hot_term_center(h, tick)
            pts[off:off + c] = np.clip(rows, 0.0, 0.999)
            layout.append((h, off, c))
            off += c
        self._layout = (tick, layout)
        return self._ordered(pts, tick)

    def _ordered(self, rows: np.ndarray, tick: int) -> np.ndarray:
        """A tick's rows in the seed's order under a ``pool_seed``:
        rotated by an offset drawn from the seed and stepped by the
        tick.  Without a ``pool_seed``, the rows as drawn."""
        if self.order_rng is None:
            return rows
        return np.roll(rows, -((self.shift + 7919 * tick) % len(rows)), 0)

    def terms(self, tick: int, n: int | None = None) -> np.ndarray:
        """(n, tuple_terms) raw term ids of tick ``tick``; redirected
        rows carry their hot term in slot 0 where the pool tags them."""
        n = self.batch if n is None else int(n)
        out = _cyclic(self.term_pool, tick * n, n).copy()
        if self._layout[0] != tick:
            self.points(tick, n)
        for h, off, c in self._layout[1]:
            tag = _cyclic(h["tagged"], tick * c, c)
            out[off:off + c, 0] = np.where(tag, h["term"],
                                           out[off:off + c, 0])
        return self._ordered(out, tick)

    def queries(self, n: int, side: float) -> np.ndarray:
        """(n, 4) standing range rectangles whose focal points follow
        the background mixture (``rects_around``)."""
        foci = self._mixture_points(self.query_rng, n)
        half = side / 2
        rects = np.clip(np.concatenate([foci - half, foci + half], 1),
                        0.0, 0.999).astype(np.float32)
        if self.order_rng is None:
            return rects
        self._qperm = self.order_rng.permutation(n)
        return rects[self._qperm]

    def subscription_terms(self, n: int, k: int) -> np.ndarray:
        terms = self.zipf_terms(self.query_rng, (n, k))
        # each subscription keeps its terms through the seed's order
        return terms if self._qperm is None else terms[self._qperm]

    # -- the engine's source protocol ------------------------------------
    def sample_points(self, n: int, tick: int) -> np.ndarray:
        return self.points(tick, n)

    def sample_terms(self, xy, tick: int, k: int) -> np.ndarray:
        return self.terms(tick, len(xy))[:, :k]

    def query_arrivals(self, tick: int) -> np.ndarray:
        return np.zeros((0, 4), np.float32)

    def next_query_arrival(self, tick: int) -> None:
        return None
