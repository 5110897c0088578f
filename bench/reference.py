"""The plain reference, and the comparison that decides ``correct``.

The reference imports nothing of the program.  From the benchmark's own
events (``traffic.generator``), standing queries and the configuration's
cost model it recomputes, tick by tick in float64, what the timed path
reports: tuples injected, per-machine work units and utilization, and
for pub/sub the expected deliveries; and, per collector drain, the N'
row and column collectors the device window accumulated; and, per
sampled round, the Algorithm-2 close of the statistics banks.

The balancer's plan (partition boxes and owners after each round) is
the system's decision, not an answer: the reference takes it as given,
checks that it covers the grid exactly once with live partitions, and
derives everything else from it itself, including the resident-query
counts per partition and the install work each transfer bills.

``contract`` is the one place where the reference multiplies and sums
(``numpy.einsum`` in float64), and ``rounding`` says how the
per-partition work and delivery terms are stored (exactly here).  The
control swaps in the same contractions in float32 at a lower matmul
precision on the device and bfloat16 terms; see
``bench/tests/test_control.py``.
"""
from __future__ import annotations

import numpy as np

# statistics bank channels (paper §4.2, Algorithm 2)
N, Q, R, SPANQ, PRESPANQ, C_N, C_Q, C_SPAN = range(8)


def contract64(subscripts: str, *ops) -> np.ndarray:
    return np.einsum(subscripts, *(np.asarray(o, np.float64) for o in ops))


def to_cells(xy: np.ndarray, g: int):
    """(row, col) of points in [0, 1)²: x is the column, y the row."""
    col = np.clip((xy[:, 0] * g).astype(np.int64), 0, g - 1)
    row = np.clip((xy[:, 1] * g).astype(np.int64), 0, g - 1)
    return row, col


def rect_cells(rects: np.ndarray, g: int):
    """Inclusive cell bounds (r0, c0, r1, c1) of (x0, y0, x1, y1) rects."""
    c0 = np.clip((rects[:, 0] * g).astype(np.int64), 0, g - 1)
    r0 = np.clip((rects[:, 1] * g).astype(np.int64), 0, g - 1)
    c1 = np.maximum(np.clip((rects[:, 2] * g).astype(np.int64), 0, g - 1), c0)
    r1 = np.maximum(np.clip((rects[:, 3] * g).astype(np.int64), 0, g - 1), r0)
    return r0, c0, r1, c1


def term_buckets(terms: np.ndarray, n_buckets: int) -> np.ndarray:
    """Term id → bucket by the configuration's 32-bit xorshift-multiply
    hash (``bucket = mix32(term) mod T``)."""
    x = np.asarray(terms, np.int64) & 0xFFFFFFFF
    x = ((x ^ (x >> 16)) * 0x45D9F3B) & 0xFFFFFFFF
    x = ((x ^ (x >> 16)) * 0x45D9F3B) & 0xFFFFFFFF
    x = x ^ (x >> 16)
    return x % n_buckets


class Plan:
    """One plan: live partition ids with their cell boxes and owners."""

    def __init__(self, pids, boxes, owners, transfers=()):
        self.pids = np.asarray(pids, np.int64)
        self.boxes = np.asarray(boxes, np.int64).reshape(-1, 4)
        self.owners = np.asarray(owners, np.int64)
        self.transfers = tuple(transfers)   # ((m_l, new_pids), ...)


class Reference:
    """Float64 replay of the engine's tick semantics under given plans."""

    def __init__(self, dep: dict, cost: dict, rects: np.ndarray,
                 sub_terms: np.ndarray | None = None, contract=contract64,
                 rounding=None):
        self.g = int(dep["grid_size"])
        self.m = int(dep["machines"])
        self.batch = int(dep["batch"])
        self.cap = float(dep["cap_units"])
        self.mem_queries = int(dep["mem_queries"])
        self.cost = cost
        self.contract = contract
        # how the per-partition work and delivery terms are stored:
        # exactly (float64) in the reference, rounded in the control
        self.rounding = rounding or (lambda a: a)
        self.rc = rect_cells(np.asarray(rects, np.float32), self.g)
        self.t = int(dep.get("term_buckets", 0))
        self.pivot = None
        if sub_terms is not None:
            b = term_buckets(sub_terms, self.t)
            self.pivot = b.min(axis=1) if b.shape[1] else \
                np.full(len(b), self.t)
        self.box = {}                       # pid -> (r0, c0, r1, c1)
        self.qres = {}                      # pid -> resident queries
        self.qres_kw = {}                   # pid -> (T + 1,) pivot counts
        self.qu = np.zeros(self.m)
        self.qt = np.zeros(self.m)
        self.lam = float(self.batch)
        self.cover_errors = 0

    # -- plan ---------------------------------------------------------------
    def _resident(self, box):
        r0, c0, r1, c1 = box
        q0, qc0, q1, qc1 = self.rc
        hit = (q0 <= r1) & (q1 >= r0) & (qc0 <= c1) & (qc1 >= c0)
        kw = None
        if self.pivot is not None:
            kw = np.bincount(self.pivot[hit], minlength=self.t + 1
                             ).astype(np.float64)
        return int(hit.sum()), kw

    def set_plan(self, plan: Plan) -> np.ndarray:
        """Adopt ``plan``; returns the install work each machine is
        billed for the queries its transfers moved to it."""
        g = self.g
        cell = np.full((g, g), -1, np.int64)
        hits = np.zeros((g, g), np.int64)
        for pid, box in zip(plan.pids, plan.boxes):
            r0, c0, r1, c1 = box
            cell[r0:r1 + 1, c0:c1 + 1] = pid
            hits[r0:r1 + 1, c0:c1 + 1] += 1
            key = tuple(int(v) for v in box)
            if self.box.get(int(pid)) != key:
                self.box[int(pid)] = key
                self.qres[int(pid)], self.qres_kw[int(pid)] = \
                    self._resident(key)
        self.cover_errors += int((hits != 1).sum())
        self.owner_of = dict(zip(plan.pids.tolist(), plan.owners.tolist()))
        self.cell_pid = cell
        self.pids = plan.pids
        self.pid_row = {int(p): i for i, p in enumerate(plan.pids)}
        self.cell_idx = np.vectorize(self.pid_row.get)(cell).reshape(-1)
        owner_cell = plan.owners[self.cell_idx]
        self.owner_onehot = (owner_cell[:, None]
                             == np.arange(self.m)[None, :]).astype(np.float64)
        qres = np.array([self.qres[int(p)] for p in plan.pids], np.float64)
        q_machine = np.bincount(plan.owners, weights=qres, minlength=self.m)
        # an executor past its resident-query memory stops all injection
        self.mem_wall = bool(q_machine.max() > self.mem_queries)
        c = self.cost
        area = ((plan.boxes[:, 2] - plan.boxes[:, 0] + 1)
                * (plan.boxes[:, 3] - plan.boxes[:, 1] + 1)) / (g * g)
        cov = np.minimum(c["query_area"] / np.maximum(area, 1e-12), 1.0)
        q = q_machine[plan.owners]
        probe = c["kappa_probe"] * np.log2(1.0 + q) * (
            1.0 + np.maximum(0.0, (q - c["q_cache"]) / c["q_cache"]))
        self.base = c["c0"] + probe + c["store_cost"]
        self.cov = cov
        if self.pivot is None:
            self.base = self.base + c["match_factor"] * c["kappa_match"] \
                * qres * cov
        else:
            self.kw_pid = np.stack([self.qres_kw[int(p)] for p in plan.pids])
        self.base = self.rounding(self.base)
        install = np.zeros(self.m)
        for m_l, new_pids in plan.transfers:
            moved = sum(self.qres[int(p)] for p in new_pids
                        if self.owner_of.get(int(p)) == m_l)
            install[m_l] += moved * c["migration_unit_cost"]
        return install

    # -- one tick -------------------------------------------------------------
    def tick(self, xy: np.ndarray, terms: np.ndarray | None = None) -> dict:
        """Route, price and process one tick's batch; returns the tick's
        outputs and its (G, G) cell histogram."""
        g = self.g
        n = 0 if self.mem_wall else int(np.floor(min(self.batch, self.lam)))
        row, col = to_cells(xy[:n], g)
        flat = row * g + col
        hist = np.bincount(flat, minlength=g * g).astype(np.float64)
        ctr = self.contract
        cell_base = self.base[self.cell_idx]
        du = ctr("c,cm->m", hist, cell_base[:, None] * self.owner_onehot)
        dt = ctr("c,cm->m", hist, self.owner_onehot)
        dels = 0.0
        if terms is not None:
            t1 = self.t + 1
            b = np.sort(term_buckets(terms[:n], self.t), axis=1)
            keep = np.ones(b.shape, bool)
            keep[:, 1:] = b[:, 1:] != b[:, :-1]
            ids = np.concatenate([b, np.full((n, 1), self.t)], 1)
            keep = np.concatenate([keep, np.ones((n, 1), bool)], 1)
            cells = np.repeat(flat, ids.shape[1])[keep.reshape(-1)]
            kwh = np.bincount(cells * t1 + ids.reshape(-1)[keep.reshape(-1)],
                              minlength=g * g * t1).reshape(g * g, t1)
            dens = self.rounding(self.kw_pid[self.cell_idx]
                                 * self.cov[self.cell_idx][:, None])
            c = self.cost
            unit = c["match_factor"] * c["kappa_match"] + c["delivery_cost"]
            du = du + unit * ctr("cb,cbm->m", kwh,
                                 dens[:, :, None] * self.owner_onehot[:, None])
            dels = float(ctr("cb,cb->", kwh, dens))
        return self._process(du, dt, n, dels, hist.reshape(g, g))

    def _process(self, du, dt, n, dels, hist2d) -> dict:
        """The engine's tick dynamics (process, latency, backpressure)."""
        c = self.cost
        self.qu += du
        self.qt += dt
        cap = np.full(self.m, self.cap)
        pu = np.minimum(self.qu, cap)
        avg = np.where(self.qt > 0, self.qu / np.maximum(self.qt, 1e-9), 1.0)
        pt = np.minimum(pu / np.maximum(avg, 1e-9), self.qt)
        self.qu -= pt * avg
        self.qt -= pt
        delay = self.qu / cap + avg / cap
        w = float(pt.sum())
        lat = float((delay * pt).sum() / w) if w > 0 else 0.0
        if (self.qu > c["bp_high"] * self.cap).any():
            self.lam = max(self.lam * c["bp_dec"], 1.0)
        else:
            self.lam = min(self.lam + c["bp_inc"] * self.batch, self.batch)
        return {"injected": n, "throughput": w, "latency": lat,
                "utilization": pu / self.cap, "deliveries": dels,
                "hist": hist2d}

    def collectors(self, hist2d: np.ndarray, n_rows: int):
        """N' row and column collectors of a drain interval's summed cell
        histogram under the current plan, as (n_rows, G + 1) banks."""
        g = self.g
        onehot = (self.cell_pid.reshape(g, g, 1)
                  == np.arange(n_rows)[None, None, :]).astype(np.float64)
        rows = np.zeros((n_rows, g + 1))
        cols = np.zeros((n_rows, g + 1))
        rows[:, :g] = self.contract("rc,rcp->pr", hist2d, onehot)
        cols[:, :g] = self.contract("rc,rcp->pc", hist2d, onehot)
        return rows, cols

    def close(self, bank: np.ndarray, decay: float) -> np.ndarray:
        """Algorithm 2 on one (NUM_CH, P, G + 1) bank, float64, rounded
        once to float32 as the bank stores it."""
        g1 = bank.shape[-1]
        tri = np.triu(np.ones((g1, g1)))
        cum = lambda ch: self.contract("pk,kj->pj", bank[ch], tri)
        out = np.array(bank, np.float64)
        cn, cq, cs = cum(C_N), cum(C_Q), cum(C_SPAN)
        out[N] = bank[N] * decay + cn
        out[Q] = bank[Q] + cq
        out[R] = cn + cq
        out[SPANQ] = bank[SPANQ] + cs
        out[PRESPANQ] = cs
        out[C_N] = out[C_Q] = out[C_SPAN] = 0.0
        return out.astype(np.float32)


def flush(bank: np.ndarray) -> np.ndarray:
    """A float32 bank as the TPU keeps it: with no subnormals.  Decay
    halves a partition's counts every round, and after some 130 rounds
    without events they pass below float32's least normal number, where
    the chip flushes them to zero and NumPy does not."""
    b = np.asarray(bank, np.float32)
    return np.where(np.abs(b) < np.finfo(np.float32).tiny, 0.0, b)


def max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), 1e-30)
    return float(np.max(np.abs(got - want) / scale, initial=0.0))


def simulate(run: dict, ref: Reference) -> dict:
    """Run the reference over every tick the run handed the engine.

    ``run`` holds what the harness recorded: ``ticks`` (handed),
    ``points(t)`` and ``terms(t)`` (the events, ``terms`` None for pure
    spatial runs), ``plans`` ({effective tick: Plan}), ``drains`` ([(bound
    tick, rows, cols)]: the N' banks drained for the ticks before the
    bound) and ``closes`` ([(round tick, decay, rows before, cols before,
    rows after, cols after)]).  Returns the reference's answers in the
    same shapes: per-tick ``injected``, ``utilization`` and
    ``deliveries``, the collectors at each drain bound, and each sampled
    close applied to the banks it was given."""
    keyword = run.get("terms") is not None
    # the run's end is a bound too: every tuple's collector update must
    # have been drained by then
    bounds = sorted({d[0] for d in run["drains"]} | {run["ticks"]})
    acc = np.zeros((ref.g, ref.g))
    n_rows = max([d[1].shape[0] for d in run["drains"]]
                 + [int(p.pids.max()) + 1 for p in run["plans"].values()])
    out = {"injected": [], "utilization": [], "deliveries": [],
           "drains": {}, "closes": {}}

    def drain_upto(t):
        nonlocal acc
        while bounds and bounds[0] <= t:
            out["drains"][bounds.pop(0)] = ref.collectors(acc, n_rows)
            acc = np.zeros_like(acc)

    for t in range(run["ticks"]):
        drain_upto(t)
        if t in run["plans"]:
            ref.qu += ref.set_plan(run["plans"][t])
        res = ref.tick(run["points"](t), run["terms"](t) if keyword else None)
        acc += res["hist"]
        for key in ("injected", "utilization", "deliveries"):
            out[key].append(res[key])
    drain_upto(run["ticks"])
    for tick, decay, b_rows, b_cols, _, _ in run.get("closes", ()):
        out["closes"][tick] = (ref.close(b_rows, decay),
                               ref.close(b_cols, decay))
    out["cover_errors"] = ref.cover_errors
    return out


def compare(run: dict, want: dict) -> dict:
    """The compared numbers: the run's answers against the reference's
    (``simulate``), per tick over ``run["window_ticks"]``, per drain and
    per sampled close."""
    got = run["outputs"]
    lo, hi = run["window_ticks"]
    inj = sum(int(got["injected"][t] != want["injected"][t])
              for t in range(lo, hi))
    util = max((max_rel_err(got["utilization"][t], want["utilization"][t])
                for t in range(lo, hi)), default=0.0)
    coll = 0.0
    drained = {b: (rows, cols) for b, rows, cols in run["drains"]}
    for bound, (w_rows, w_cols) in want["drains"].items():
        zero = np.zeros((0, w_rows.shape[1]))
        rows, cols = drained.get(bound, (zero, zero))
        p = rows.shape[0]
        coll = max(coll, float(np.abs(rows - w_rows[:p]).max(initial=0)),
                   float(np.abs(cols - w_cols[:p]).max(initial=0)),
                   float(np.abs(w_rows[p:]).max(initial=0)),
                   float(np.abs(w_cols[p:]).max(initial=0)))
    close = 0.0
    for tick, _, _, _, a_rows, a_cols in run.get("closes", ()):
        w_rows, w_cols = want["closes"][tick]
        close = max(close, float(np.abs(flush(a_rows[:5])
                                        - flush(w_rows[:5])).max()),
                    float(np.abs(flush(a_cols[:5])
                                 - flush(w_cols[:5])).max()))
    numbers = {"injected_ticks_off": float(inj),
               "collectors_max_diff": coll, "close_max_diff": close,
               "plan_cover_errors": float(want["cover_errors"]),
               "utilization_rel_err": util}
    if run.get("terms") is not None:
        numbers["deliveries_rel_err"] = max(
            (max_rel_err(got["deliveries"][t], want["deliveries"][t])
             for t in range(lo, hi)), default=0.0)
    return numbers


def as_run(run: dict, answers: dict) -> dict:
    """``run`` with its answers replaced by ``answers`` (a ``simulate``
    result): the control puts a lower-precision reference in the
    program's place this way."""
    out = dict(run)
    out["outputs"] = {k: answers[k] for k in
                      ("injected", "utilization", "deliveries")}
    out["drains"] = [(b, *d) for b, d in answers["drains"].items()]
    out["closes"] = [(t, d, br, bc, *answers["closes"][t])
                     for t, d, br, bc, _, _ in run.get("closes", ())]
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct iff none exceeds."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()
              if k in limits}
    ok = len(checks) == len(numbers) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
