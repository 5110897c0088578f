"""Multi-device sharded data plane: one simulated machine per device
shard.

The single-device :class:`~repro.streaming.planes.JaxPlane` simulates
all M machines inside one ``DeviceState`` on one device — "throughput"
can never scale past one chip, and a planner transfer is just a scatter
patch.  :class:`ShardedJaxPlane` maps the machine axis onto a real
device mesh (``launch.mesh.streaming_mesh``, a 1-D ``("machines",)``
mesh) so the simulation is physically distributed:

* **State layout.**  Small plan state (the cell→partition ``grid``, the
  ``owner`` table, ``qres``/``area_frac``/``q_machine``, the keyword
  pivot ``qres_kw``) is replicated — it is the routing table every
  ingest worker needs.  Partition-indexed *work* state is sharded: each
  device holds a ``(S, G+1)`` slot bank of N′ collectors for exactly
  the partitions whose owner machine is homed on it (``home[m] =
  m·D//M`` maps machines to contiguous device blocks), plus the
  ``slot_pid`` slot→partition map for its block.
* **Per-tick routing = owner-keyed ``all_to_all``.**  Each device
  ingests its 1/D share of every staged batch (contiguous chunk = one
  ingest worker) and bincounts it into a per-cell histogram.  Inside
  ``shard_map`` the histogram is masked by the destination device of
  each cell's owner machine and exchanged with one
  ``lax.all_to_all`` — after which every device holds exactly the
  counts of *its* partitions' cells.  Integer counts in float32 are
  exact, and summing the D worker histograms reproduces the global
  per-tick bincount bit-for-bit, so the fused window stays
  metrics-identical to the single-device plane (same scan dynamics,
  same backpressure replay contract, same membership scatter patches).
* **Transfers = real cross-device resharding.**
  :meth:`ShardedJaxPlane.reshard_transfers` physically moves each
  applied transfer's payload (64 B/query rows + the store payload)
  from the sender's device to the receiver's device with
  ``device_put``; the bytes moved equal the billed
  ``RoundOutcome.migration_bytes`` (regression-tested), so the cost
  model and the physical bytes agree.

Runs on CPU via forced host devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=4

(set it before jax initializes — ``launch.mesh.force_host_device_count``
is the sanctioned helper).  ``tests/test_sharded.py`` holds the parity
suite; ``benchmarks/engine_throughput.py --devices`` the scaling sweep.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

from .fused import (DeviceState, EngineCarry, FusedHostState, FusedOutputs,
                    window_histograms)
from .planes import (CostParams, JaxPlane, _pad64, _pad_pow2, _tracer,
                     probe_term)

# wire format of one re-homed resident query: 16 float32 fields
# (rect, terms digest, counters) = 64 B — matches the cost model's
# BYTES_PER_QUERY billing constant (streaming.baselines)
QUERY_ROW_FLOATS = 16
BYTES_PER_QUERY = 4 * QUERY_ROW_FLOATS


class ShardedState(NamedTuple):
    """Device-resident fused state, machine axis sharded over a mesh.

    The first five fields mirror :class:`~repro.streaming.fused.
    DeviceState` (and keep its names, so ``FusedHostState.diff`` →
    ``scatter_update`` patches apply unchanged); they are replicated.
    The collector banks are *slot-sharded*: ``cn_rows``/``cn_cols`` are
    (D, S, G+1) with the leading axis on the mesh, ``slot_pid`` (D, S)
    maps each device-local slot to its partition id (−1 = empty), and
    ``pid_slot`` (P,) is the replicated inverse (slot on the owning
    device).  ``home`` (M,) maps machines to devices."""

    grid: object
    owner: object
    qres: object
    area_frac: object
    q_machine: object
    cn_rows: object
    cn_cols: object
    qres_kw: object = None
    slot_pid: object = None
    pid_slot: object = None
    home: object = None


def machine_homes(num_machines: int, devices: int) -> np.ndarray:
    """Machine→device map: contiguous blocks, ``home[m] = m·D//M``."""
    return (np.arange(num_machines, dtype=np.int64)
            * devices // max(num_machines, 1)).astype(np.int32)


def assign_slots(owner: np.ndarray, home: np.ndarray, devices: int):
    """Pack every partition id into a per-device slot bank.

    Returns ``(slot_pid (D, S) int32, pid_slot (P,) int32, S)`` with S
    the 64-padded max per-device occupancy (shared bucket → one compile
    per bank size).  All capacity rows get slots — unallocated ids have
    zero ``qres``/counts, so pricing them is exact and the bank size
    tracks the capacity bank like the single-device plane's.
    """
    owner = np.asarray(owner, np.int64)
    dev = home[np.clip(owner, 0, len(home) - 1)].astype(np.int64)
    counts = np.bincount(dev, minlength=devices)
    s = _pad64(max(int(counts.max()), 1))
    order = np.argsort(dev, kind="stable")
    start = np.zeros(devices, np.int64)
    start[1:] = np.cumsum(counts)[:-1]
    rank = np.arange(len(owner), dtype=np.int64) - start[dev[order]]
    slot_pid = np.full((devices, s), -1, np.int32)
    slot_pid[dev[order], rank] = order.astype(np.int32)
    pid_slot = np.empty(len(owner), np.int32)
    pid_slot[order] = rank.astype(np.int32)
    return slot_pid, pid_slot, int(s)


class ShardedJaxPlane(JaxPlane):
    """JAX data plane with the machine axis sharded over a device mesh.

    Stateless per-call math (routing, cost terms, round close) is
    inherited unchanged from :class:`JaxPlane` — only the
    device-resident fused contract is re-implemented for the mesh.
    ``devices=None`` uses every visible device."""

    name = "sharded"
    wants_cells = True

    def __init__(self, devices: int | None = None):
        super().__init__()
        from ..launch.mesh import streaming_mesh
        jax = self._jax
        from jax.sharding import NamedSharding, PartitionSpec
        self._mesh = streaming_mesh(devices)
        self._d = int(self._mesh.devices.size)
        self._Pspec = PartitionSpec
        self._shard = NamedSharding(self._mesh, PartitionSpec("machines"))
        self._repl = NamedSharding(self._mesh, PartitionSpec())
        self._swindow_cache: dict = {}
        # chained-window upload caches: the carry the engine hands back
        # is usually the one we just returned, and alive changes only at
        # membership events — skip the replicated re-uploads (one
        # device_put here fans out to every mesh device)
        self._carry_cache: tuple | None = None
        self._alive_cache: dict = {}
        # cumulative bytes physically moved by reshard_transfers —
        # tests compare this against the billed migration bytes
        self.reshard_bytes_total = 0
        self.last_reshard_bytes = 0

    @property
    def devices(self) -> int:
        return self._d

    # -- state layout --------------------------------------------------------
    def _put_r(self, a, dt):
        return self._jax.device_put(np.asarray(a, dt), self._repl)

    def make_state(self, host: FusedHostState) -> ShardedState:
        jax = self._jax
        g1 = host.grid.shape[0] + 1
        home = machine_homes(len(host.q_machine), self._d)
        slot_pid, pid_slot, s = assign_slots(np.asarray(host.owner), home,
                                             self._d)
        z = lambda: jax.device_put(  # noqa: E731
            np.zeros((self._d, s, g1), np.float32), self._shard)
        qkw = (None if host.qres_kw is None
               else self._put_r(host.qres_kw, np.float32))
        return ShardedState(
            self._put_r(host.grid, np.int32),
            self._put_r(host.owner, np.int32),
            self._put_r(host.qres, np.float32),
            self._put_r(host.area_frac, np.float32),
            self._put_r(host.q_machine, np.float32),
            z(), z(), qkw,
            jax.device_put(slot_pid, self._shard),
            self._put_r(pid_slot, np.int32),
            self._put_r(home, np.int32))

    def scatter_update(self, state: ShardedState, updates) -> ShardedState:
        state = super().scatter_update(state, updates)
        if "owner" in updates:
            # ownership changed (rebalance transfer, recovery re-homing,
            # split allocating new pids): partitions may have moved to a
            # different device block — recompute the slot layout
            state = self._resync_slots(state)
        return state

    def _resync_slots(self, state: ShardedState) -> ShardedState:
        jax = self._jax
        owner = np.asarray(state.owner)
        home = np.asarray(state.home)
        slot_pid, pid_slot, s = assign_slots(owner, home, self._d)
        old = np.asarray(state.slot_pid)
        if s == old.shape[1] and np.array_equal(slot_pid, old):
            return state
        # re-home the banks through partition order.  The engine drains
        # the collectors before any plan change reaches us, so in
        # practice these are zeros — but moving the contents keeps the
        # operation exact for any caller.
        cnr, cnc = self.collector_banks(state)
        g1 = cnr.shape[1]
        nr = np.zeros((self._d, s, g1), np.float32)
        nc = np.zeros((self._d, s, g1), np.float32)
        valid = slot_pid >= 0
        nr[valid] = cnr[slot_pid[valid]]
        nc[valid] = cnc[slot_pid[valid]]
        return state._replace(
            slot_pid=jax.device_put(slot_pid, self._shard),
            pid_slot=self._put_r(pid_slot, np.int32),
            cn_rows=jax.device_put(nr, self._shard),
            cn_cols=jax.device_put(nc, self._shard))

    def reset_collectors(self, state: ShardedState) -> ShardedState:
        jax = self._jax
        z = np.zeros(state.cn_rows.shape, np.float32)
        return state._replace(cn_rows=jax.device_put(z, self._shard),
                              cn_cols=jax.device_put(z, self._shard))

    def collector_banks(self, state: ShardedState):
        """Unscatter the per-device slot banks into partition order
        (P, G+1) for ``Swarm.absorb_collectors``."""
        sp = np.asarray(state.slot_pid)
        cnr = np.asarray(state.cn_rows)
        cnc = np.asarray(state.cn_cols)
        p = int(state.owner.shape[0])
        out_r = np.zeros((p, cnr.shape[-1]), np.float32)
        out_c = np.zeros((p, cnc.shape[-1]), np.float32)
        valid = sp >= 0
        out_r[sp[valid]] = cnr[valid]
        out_c[sp[valid]] = cnc[valid]
        return out_r, out_c

    # -- single-tick path (tests/tools; the engine boundary ticks route
    #    through the router's per-call API, not plane.step) ------------------
    def step(self, state: ShardedState, cp: CostParams, xy,
             track_stats: bool = False, query_batch=None, kw=None):
        tmp = DeviceState(state.grid, state.owner, state.qres,
                          state.area_frac, state.q_machine,
                          self._jnp.zeros((state.owner.shape[0],
                                           state.grid.shape[0] + 1),
                                          self._jnp.float32),
                          self._jnp.zeros((state.owner.shape[0],
                                           state.grid.shape[0] + 1),
                                          self._jnp.float32),
                          state.qres_kw)
        tmp, out = super().step(tmp, cp, xy, track_stats, query_batch, kw)
        if track_stats:
            # fold the single-device collector delta into the owning
            # devices' slot banks
            sp = np.asarray(state.slot_pid)
            dr = np.asarray(tmp.cn_rows)
            dc = np.asarray(tmp.cn_cols)
            cnr = np.array(np.asarray(state.cn_rows))
            cnc = np.array(np.asarray(state.cn_cols))
            valid = sp >= 0
            cnr[valid] += dr[sp[valid]]
            cnc[valid] += dc[sp[valid]]
            state = state._replace(
                cn_rows=self._jax.device_put(cnr, self._shard),
                cn_cols=self._jax.device_put(cnc, self._shard))
        return state, out

    # -- fused window --------------------------------------------------------
    def _sharded_window(self, state, carry, hists, kwh, sc, ep, alive, *,
                        track_stats: bool, tuple_driven: bool,
                        keyword: bool, batch: int):
        """The fused window under ``shard_map``: per-shard ingest
        histograms → owner-keyed ``all_to_all`` → slot-bank matmuls →
        ``psum`` of the (W, M) aggregates → the replicated engine scan.

        The only cross-device traffic per window is the histogram
        exchange and the two (W, M) psums; the scan runs replicated on
        psum'd aggregates, so the carry/metrics are bit-identical on
        every shard (and to the single-device plane: summing the D
        ingest-worker histograms reproduces the global bincount exactly,
        and the per-machine unit/tuple aggregates are the same sums in
        a different association — integer counts stay exact, float
        units agree to reduction order)."""
        jax, jnp = self._jax, self._jnp
        lax = jax.lax
        d = self._d
        P = self._Pspec
        g = state.grid.shape[0]
        m = alive.shape[0]
        hp = lax.Precision.HIGHEST

        def inner(cnr, cnc, sp, hl, kwh, grid, owner, qres, area_frac,
                  q_machine, qres_kw, home, carry, sc, ep, alive):
            # scalars enter as explicit replicated args — closing over
            # outer-jit tracers inside shard_map is off-limits
            cap_units, lambda_max, bp_high, bp_dec, bp_inc, n_ticks = ep
            cnr, cnc, sp, hl = cnr[0], cnc[0], sp[0], hl[0]
            s = sp.shape[0]
            grid_f = grid.reshape(-1)
            # destination device of every cell = home of its owner
            dev_cell = home[owner[grid_f]]
            # owner-keyed exchange: each shard sends the slice of its
            # ingest histogram destined for device k to device k; after
            # the all_to_all every device holds the full counts of its
            # own partitions' cells (and only those)
            by_dest = jnp.where(
                dev_cell[None, None, :] == jnp.arange(d)[:, None, None],
                hl[None], 0.0)
            mine = lax.all_to_all(by_dest, "machines", 0, 0).sum(0)
            mm = functools.partial(jnp.matmul, precision=hp)
            cell_slot = (grid_f[:, None] == sp[None, :]).astype(jnp.float32)
            count_ws = mm(mine, cell_slot)           # exact int counts
            owner_s = owner[sp]
            own_sm = (owner_s[:, None]
                      == jnp.arange(m)[None, :]).astype(jnp.float32)
            if keyword:
                (c0, kappa_probe, kappa_match, q_cache, query_area, mf,
                 store_cost, delivery_cost) = sc
                q = q_machine[owner_s].astype(jnp.float32)
                base_s = c0 + probe_term(jnp, q, kappa_probe, q_cache) \
                    + store_cost
                cov_s = jnp.minimum(
                    query_area
                    / jnp.maximum(area_frac[sp], 1e-12), 1.0)
                t1 = qres_kw.shape[1]
                kw3 = kwh[0].reshape(kwh.shape[1], g * g, t1)
                by_kw = jnp.where(
                    dev_cell[None, None, :, None]
                    == jnp.arange(d)[:, None, None, None], kw3[None], 0.0)
                mine_kw = lax.all_to_all(by_kw, "machines", 0, 0).sum(0)
                cnt_wsb = jnp.einsum("wcb,cs->wsb", mine_kw, cell_slot,
                                     precision=hp)
                del_ws = ((cnt_wsb * qres_kw[sp][None]).sum(-1)
                          * cov_s[None, :])
                units_wm = lax.psum(
                    mm(count_ws, base_s[:, None] * own_sm)
                    + (mf * kappa_match + delivery_cost)
                    * mm(del_ws, own_sm), "machines")
                dels_w = lax.psum(del_ws.sum(1), "machines")
            else:
                cost_s = self._cost_body(s, sp, owner_s, qres, q_machine,
                                         area_frac, *sc,
                                         tuple_driven=tuple_driven)
                units_wm = lax.psum(mm(count_ws, cost_s[:, None] * own_sm),
                                    "machines")
                dels_w = jnp.zeros(hl.shape[0], jnp.float32)
            tuples_wm = lax.psum(mm(count_ws, own_sm), "machines")
            cap = cap_units * alive
            ticks = jnp.arange(hl.shape[0])

            # the engine scan — verbatim the single-device plane's body,
            # replicated (all inputs are psum'd or replicated)
            def body(c, x):
                qu0, qt0, lam0 = c
                du, dt, i = x
                valid = i < n_ticks
                n = jnp.floor(jnp.minimum(lambda_max,
                                          lam0)).astype(jnp.int32)
                ok = (n >= batch) | ~valid
                qu = qu0 + du
                qt = qt0 + dt
                pu = jnp.minimum(qu, cap)
                avg = jnp.where(qt > 0, qu / jnp.maximum(qt, 1e-9), 1.0)
                pt = jnp.minimum(pu / jnp.maximum(avg, 1e-9), qt)
                qu = qu - pt * avg
                qt = qt - pt
                delay = jnp.where(cap > 0,
                                  qu / jnp.maximum(cap, 1e-9)
                                  + avg / jnp.maximum(cap, 1e-9), 0.0)
                w = pt.sum()
                latency = jnp.where(
                    w > 0, (delay * pt).sum() / jnp.maximum(w, 1e-9), 0.0)
                lam = jnp.where(
                    (qu > bp_high * cap_units).any(),
                    jnp.maximum(lam0 * bp_dec, 1.0),
                    jnp.minimum(lam0 + bp_inc * lambda_max, lambda_max))
                util = pu / jnp.maximum(cap_units, 1e-9)
                c = (jnp.where(valid, qu, qu0), jnp.where(valid, qt, qt0),
                     jnp.where(valid, lam, lam0))
                return c, (w, latency, util, n, ok)

            carry_out, (w_, lat, util, n_, ok) = lax.scan(
                body, carry, (units_wm, tuples_wm, ticks))
            dels_w = jnp.where(ticks < n_ticks, dels_w, 0.0)
            if track_stats:
                hist2d = mine.sum(0).reshape(g, g)
                oh3 = cell_slot.reshape(g, g, s)
                cnr = cnr.at[:, :g].add(jnp.einsum("rc,rcp->pr", hist2d,
                                                   oh3, precision=hp))
                cnc = cnc.at[:, :g].add(jnp.einsum("rc,rcp->pc", hist2d,
                                                   oh3, precision=hp))
            return (cnr[None], cnc[None], carry_out,
                    (w_, lat, util, n_, dels_w), ok.all())

        pm, pr = P("machines"), P()
        fn = jax.shard_map(
            inner, mesh=self._mesh,
            in_specs=(pm, pm, pm, pm, pm, pr, pr, pr, pr, pr, pr, pr,
                      pr, pr, pr, pr),
            out_specs=(pm, pm, pr, pr, pr))
        return fn(state.cn_rows, state.cn_cols, state.slot_pid, hists, kwh,
                  state.grid, state.owner, state.qres, state.area_frac,
                  state.q_machine, state.qres_kw, state.home, carry, sc,
                  ep, alive)

    def run_window(self, state: ShardedState, cp: CostParams, fp,
                   carry: EngineCarry, xy_stack, kw_stack=None, cells=None):
        jax, jnp = self._jax, self._jnp
        w, b = len(xy_stack), len(xy_stack[0])
        g = int(state.grid.shape[0])
        wp = _pad_pow2(w)
        keyword = kw_stack is not None
        t1 = int(state.qres_kw.shape[1]) if keyword else 0
        d, s = self._d, int(state.slot_pid.shape[1])
        # host ingest tier: one contiguous chunk = one ingest worker per
        # device; batches carrying precomputed cell ids skip the
        # point→cell pass entirely
        tr = _tracer()
        with tr.span("window_bin", ticks=w):
            hists, kwh = window_histograms(xy_stack, g, devices=d, wp=wp,
                                           cells=cells, kw_stack=kw_stack,
                                           t1=t1)
        key = (wp, b, int(state.owner.shape[0]), s, g, len(fp.alive),
               fp.track_stats, cp.tuple_driven, keyword, t1)
        fn = self._swindow_cache.get(key)
        compiling = fn is None
        if compiling:
            fn = functools.partial(
                jax.jit(self._sharded_window, static_argnames=(
                    "track_stats", "tuple_driven", "keyword", "batch")),
                track_stats=fp.track_stats, tuple_driven=cp.tuple_driven,
                keyword=keyword, batch=b)
            self._swindow_cache[key] = fn
        with tr.span("window_upload"):
            ep = tuple(self._sc(v) for v in (fp.cap_units, fp.lambda_max,
                                             fp.bp_high, fp.bp_dec,
                                             fp.bp_inc)
                       ) + (self._upload.get(np.int32(w)),)
            ck = (np.asarray(carry.queue_units, np.float64).tobytes(),
                  np.asarray(carry.queue_tuples, np.float64).tobytes(),
                  float(carry.lam_bp))
            if self._carry_cache is not None and self._carry_cache[0] == ck:
                carry_dev = self._carry_cache[1]
            else:
                carry_dev = (
                    self._put_r(np.asarray(carry.queue_units), np.float32),
                    self._put_r(np.asarray(carry.queue_tuples), np.float32),
                    jnp.float32(carry.lam_bp))
            hs = jax.device_put(hists, self._shard)
            kws = None if kwh is None else jax.device_put(kwh, self._shard)
            ak = np.asarray(fp.alive, np.float32).tobytes()
            alive = self._alive_cache.get(ak)
            if alive is None:
                if len(self._alive_cache) > 64:
                    self._alive_cache.clear()
                alive = self._alive_cache[ak] = self._put_r(fp.alive,
                                                            np.float32)
            args = (state, carry_dev, hs, kws, self._cost_scalars(cp), ep,
                    alive)
        if tr.enabled:
            name = ("sharded_window_compile" if compiling
                    else "sharded_window_dispatch")
            with tr.span(name, ticks=w, batch=b, plane="sharded",
                         devices=d):
                cnr, cnc, (qu, qt, lam_bp), outs, ok = fn(*args)
                jax.block_until_ready((cnr, cnc, qu, qt, outs, ok))
            # per-shard ingest tracks: tuples each device's worker
            # binned this window
            for k in range(d):
                tr.counter("shard_tuples", float(hists[k, :w].sum()),
                           machine=k)
        else:
            cnr, cnc, (qu, qt, lam_bp), outs, ok = fn(*args)
        state = state._replace(cn_rows=cnr, cn_cols=cnc)
        with tr.span("window_readback"):
            qu_h = np.asarray(qu, np.float64)
            qt_h = np.asarray(qt, np.float64)
            lam_h = float(lam_bp)
            self._carry_cache = ((qu_h.tobytes(), qt_h.tobytes(), lam_h),
                                 (qu, qt, lam_bp))
            return (state,
                    EngineCarry(qu_h, qt_h, lam_h),
                    FusedOutputs(np.asarray(outs[0], np.float64)[:w],
                                 np.asarray(outs[1], np.float64)[:w],
                                 np.asarray(outs[2], np.float64)[:w],
                                 np.asarray(outs[3], np.int64)[:w],
                                 (np.asarray(outs[4], np.float64)[:w]
                                  if keyword else None)),
                    bool(ok))

    # -- transfers as physical resharding ------------------------------------
    def reshard_transfers(self, state, outcome, router) -> int:
        """Move each applied transfer's payload sender-device →
        receiver-device and return the bytes that crossed.

        Payload per transfer = one (moved_queries, 16) float32 block of
        re-homed resident-query rows (64 B each, the wire format the
        cost model bills as ``BYTES_PER_QUERY``) plus — on the first
        transfer — the migrated store payload (the simulated store is a
        count sketch, so the buffer carries exactly the billed bytes).
        Total bytes moved therefore equal the billed
        ``RoundOutcome.migration_bytes``; ``tests/test_sharded.py``
        keeps that identity as a regression gate."""
        transfers = tuple(getattr(outcome, "transfers", ()) or ())
        if state is None or not transfers:
            self.last_reshard_bytes = 0
            return 0
        jax = self._jax
        devs = list(self._mesh.devices.reshape(-1))
        home = np.asarray(state.home)
        qres = np.asarray(state.qres)
        af = np.asarray(state.area_frac)
        moved_q = int(getattr(outcome, "moved_queries", 0) or 0)
        migration = int(getattr(outcome, "migration_bytes", 0) or 0)
        per_q = BYTES_PER_QUERY
        data_bytes = migration - per_q * moved_q
        if data_bytes < 0:      # router bills a different query size
            per_q, data_bytes = 0, migration
        moved_by = list(getattr(outcome, "moved_by_transfer", ()) or ())
        if len(moved_by) != len(transfers) or sum(moved_by) != moved_q:
            moved_by = [moved_q] + [0] * (len(transfers) - 1)
        tr = _tracer()
        total = 0
        for i, (rec, nq) in enumerate(zip(transfers, moved_by)):
            src = devs[int(home[rec.m_h]) % len(devs)]
            dst = devs[int(home[rec.m_l]) % len(devs)]
            payload = []
            if per_q and nq:
                rows = np.zeros((int(nq), QUERY_ROW_FLOATS), np.float32)
                # header rows carry the re-homed partitions' metadata
                # (pid, qres, area fraction) — real content, exact size
                pids = np.asarray(rec.new_pids, np.int64)[:int(nq)]
                rows[:len(pids), 0] = pids
                rows[:len(pids), 1] = qres[pids]
                rows[:len(pids), 2] = af[pids]
                payload.append(rows)
            if i == 0 and data_bytes:
                payload.append(np.zeros(int(data_bytes), np.uint8))
            moved = 0
            for buf in payload:
                x = jax.device_put(buf, src)
                y = jax.device_put(x, dst)
                y.block_until_ready()
                moved += y.nbytes
            total += moved
            if tr.enabled and moved:
                tr.counter("reshard_bytes", float(moved),
                           machine=int(rec.m_l))
        self.last_reshard_bytes = total
        self.reshard_bytes_total += total
        return total


@functools.lru_cache(maxsize=None)
def sharded_plane(devices: int | None = None) -> ShardedJaxPlane:
    """Shared plane instance per device count (planes are stateless
    apart from compile caches — sharing avoids recompiling per run;
    ``EngineConfig.devices`` resolves through here)."""
    return ShardedJaxPlane(devices)
