"""Render EXPERIMENTS.md tables from dry-run artifacts, the
mixed-workload query table from BENCH_queries.json, and the planner
decision timeline from flight-recorder traces.

Usage: PYTHONPATH=src python -m benchmarks.make_tables [baseline_dir] [final_dir]
       PYTHONPATH=src python -m benchmarks.make_tables --queries [BENCH_queries.json]
       PYTHONPATH=src python -m benchmarks.make_tables --decisions TRACE_DIR
       PYTHONPATH=src python -m benchmarks.make_tables --pubsub [BENCH_pubsub.json]
       PYTHONPATH=src python -m benchmarks.make_tables --sharded [BENCH_engine.json]
       PYTHONPATH=src python -m benchmarks.make_tables --geo [BENCH_geo.json]
"""
import glob
import json
import os
import sys


def load(d):
    out = {}
    for f in sorted(glob.glob(os.path.join(d, "*.json"))):
        r = json.load(open(f))
        out[(r["arch"], r["shape"], r["mesh"])] = r
    return out


def fmt_row(r):
    if r["status"] == "skip":
        return None
    if r["status"] != "ok":
        return f"| {r['arch']} | {r['shape']} | {r['mesh']} | FAIL | | | | | |"
    rl = r["roofline"]
    m = r["memory"]
    return ("| {a} | {s} | {mesh} | {tc:.3g} | {tm:.3g} | {tl:.3g} | {dom} "
            "| {frac:.2f} | {peak:.1f} |").format(
        a=r["arch"], s=r["shape"], mesh=r["mesh"], tc=rl["t_compute"],
        tm=rl["t_memory"], tl=rl["t_collective"],
        dom=rl["dominant"], frac=rl.get("achievable_flops_frac", 0),
        peak=m["peak_hbm_bytes"] / 2**30)


def table(recs, mesh_filter=None):
    head = ("| arch | shape | mesh | t_compute (s) | t_memory (s) | "
            "t_collective (s) | dominant | flops-frac | peak GiB/dev |\n"
            "|---|---|---|---|---|---|---|---|---|")
    rows = [head]
    skips = []
    for key in sorted(recs):
        r = recs[key]
        if mesh_filter and r["mesh"] != mesh_filter:
            continue
        row = fmt_row(r)
        if row is None:
            skips.append(f"* {r['arch']} × {r['shape']}: {r['reason']}")
        else:
            rows.append(row)
    return "\n".join(rows), sorted(set(skips))


def dryrun_table(recs, mesh):
    head = ("| arch | shape | compile s | peak GiB/dev | collective ops | "
            "collective GiB/dev/step | useful-flops frac |\n"
            "|---|---|---|---|---|---|---|")
    rows = [head]
    for key in sorted(recs):
        r = recs[key]
        if r["mesh"] != mesh or r["status"] != "ok":
            continue
        rl = r["roofline"]
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r.get('compile_s', 0)} "
            f"| {r['memory']['peak_hbm_bytes'] / 2**30:.1f} "
            f"| {rl['collective_op_count']} "
            f"| {rl['collective_bytes_per_device'] / 2**30:.2f} "
            f"| {r['model']['useful_fraction']:.2f} |")
    return "\n".join(rows)


def queries_table(path="BENCH_queries.json"):
    """Units-of-work matrix per (query model × persistence) workload
    (benchmarks/queries_mixed.py output)."""
    rec = json.load(open(path))
    rows = {}
    systems = []
    for r in rec["results"]:
        rows.setdefault(r["workload"], {})[r["system"]] = r
        if r["system"] not in systems:
            systems.append(r["system"])
    print(f"### Mixed query/persistence workloads — mean units of work "
          f"({rec['scenario']}, {rec['ticks']} ticks)\n")
    print("| workload | " + " | ".join(systems) + " | swarm vs history |")
    print("|---" * (len(systems) + 2) + "|")
    for wl, by_sys in rows.items():
        cells = [f"{by_sys[s]['uow_mean']:.3e}" if s in by_sys else ""
                 for s in systems]
        ratio = (by_sys["swarm"]["uow_mean"]
                 / max(by_sys["static_history"]["uow_mean"], 1e-9))
        print(f"| {wl} | " + " | ".join(cells) + f" | {ratio:.2f}x |")


def pubsub_table(path="BENCH_pubsub.json"):
    """Spatio-textual pub/sub matching throughput under hot-hashtag
    migration (benchmarks/pubsub.py output)."""
    rec = json.load(open(path))
    print(f"### Spatio-textual pub/sub — hot-hashtag migration, "
          f"{rec['subscriptions']:,} standing subscriptions, "
          f"{rec['ticks']} ticks ({rec['hot_terms']} trending terms @ "
          f"{rec['term_peak']:.0%} peak, T={rec['term_buckets']} "
          f"term buckets)\n")
    print("| plane | system | hot-window throughput (tuples/tick) | "
          "hot-window latency (ticks) | deliveries | wall s |")
    print("|---" * 6 + "|")
    for row in rec["results"]:
        for system in ("swarm", "static_history"):
            r = row[system]
            print(f"| {row['plane']} | {system} | {r['thr_hot']:.1f} "
                  f"| {r['lat_hot']:.1f} | {r['deliveries']:.3e} "
                  f"| {r['wall_s']:.2f} |")
    print()
    for row in rec["results"]:
        print(f"* {row['plane']}: swarm vs static-history = "
              f"{row['throughput_ratio']:.2f}x throughput, "
              f"{row['latency_ratio']:.2f}x latency")


def sharded_table(path="BENCH_engine.json"):
    """Sharded-plane scaling table from the engine benchmark's devices
    axis: fused events/s per forced host-device count, speedup over the
    single-device jax fused plane, and scaling efficiency (speedup/D
    relative to the D=1 sharded cell)."""
    rec = json.load(open(path))
    # counts above the devices the run could see are recorded as not run
    rows = [r for r in rec.get("devices") or [] if "not_run" not in r]
    if not rows:
        print(f"no devices axis in {path}; rerun "
              f"`python -m benchmarks.run --only engine`")
        return
    base = rows[0]["sharded_fused_evps"]
    cpus = rec.get("host_cpus")
    host = f", {cpus} host cpu{'s' if cpus != 1 else ''}" if cpus else ""
    print(f"### Sharded data plane — fused ingest throughput vs forced "
          f"host devices (batch={rows[0]['batch']:,}, grid {rec['grid']}, "
          f"{rec['machines']} machines{host})\n")
    print("| devices | events/s | vs jax fused (1 dev) | "
          "vs sharded D=1 | scaling eff. | counts equal |")
    print("|---" * 6 + "|")
    for r in rows:
        d = r["devices"]
        rel = r["sharded_fused_evps"] / base
        print(f"| {d} | {r['sharded_fused_evps']:,.0f} "
              f"| {r['speedup_vs_jax_fused']:.2f}x | {rel:.2f}x "
              f"| {rel / d:.0%} | {r['counts_equal']} |")


def geo_table(path="BENCH_geo.json"):
    """Two-region chaos comparison from benchmarks/geo.py: sustained
    throughput of the geo-aware stack vs the latency-blind SWARM and
    the static grid, plus the machine-count scalability knee."""
    rec = json.load(open(path))
    ch = rec["chaos"]
    print(f"### Geo robustness — {rec['machines']} machines in two "
          f"regions ({rec['inter_ms']:.0f} ms / {rec['jitter_ms']:.0f} ms "
          f"jitter links, {rec['tick_ms']:.0f} ms ticks), "
          f"λ={rec['lambda']}, chaos seed {ch['seed']} "
          f"({ch['partitions']} correlated WAN flaps × "
          f"{ch['partition_len']} ticks, drops {ch['drop_beats']:.0%}, "
          f"delays {ch['delay_beats']:.0%}, {ch['interrupts']} "
          f"interrupts)\n")
    print("| plane | system | sustained thr (tuples/tick) | "
          "false suspicions | retried | aborted | migration MB |")
    print("|---" * 7 + "|")
    for row in rec["results"]:
        for system in ("swarm_aware", "swarm_blind", "static_history"):
            r = row[system]
            print(f"| {row['plane']} | {system} "
                  f"| {r['sustained_throughput']:.0f} "
                  f"| {r['false_suspicions']} | {r['retried_transfers']} "
                  f"| {r['aborted_transfers']} "
                  f"| {r['migration_bytes'] / 1e6:.2f} |")
    print()
    for row in rec["results"]:
        print(f"* {row['plane']}: aware vs blind = "
              f"{row['speedup_vs_blind']:.2f}x, aware vs static = "
              f"{row['speedup_vs_static']:.2f}x sustained throughput")
    knee = rec["knee"]
    pts = ", ".join(f"{m}→{knee['sustained'][m]:.0f}"
                    for m in map(str, knee["machines"]))
    print(f"* scalability knee at {knee['knee']} machines "
          f"(saturated sustained throughput: {pts})")


def decisions_table(trace_dir):
    """Per-run planner decision timeline from the flight-recorder JSONL
    exports (``benchmarks.run --trace=DIR``): one row per round the
    coordinator closed, with FSM state, R(S), and what moved."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "*.jsonl")))
    if not paths:
        print(f"no *.jsonl traces under {trace_dir}")
        return
    for path in paths:
        rows = []
        label = os.path.basename(path)[:-len(".jsonl")]
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                if row.get("kind") == "decision":
                    rows.append(row)
        if not rows:
            continue
        print(f"\n### Decision timeline — {label}\n")
        print("| tick | round | kind | stage | decision | R(S) | Δ | "
              "pair | action | pids moved | wire B | moved queries |")
        print("|---" * 12 + "|")
        for row in rows:
            rec = row["record"]
            fsm = rec.get("fsm_after") or {}
            trend = ("improved" if rec.get("improved")
                     else "-" if rec.get("r_s_prev", -1) < 0 else "worse")
            transfers = rec.get("transfers") or []
            pair = ", ".join(f"m{t['m_h']}→m{t['m_l']}" for t in transfers) \
                or "-"
            action = ", ".join(sorted({t["action"] for t in transfers})) \
                or "-"
            pids = sum(len(t["moved_pids"]) for t in transfers)
            mq = rec.get("moved_queries", -1)
            print(f"| {row['tick']} | {rec['round_no']} | {rec['kind']} "
                  f"| {fsm.get('stage', '?')} | {rec['decision']} "
                  f"| {rec['r_s']:.3f} | {trend} | {pair} | {action} "
                  f"| {pids or '-'} | {rec.get('wire_bytes', 0)} "
                  f"| {mq if mq >= 0 else '-'} |")


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--decisions":
        decisions_table(sys.argv[2] if len(sys.argv) > 2 else "traces")
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--queries":
        queries_table(sys.argv[2] if len(sys.argv) > 2
                      else "BENCH_queries.json")
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--sharded":
        sharded_table(sys.argv[2] if len(sys.argv) > 2
                      else "BENCH_engine.json")
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--pubsub":
        pubsub_table(sys.argv[2] if len(sys.argv) > 2
                     else "BENCH_pubsub.json")
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--geo":
        geo_table(sys.argv[2] if len(sys.argv) > 2
                  else "BENCH_geo.json")
        return
    base_dir = sys.argv[1] if len(sys.argv) > 1 else "artifacts/dryrun"
    final_dir = sys.argv[2] if len(sys.argv) > 2 else "artifacts/dryrun_final"
    base = load(base_dir)
    final = load(final_dir)
    print("### Dry-run (single-pod 16×16) — optimized configuration\n")
    print(dryrun_table(final, "16x16"))
    print("\n### Dry-run (multi-pod 2×16×16 = 512 chips)\n")
    print(dryrun_table(final, "2x16x16"))
    print("\n### Roofline — paper-faithful baseline (16×16)\n")
    t, skips = table(base, "16x16")
    print(t)
    print("\nSkips:\n" + "\n".join(skips))
    print("\n### Roofline — optimized (16×16)\n")
    t, _ = table(final, "16x16")
    print(t)
    print("\n### Roofline — optimized (2×16×16)\n")
    t, _ = table(final, "2x16x16")
    print(t)
    # before/after deltas
    print("\n### Baseline → optimized deltas (16×16)\n")
    print("| arch | shape | peak GiB | t_dominant (s) | dominant |")
    print("|---|---|---|---|---|")
    for key in sorted(base):
        a, s, mesh = key
        if mesh != "16x16" or base[key]["status"] != "ok":
            continue
        b, f = base[key], final.get(key)
        if not f or f["status"] != "ok":
            continue
        bd = b["roofline"]["step_time_bound_s"]
        fd = f["roofline"]["step_time_bound_s"]
        print(f"| {a} | {s} "
              f"| {b['memory']['peak_hbm_bytes']/2**30:.1f} → "
              f"{f['memory']['peak_hbm_bytes']/2**30:.1f} "
              f"| {bd:.3g} → {fd:.3g} "
              f"| {b['roofline']['dominant']} → {f['roofline']['dominant']} |")


if __name__ == "__main__":
    main()
