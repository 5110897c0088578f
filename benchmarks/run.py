"""Benchmark driver — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines (benchmarks/common.emit).

  fig11  capability + latency vs #queries     (benchmarks/capability.py)
  fig12–16 hotspot scenarios                  (benchmarks/hotspots.py)
  fig17  machine utilization spread           (benchmarks/utilization.py)
  fig18/19 SWARM operation overheads          (benchmarks/overheads.py)
  fig20  statistics network traffic           (benchmarks/stats_network.py)
  kernels  Pallas-oracle throughput           (benchmarks/kernels.py)
  roofline per-cell three-term analysis       (benchmarks/roofline.py)
  queries  query×persistence workload matrix  (benchmarks/queries_mixed.py)
  dataplane NumPy vs JAX plane throughput     (benchmarks/dataplane.py)
  control  round-close + planner throughput   (benchmarks/control_plane.py)
  engine   per-tick vs fused engine ingest +  (benchmarks/engine_throughput.py)
           sharded-plane devices axis
  elasticity kill/join/straggler recovery     (benchmarks/elasticity.py)
  pubsub   spatial-keyword matching at 1M subs (benchmarks/pubsub.py)
  geo      two-region chaos: link-aware SWARM  (benchmarks/geo.py)
           vs latency-blind vs static

``--data-plane`` selects the routing data plane for the experiment
sections; a comma list (e.g. ``--data-plane=numpy,jax,sharded``)
repeats the chosen sections once per plane.  ``--trace=DIR`` turns the flight
recorder on for every experiment cell and exports JSONL + Perfetto
traces into DIR (validate/inspect with ``benchmarks.validate_trace``
and ``benchmarks.make_tables --decisions``).
"""
import argparse
import inspect


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: capability,hotspots,utilization,"
                         "overheads,stats_network,kernels,roofline,queries,"
                         "dataplane,control,engine,elasticity,pubsub,geo")
    ap.add_argument("--smoke", action="store_true",
                    help="short timelines (CI sanity run)")
    ap.add_argument("--data-plane", default="numpy",
                    help="routing data plane(s), comma list: numpy,jax")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="export telemetry traces (JSONL + Perfetto) for "
                         "every experiment cell into DIR")
    args = ap.parse_args()
    from repro.launch.mesh import use_compile_cache
    use_compile_cache()
    from . import (capability, common, control_plane, dataplane, elasticity,
                   engine_throughput, geo, hotspots, kernels, overheads,
                   pubsub, queries_mixed, roofline, stats_network,
                   utilization)
    sections = {
        "capability": capability.run,
        "hotspots": hotspots.run,
        "utilization": utilization.run,
        "overheads": overheads.run,
        "stats_network": stats_network.run,
        "kernels": kernels.run,
        "roofline": roofline.run,
        "queries": queries_mixed.run,
        "dataplane": dataplane.run,
        "control": control_plane.run,
        "engine": engine_throughput.run,
        # runs both data planes internally (and asserts fused ≡ per-tick
        # across a scheduled failure before measuring anything)
        "elasticity": elasticity.run,
        # runs both data planes internally; asserts hashed-matching
        # collision bound, plane parity and fused ≡ per-tick first
        "pubsub": pubsub.run,
        # runs both data planes internally; pins same-seed fault-schedule
        # determinism before scoring the two-region chaos comparison
        "geo": geo.run,
    }
    # sections whose results depend on the routing data plane; the rest
    # run once regardless of how many planes were requested
    plane_sensitive = {"capability", "hotspots", "utilization", "queries"}
    chosen = (args.only.split(",") if args.only else list(sections))
    if args.trace:
        common.set_trace_dir(args.trace)
    planes = args.data_plane.split(",")
    print("name,us_per_call,derived")
    for i, plane in enumerate(planes):
        common.set_data_plane(plane)
        if len(planes) > 1:
            print(f"# data plane: {plane}")
        for name in chosen:
            if i > 0 and name not in plane_sensitive:
                continue
            fn = sections[name]
            if args.smoke and "smoke" in inspect.signature(fn).parameters:
                fn(smoke=True)
            else:
                fn()


if __name__ == "__main__":
    main()
