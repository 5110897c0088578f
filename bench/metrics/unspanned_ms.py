"""Round path, outside the program's spans: host time inside the
harness's ``bench_call`` annotations that no program span covers, per
round in the window, in ms.

After a round that moves partitions, ``SwarmRouter._outcome`` runs
``reindex_all_queries`` over every standing query on the host, and the
program has no span around it, so ``round_ms`` does not see it.  A CPU
profile of 256 ticks of ``lbs_range`` put 2.75 s of 3.9 s there (16
calls); the traced runs on the chip show it as idle gaps of 140–260 ms
inside ``bench_call``.  This reads all such uncovered time, whatever
the program does in it."""
from tracing import union_ns


def read(r):
    rounds = r.rounds_in_window()
    calls = [(s, e) for name, s, e in r.trace.annotations
             if name == "bench_call" and r.lo <= s and e <= r.hi]
    if not rounds or not calls:
        return None
    spans = [(s[1], s[2]) for s in r.spans]
    outside = sum((e - s) - union_ns(spans, s, e) for s, e in calls)
    return outside / len(rounds) / 1e6
