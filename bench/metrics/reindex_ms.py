"""Round path: ``reindex_queries`` time (``SwarmRouter.
reindex_all_queries`` over every standing query, after a round that
moved partitions) summed over the window and divided by the rounds in
it, in ms per round: the denominator of ``unspanned_ms``.  0.0 when
rounds ran and none re-indexed; nothing from a program without the
``router_round`` span, which cannot say whether a round re-indexed."""


def read(r):
    rounds = r.rounds_in_window()
    if not rounds or not r.spans_named("router_round"):
        return None
    spans = r.spans_named("reindex_queries")
    return sum(s[2] - s[1] for s in spans) / len(rounds) / 1e6
