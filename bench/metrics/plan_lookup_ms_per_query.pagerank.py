"""Host milliseconds per PageRank query spent finding the operand's plan:
the program's `plan_cache.get` span (the fingerprint of the operand, the
cache lookup, and a build on a miss), over the window's queries."""

from bench import scopes


def read(r):
    queries = scopes.recent_queries("pagerank", r.counts.get("queries", 0))
    if not queries:
        return None
    return sum(q.get("plan_cache.get", 0.0) for q in queries) \
        / len(queries) * 1e3
