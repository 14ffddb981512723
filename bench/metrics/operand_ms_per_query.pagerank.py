"""Host milliseconds per PageRank query spent deriving the operand from
the adjacency: the program's `graph.operand` span, over the window's
queries."""

from bench import scopes


def read(r):
    queries = scopes.recent_queries("pagerank", r.counts.get("queries", 0))
    if not queries:
        return None
    return sum(q.get("graph.operand", 0.0) for q in queries) \
        / len(queries) * 1e3
