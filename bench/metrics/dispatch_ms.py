"""Mean host time from a chunk call to its return, before blocking."""


def read(ctx):
    """Mean dispatch time of a chunk, in ms."""
    d = [e - s for name, s, e in ctx["spans"] if name == "bench.dispatch"]
    return 1e3 * sum(d) / len(d) if d else None
