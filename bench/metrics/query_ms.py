"""query_ms: the window's length over the queries completed in it;
each query ends with its answer on the host.  Host clock."""


def read(run, cell):
    queries = run.data.get("queries")
    if queries is None or not len(queries):
        return None
    t0, t1 = run.window
    return (t1 - t0) / len(queries) * 1e3
