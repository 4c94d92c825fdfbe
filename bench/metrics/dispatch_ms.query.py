"""dispatch_ms.query: median host time of the program's pipeline call
(``lower_pipeline``'s returned callable) until it hands back its
not-yet-computed answer, over the window's queries.  Host clock."""
import numpy as np


def read(run, cell):
    queries = run.data.get("queries")
    if queries is None or not len(queries):
        return None
    return float(np.median(queries[:, 1] - queries[:, 0])) * 1e3
