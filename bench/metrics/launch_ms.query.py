"""launch_ms.query: median length of the program's ``pipeline.launch``
spans in the traced slice: the host dispatching the fused-DAG kernel,
from the call's arguments ready until the call returns.  Host clock,
from the profiler trace."""
import numpy as np

import program_trace


def read(run, cell):
    tr = program_trace.load(cell)
    if tr is None or not tr.spans.get("pipeline.launch"):
        return None
    return float(np.median([b - a for a, b in
                            tr.spans["pipeline.launch"]])) * 1e-6
