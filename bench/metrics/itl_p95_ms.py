"""itl_p95_ms: 95th percentile of every gap between consecutive tokens
of every request, over the gaps that lie in the window.  A request's
first token is on the host when its admission span ends; each later
one when the decode step that made it ends.  Host clock, from the
program's spans."""
import numpy as np


def read(run, cell):
    if "steps" not in run.data:
        return None
    t0, t1 = run.window
    ends = run.data["steps"][:, 1]
    gen = run.data["gen"]
    gaps = []
    for r, _, admit_end in run.data["admits"]:
        k = run.data["first_step"][r]
        times = np.concatenate([[admit_end], ends[k:k + gen]])
        ok = (times[:-1] >= t0) & (times[1:] <= t1)
        gaps.append(np.diff(times)[ok])
    gaps = np.concatenate(gaps)
    if not len(gaps):
        return None
    return float(np.percentile(gaps, 95)) * 1e3
