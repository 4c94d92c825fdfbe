"""step_host_ms: host time of each decode step outside the device's
work: its ``serve.step.launch`` (the step program dispatched) plus the
``serve.step.host`` bookkeeping that follows it until the next step
starts (admissions excluded).  Median over the steps that end in the
window; host clock, from the program's span log."""
import numpy as np

import program_trace


def read(run, cell):
    log = program_trace.span_log(run)
    if log is None or "serve.step.launch" not in log:
        return None
    steps, launch = log["serve.decode_step"], log["serve.step.launch"]
    host = log.get("serve.step.host", np.zeros((0, 2)))
    nxt = np.append(steps[1:, 0], np.inf)
    t0, t1 = run.window
    out = []
    for k, (a, b) in enumerate(steps):
        if not t0 < b <= t1:
            continue
        mine = (launch[:, 0] >= a) & (launch[:, 1] <= b)
        after = (host[:, 0] >= b) & (host[:, 1] <= nxt[k])
        out.append(float(np.sum(launch[mine, 1] - launch[mine, 0])
                         + np.sum(host[after, 1] - host[after, 0])))
    if not out:
        return None
    return float(np.median(out)) * 1e3
