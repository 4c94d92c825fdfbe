"""admit_scatter_share: per cent of the window spent in the admissions'
``serve.admit.scatter`` spans (page assignment and the prefilled K/V
scattered into the pool, until the pool is written) that end inside
it.  Part of ``admit_share``; host clock, from the program's span
log."""
import program_trace


def read(run, cell):
    log = program_trace.span_log(run)
    if log is None or "serve.admit.scatter" not in log:
        return None
    inside = program_trace.ending_in(run, log["serve.admit.scatter"])
    if not len(inside):
        return None
    t0, t1 = run.window
    return 100.0 * float((inside[:, 1] - inside[:, 0]).sum()) / (t1 - t0)
