"""admit_share: per cent of the window spent in admissions (the
program's ``serve.admit`` spans: batch-1 prefill and the scatter of
its K/V into the pool), which stall every slot's decoding.  Nothing
to read where no admission ends inside the window."""
import readings


def read(run, cell):
    if "admits" not in run.data:
        return None
    inside = [end - start for _, start, end in run.data["admits"]
              if readings.in_window(run, end)]
    if not inside:
        return None
    t0, t1 = run.window
    return 100.0 * sum(inside) / (t1 - t0)
