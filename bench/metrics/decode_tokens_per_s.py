"""decode_tokens_per_s: tokens produced in the window -- by decode
steps and by the admissions (prefill) that end in it -- over the
window's length.  Host clock, from the program's spans."""
import readings


def read(run, cell):
    if "steps" not in run.data:
        return None
    steps = readings.window_steps(run)
    tokens = int(run.data["active"][steps].sum())
    tokens += sum(readings.in_window(run, end)
                  for _, _, end in run.data["admits"])
    t0, t1 = run.window
    return tokens / (t1 - t0)
