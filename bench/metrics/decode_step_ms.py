"""decode_step_ms: median length of the program's ``serve.decode_step``
spans in the window: one ``paged_decode_step`` over every slot, from
dispatch until its tokens are on the host."""
import numpy as np

import readings


def read(run, cell):
    if "steps" not in run.data:
        return None
    steps = run.data["steps"][readings.window_steps(run)]
    if not len(steps):
        return None
    return float(np.median(steps[:, 1] - steps[:, 0])) * 1e3
