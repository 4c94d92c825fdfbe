"""decode_mfu: per cent of the chip's peak that the decode steps reach:
the operations they need (``counts.decode_step_flops`` over the live
contexts of each step) over the summed length of their spans in the
window, against the published bf16 peak."""
import counts
import peaks
import readings


def read(run, cell):
    if "steps" not in run.data:
        return None
    ks = readings.window_steps(run)
    if not len(ks):
        return None
    steps = run.data["steps"]
    flops = sum(counts.decode_step_flops(cell.config, run.data["live"][k])
                for k in ks)
    seconds = float((steps[ks, 1] - steps[ks, 0]).sum())
    p = peaks.peaks(run.data["device"]["kind"])
    return 100.0 * flops / seconds / p.flops_per_s
