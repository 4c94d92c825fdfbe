"""decode_mfu.moe: per cent of the chip's peak that the decode steps of
a windowed/full, dropless-expert model reach: the active operations
they need (``counts_moe.decode_step_flops``: projections, routers and
head per token, the held experts' token-expert pairs from the
program's ``moe_tokens_held``, attention over the positions each
layer sees) over the summed length of their spans in the window,
against the published bf16 peak."""
import counts_moe
import peaks
import readings


def read(run, cell):
    if "moe_pairs" not in run.data:
        return None
    ks = readings.window_steps(run)
    if not len(ks):
        return None
    steps = run.data["steps"]
    flops = sum(counts_moe.decode_step_flops(
        cell.config, run.data["live"][k], int(run.data["moe_pairs"][k]))
        for k in ks)
    seconds = float((steps[ks, 1] - steps[ks, 0]).sum())
    p = peaks.peaks(run.data["device"]["kind"])
    return 100.0 * flops / seconds / p.flops_per_s
