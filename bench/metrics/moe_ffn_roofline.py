"""moe_ffn_roofline: per cent of the roofline the grouped expert
matmul (``codegen_pallas.lower_moe_gmm``, kernel ``moe_gmm``) reaches:
the touched held experts' weights, the token rows in and out and
``6 d f`` operations per token-expert pair, from the program's routing
counters (``counts_moe.gmm_bytes``/``gmm_flops``), at the chip's
peaks, over the kernel's summed device time in the trace."""
import peaks


def read(run, cell):
    tr = run.trace
    if not tr or not tr.get("moe_gmm_calls"):
        return None
    p = peaks.peaks(run.data["device"]["kind"])
    least, _ = peaks.roofline_s(tr["moe_gmm_flops"], tr["moe_gmm_bytes"], p)
    return 100.0 * least / tr["moe_gmm_s"]
