"""swa_attn_roofline: per cent of the roofline the paged-attention
kernel (``codegen_pallas.lower_paged_decode``, kernel
``paged_decode``) reaches where windowed and full layers mix: the
bytes and operations each call needs for the positions it attends to,
``min(l + 1, window)`` in a windowed layer and ``l + 1`` in a full one
(``counts_moe.attn_bytes``/``attn_flops``), at the chip's peaks, over
the kernel's summed device time in the trace."""
import peaks


def read(run, cell):
    tr = run.trace
    if not tr or not tr.get("paged_calls"):
        return None
    p = peaks.peaks(run.data["device"]["kind"])
    least, _ = peaks.roofline_s(tr["paged_flops"], tr["paged_bytes"], p)
    return 100.0 * least / tr["paged_s"]
