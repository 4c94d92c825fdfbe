"""paged_attn_roofline: per cent of the roofline the paged-attention
kernel (``codegen_pallas.lower_paged_decode``) reaches: the bytes and
operations each call needs for the live tokens
(``counts.paged_attn_bytes``/``paged_attn_flops``) at the chip's peaks,
over the kernel's summed device time in the trace."""
import readings


def read(run, cell):
    if "steps" not in run.data:
        return None
    return readings.roofline_share(run)
