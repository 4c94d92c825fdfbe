"""fused_dag_roofline: per cent of the roofline the fused-DAG kernel
(``codegen_pallas.lower_fused_dag``) reaches: the bytes and operations
a scan needs (``counts.scan_bytes``/``scan_flops``) at the chip's
peaks, over the kernel's summed device time in the trace."""
import readings


def read(run, cell):
    if "queries" not in run.data:
        return None
    return readings.roofline_share(run)
