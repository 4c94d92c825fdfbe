"""idle_share.query: per cent of the traced slice of the window in
which no operation ran on the device, in the scan cells."""
import readings


def read(run, cell):
    if "queries" not in run.data:
        return None
    return readings.idle_share(run)
