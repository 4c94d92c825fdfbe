"""idle_share.serve: per cent of the traced slice of the window in
which no operation ran on the device, in the serving cells."""
import readings


def read(run, cell):
    if "steps" not in run.data:
        return None
    return readings.idle_share(run)
