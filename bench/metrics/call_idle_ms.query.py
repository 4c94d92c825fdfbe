"""call_idle_ms.query: device idle time per query while the host is in
the pipeline call: the part of each ``pipeline.call`` span in which no
device operation ran, with the device's times moved onto the host's
clock by the ``run_id`` offset (``program_trace``), averaged over the
calls of the traced slice."""
import program_trace


def read(run, cell):
    tr = program_trace.load(cell)
    if tr is None or not tr.spans.get("pipeline.call"):
        return None
    calls = tr.spans["pipeline.call"]
    return sum(tr.idle_in(c) for c in calls) / len(calls) * 1e-6
