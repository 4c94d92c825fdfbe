"""programs_per_query: device programs run per pipeline call: the
device modules whose host enqueue (matched by ``run_id``) lies inside a
``pipeline.call`` span, over the ``pipeline.call`` spans of the traced
slice.  A count, from the profiler trace."""
import program_trace


def read(run, cell):
    tr = program_trace.load(cell)
    if tr is None or not tr.spans.get("pipeline.call"):
        return None
    calls = tr.spans["pipeline.call"]
    return sum(len(tr.runs_in(c)) for c in calls) / len(calls)
