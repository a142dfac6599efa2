"""``trainer_traces_per_query.train``: traces of the streamed trainer's
step programs in the traced window (the program's ``trainer.trace``
spans, one where it counts each ``trainer.traces``), per ``train_glm``
call there (``bench/program_trace.py``)."""
from bench import program_trace


def read(run):
    got = program_trace.for_run(run)
    calls = run.counters.get("program_calls", {})
    n = sum(calls.get(t.name, 0) for t in run.templates
            if t.spec["kind"] == "train_glm")
    if got is None or got["traces"] is None or not n:
        return None
    return got["traces"] / n
