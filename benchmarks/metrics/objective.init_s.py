"""Layer objectives. Host seconds of the program's ``objective.init``
span: a ranking objective's query layout and max-DCG tables, made once
when the booster is built (the newest such span: the trainer's; the
job's refusal probe comes before it). Nothing where the program records
no such span (another objective, a parent commit)."""

from types import SimpleNamespace


def _program(run):
    """The program's span recorder: handed in on ``run`` (the tests do),
    else read from the process the job ran in. None where the program
    has none (a parent commit)."""
    prog = getattr(run, "program", None)
    if prog is not None:
        return prog
    try:
        from lightgbm_tpu import profiler
        return SimpleNamespace(recorder=profiler.recorder)
    except (ImportError, AttributeError):
        return None


def read(run):
    prog = _program(run)
    if prog is None:
        return None
    spans = [s for s in prog.recorder.spans() if s.name == "objective.init"]
    if not spans:
        return None
    run.notes["objective.init_s"] = dict(spans[-1].fields)
    return spans[-1].seconds
