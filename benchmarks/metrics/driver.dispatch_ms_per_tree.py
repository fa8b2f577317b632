"""Layer driver. Host milliseconds a tree inside the program's
``gbdt.dispatch`` spans of the window (its last ``trees`` dispatches:
nothing is dispatched after the window): the driver's busy time, and the
floor a tree cannot go under however fast the device gets. Where the
window holds more steps than the runtime keeps in flight, some dispatches
wait for a step to finish; the notes carry the median beside the mean."""

from types import SimpleNamespace


def _program(run):
    """The program's span recorder and round log: handed in on ``run``
    (the tests do), else read from the process the job ran in. None
    where the program has neither (a parent commit)."""
    prog = getattr(run, "program", None)
    if prog is not None:
        return prog
    try:
        from lightgbm_tpu import profiler
        from lightgbm_tpu.boosting.gbdt import GBDT
        return SimpleNamespace(recorder=profiler.recorder,
                               round_log=GBDT.latest().round_log)
    except (ImportError, AttributeError):
        return None


def read(run):
    prog = _program(run)
    trees = run.counters.get("trees")
    if prog is None or not trees:
        return None
    spans = prog.recorder.spans("gbdt.dispatch")[-trees:]
    if len(spans) < trees:
        return None
    ms = sorted(1e3 * s.seconds for s in spans)
    run.notes["driver.dispatch_ms_per_tree"] = {
        "trees": trees, "max_ms": ms[-1], "min_ms": ms[0],
        "median_ms": ms[len(ms) // 2]}
    return sum(ms) / trees
