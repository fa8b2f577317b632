"""Layer builder. Of the rows each round of the grow loop runs its
row-sized gathers and lookups over (all of them, every round), the share
that is live: the rows of the smaller children, which is all the round's
histogram needs. ``sum(round_rows) / (rounds x rows)`` over the window's
trees, from the program's ``GBDT.round_log``: the builder's ratio of
useful to attempted work."""

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
    log = list(prog.round_log)[-trees:]
    live = sum(int(rec.rows.sum()) for rec in log)
    rounds = sum(int((rec.leaves > 0).sum()) for rec in log)
    if not rounds:
        return None
    run.notes["builder.live_row_share"] = {
        "live_rows": live, "rounds": rounds, "trees": len(log),
        "rounds_per_tree": rounds / len(log), "rows": run.shape["rows"]}
    return 100.0 * live / (rounds * run.shape["rows"])
