"""Layer builder. Of the rounds of the grow loop that built a histogram,
the share that took the dense route: one kernel pass over all rows in
stored order instead of the compacted stream's sort and gathers
(``RoundLog.dense``, 1 where the round went dense; the rule is
``tree_builder.dense_round_rows``, from the step's shape). Over the
window's trees, from the program's ``GBDT.round_log``; under a row-sharded
plan the log holds a route a shard, and the share is over shards x
rounds. 0 where the step compiles no dense route (every cell but the
narrow ones); nothing where the program keeps no ``dense`` (a parent
commit)."""

from types import SimpleNamespace

import numpy as np


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
    if not log or any(getattr(rec, "dense", None) is None for rec in log):
        return None
    dense, built = 0, 0
    for rec in log:
        ran = np.asarray(rec.leaves) > 0
        routes = np.asarray(rec.dense).reshape(-1, ran.shape[-1])[:, ran]
        dense += int((routes > 0).sum())
        built += routes.size
    if not built:
        return None
    run.notes["builder.dense_round_share"] = {
        "dense_rounds": dense, "built_rounds": built, "trees": len(log)}
    return 100.0 * dense / built
