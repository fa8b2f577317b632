"""What a reader takes from the program it measured besides the counters
the job kept: the round log of the window's trees."""

from __future__ import annotations

from typing import List, Optional


def window_log(run) -> Optional[List]:
    """The ``GBDT.round_log`` records of the window's trees (its last
    ``counters["trees"]``): off ``run.program`` where one was handed in
    (the tests do), else off the newest trainer of the process the job ran
    in. None where there are none to read (a parent commit, no trees)."""
    trees = run.counters.get("trees")
    if not trees:
        return None
    prog = getattr(run, "program", None)
    try:
        if prog is None:
            from lightgbm_tpu.boosting.gbdt import GBDT
            prog = GBDT.latest()
        log = list(prog.round_log)[-trees:]
    except (ImportError, AttributeError):
        return None
    return log or None


def rounds_of(log) -> int:
    """Rounds of the grow loop that built for at least one leaf."""
    return sum(int((rec.leaves > 0).sum()) for rec in log)
