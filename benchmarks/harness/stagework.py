"""What one unit of a stage's work costs: a stage's device seconds of the
traced window over the count of its work in the window's trees. The count
is the program's own (``GBDT.stage_work``: from its round log and from the
step's shape on its ``gbdt.step_ready`` span, in the units of
``phases.STAGE_WORK``); nothing is taken from the configuration's shape.
In a cell on several chips seconds and counts are both one chip's: the
job kind keeps the chips' mean seconds, the program counts the mean
shard's work."""

from __future__ import annotations

from typing import Optional, Sequence

from . import program


def window_work(run) -> Optional[dict]:
    """``{stage: (count, unit)}`` over the window's trees, or None where
    the program has no work function (a parent commit), made no fused step,
    or its round log does not hold all the window's trees."""
    trees = run.counters.get("trees")
    log = program.window_log(run)
    if not trees or not log or len(log) != trees:
        return None
    prog = getattr(run, "program", None)
    if prog is None:
        from lightgbm_tpu.boosting.gbdt import GBDT
        prog = GBDT.latest()
    count = getattr(prog, "stage_work", None)
    return (count(trees) or None) if count is not None else None


def stage_seconds(run, stages: Sequence[str]) -> Optional[float]:
    """Seconds of the traced window under ``stages`` by the program's stage
    map (``counters["stage_s"]``); None where the job kind kept none."""
    stage_s = run.counters.get("stage_s")
    if not stage_s:
        return None
    return sum(stage_s.get(s, 0.0) for s in stages)


def unit_cost(run, name: str, seconds: Optional[float], counted: str,
              per_second: float) -> Optional[float]:
    """``seconds`` over the count of stage ``counted``, in units of
    ``1 / per_second`` seconds (1e9: ns); seconds, count and trees go to
    ``run.notes[name]``. None without seconds or without a count."""
    if not seconds or seconds <= 0:
        return None
    work = window_work(run)
    if not work or counted not in work:
        return None
    count, unit = work[counted]
    if count <= 0:
        return None
    run.notes[name] = {"seconds": seconds, "count": count, "unit": unit,
                       "trees": run.counters["trees"]}
    return per_second * seconds / count
