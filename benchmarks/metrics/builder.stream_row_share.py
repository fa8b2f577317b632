"""Layer builder. Of the stream positions the rounds of the grow loop
touched to feed their histograms (gathered by the compacted index, cast,
re-laid for the kernel: ``RoundLog.stream_rows``, trips x chunk of the
stream's loop), the share that held a live row (``RoundLog.rows``):
``sum(rows) / sum(stream_rows)`` over the window's trees, from the
program's ``GBDT.round_log``. 100% is a stream that stops exactly at its
live rows; ``builder.live_row_share`` is what it reads when every round
touches all R. Nothing where the program keeps no ``stream_rows`` (a
parent commit)."""

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
    if not log or any(getattr(rec, "stream_rows", None) is None
                      for rec in log):
        return None
    live = sum(int(rec.rows.sum()) for rec in log)
    touched = sum(int(rec.stream_rows.sum()) for rec in log)
    if not touched:
        return None
    rounds = sum(int((rec.leaves > 0).sum()) for rec in log)
    run.notes["builder.stream_row_share"] = {
        "live_rows": live, "stream_rows": touched, "rounds": rounds,
        "trees": len(log), "rows": run.shape["rows"],
        "touched_share_of_rounds_x_rows_pct":
            100.0 * touched / max(rounds * run.shape["rows"], 1)}
    return 100.0 * live / touched
