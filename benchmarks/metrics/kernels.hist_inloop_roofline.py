"""Layer kernels. The in-loop calls of the histogram kernel against their
roofline. The program carries, per round of the grow loop, the live rows
the call's stream was bounded by and the leaves it built for
(``GBDT.round_log``); the least seconds the chip could take for each,
from harness/peaks.py, are summed over the rounds of the window's trees
and divided by the seconds the in-loop calls took in the trace (all
kernel self time less the root passes). Live rows and valid leaves only,
never the padded stream or the padded lanes, so padding is not work
done."""

from types import SimpleNamespace

from harness import peaks


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
    if prog is None or not run.trace or not trees:
        return None
    log = list(prog.round_log)[-trees:]
    measured = run.trace.class_s.get("kernel", 0.0) - sum(
        run.trace.root_kernel_s)
    if not log or measured <= 0:
        return None
    chip = peaks.peaks_for(run.device["kind"])
    shape = run.shape
    least, rounds, bounds = 0.0, 0, {}
    for rec in log:
        # a row-sharded plan logs [n_shards, rounds]: the shards run at
        # once, so the fullest shard's stream is the round's
        rows = rec.rows.reshape(-1, rec.rows.shape[-1]).max(axis=0)
        for n_rows, n_leaves in zip(rows, rec.leaves):
            if n_leaves <= 0:
                continue
            t, bound = peaks.roofline_seconds(
                *peaks.hist_counts(int(n_rows), shape["cols"],
                                   shape["bins"], int(n_leaves)), chip)
            least += t
            rounds += 1
            bounds[bound] = bounds.get(bound, 0) + 1
    run.notes["kernels.hist_inloop_roofline"] = {
        "least_s": least, "measured_s": measured, "rounds": rounds,
        "trees": len(log), "rounds_by_bound": bounds}
    return 100.0 * least / measured
