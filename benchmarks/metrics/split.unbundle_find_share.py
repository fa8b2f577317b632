"""Layer builder. Share of device busy time in the split search of a
bundled matrix: the self seconds of every device operation whose stage
(the program's stage map) is ``unbundle`` (the bundle-space histogram
gathered to the feature-space lattice), ``find`` (the scan of that
lattice) or ``subtract`` (parent less child, in bundle space), over the
self seconds of all stages, which sum to the busy time. The job kind keeps
the per-stage seconds of the traced window (``counters["stage_s"]``).
Nothing where the run kept none, or where the program names no
``unbundle`` stage (a parent commit, a matrix of one column a feature)."""

SEARCH_STAGES = ("unbundle", "find", "subtract")


def read(run):
    stage_s = run.counters.get("stage_s")
    if not stage_s or "unbundle" not in stage_s:
        return None
    busy = sum(stage_s.values())
    if busy <= 0:
        return None
    trees = max(int(run.counters.get("trees") or 1), 1)
    run.notes["split.unbundle_find_share"] = {
        "stage_s_per_tree": {k: v / trees for k, v in sorted(stage_s.items())},
        "search_s_per_tree": {k: stage_s.get(k, 0.0) / trees
                              for k in SEARCH_STAGES},
        "unknown_share_pct": 100.0 * stage_s.get("unknown", 0.0) / busy}
    return 100.0 * sum(stage_s.get(k, 0.0) for k in SEARCH_STAGES) / busy
