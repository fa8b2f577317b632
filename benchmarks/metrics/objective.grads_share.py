"""Layer objectives. Share of device busy time under the fused step's
``grads`` stage: the self seconds of every device operation whose stage
(the program's stage map, ``costmodel.instruction_phase_map``) is
``grads`` or one of the ranking stages nested under it (``rank_gather``,
``rank_sort``, ``rank_pairs``, ``rank_scatter``), over the self seconds
of all stages, which sum to the busy time. The job kind keeps the
per-stage seconds of the traced window (``counters["stage_s"]``, from
the program's own reduction of the capture). Nothing where the run kept
none (job kind ``train``, a run without a device trace)."""

GRADS_STAGES = ("grads", "rank_gather", "rank_sort", "rank_pairs",
                "rank_scatter")


def read(run):
    stage_s = run.counters.get("stage_s")
    if not stage_s:
        return None
    busy = sum(stage_s.values())
    if busy <= 0:
        return None
    grads = {k: stage_s.get(k, 0.0) for k in GRADS_STAGES}
    trees = max(int(run.counters.get("trees") or 1), 1)
    run.notes["objective.grads_share"] = {
        "stage_s_per_tree": {k: v / trees for k, v in sorted(stage_s.items())},
        "grads_s_per_tree": sum(grads.values()) / trees,
        "unknown_share_pct": 100.0 * stage_s.get("unknown", 0.0) / busy}
    return 100.0 * sum(grads.values()) / busy
