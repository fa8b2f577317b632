"""Layer collectives. Share of a chip's busy time that its collectives
take: the self seconds of the stages ``hist_merge`` and ``winner_sync``
(the program's stage map over the traced window, by chip:
``counters["stage_s_by_chip"]``) on the chip that has most of them, over
the self seconds of all that chip's stages, which sum to its busy time. A
collective's self time on a chip holds its wait for the slowest chip, so
this is what the collectives cost as the chip feels them, not the wire's
time alone. Nothing where the run kept no stage seconds by chip (a run
without a device trace, another job kind)."""

COLLECTIVE_STAGES = ("hist_merge", "winner_sync")


def read(run):
    by_chip = run.counters.get("stage_s_by_chip")
    if not run.trace or not by_chip:
        return None     # no device plane: a CPU's seconds are no device metric
    trees = max(int(run.counters.get("trees") or 1), 1)
    spent = {chip: sum(s.get(k, 0.0) for k in COLLECTIVE_STAGES)
             for chip, s in by_chip.items()}
    chip = max(spent, key=spent.get)
    busy = sum(by_chip[chip].values())
    if busy <= 0:
        return None
    stage_s = run.counters.get("stage_s") or {}
    run.notes["collectives.exposed_share"] = {
        "chip": chip,
        "stage_s_per_tree": {k: v / trees for k, v in sorted(stage_s.items())},
        "collective_s_per_tree_by_chip": {
            c: {k: s.get(k, 0.0) / trees for k in COLLECTIVE_STAGES}
            for c, s in sorted(by_chip.items())},
        "busy_s_per_tree_by_chip": {c: sum(s.values()) / trees
                                    for c, s in sorted(by_chip.items())},
        "unknown_share_pct": 100.0 * by_chip[chip].get("unknown", 0.0) / busy}
    return 100.0 * spent[chip] / busy
