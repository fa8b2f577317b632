"""Layer collectives. MiB one chip puts on the wire a tree, under ring
algorithms: the plan's counters (``parallel/comms.plan_counters``, read by
the program from its compiled step and kept on ``gbdt.step_ready``) give
the bytes of every collective of a round of the grow loop and of what runs
once a tree outside it; the program's ``GBDT.round_log`` gives the rounds
the window's trees took. Nothing where the program has no such counters (a
parent commit, a plan of one shard)."""

from harness import program


def read(run):
    plan = run.counters.get("plan")
    log = program.window_log(run)
    if not plan or not log:
        return None
    a_round = plan.get("plan_round_bytes_by_stage")
    a_tree = plan.get("plan_tree_bytes_by_stage")
    if a_round is None or a_tree is None:
        return None
    rounds = program.rounds_of(log)
    total = rounds * sum(a_round.values()) + len(log) * sum(a_tree.values())
    run.notes["collectives.wire_mib_per_tree"] = {
        "bytes_a_round_by_stage": a_round, "bytes_a_tree_by_stage": a_tree,
        "collectives_per_round": plan.get("plan_collectives_per_round"),
        "rounds_per_tree": rounds / len(log), "trees": len(log)}
    return total / len(log) / 2.0 ** 20
