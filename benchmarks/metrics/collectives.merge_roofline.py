"""Layer collectives. The histogram merge against its wire: the bytes one
chip puts on the wire for ``hist_merge`` in the window (the plan's
counters: bytes a round times the rounds of the window's trees from
``GBDT.round_log``, plus the root pass's a tree) over the most the ports a
host of this many chips wires could carry (``harness/wires.py``), which is
the least seconds the merge could take; over the seconds the chips spent
in the stage ``hist_merge`` (self seconds of the traced window, the mean
over the chips). The cell has no kernel of its own: this share of the
wire's peak is its roofline reading. A merge of a few MB a round is bound
by latency and by the wait for the slowest chip, so it reads low; it
cannot pass 100% unless bytes are counted that never cross a wire.
Nothing without a device trace or the counters."""

from harness import program, wires


def read(run):
    plan = run.counters.get("plan")
    stage_s = run.counters.get("stage_s")
    log = program.window_log(run)
    if not run.trace or not plan or not stage_s or not log:
        return None     # no device plane: a CPU's seconds are no device metric
    measured = stage_s.get("hist_merge", 0.0)
    a_round = (plan.get("plan_round_bytes_by_stage") or {}).get("hist_merge")
    a_tree = (plan.get("plan_tree_bytes_by_stage") or {}).get("hist_merge", 0)
    if measured <= 0 or not a_round:
        return None
    rounds = program.rounds_of(log)
    sent = rounds * a_round + len(log) * a_tree
    peak = wires.send_peak(run.device["kind"], plan["plan_shards"])
    least = sent / peak["bytes_per_s"]
    run.notes["collectives.merge_roofline"] = dict(
        peak, bytes_sent_a_chip=sent, rounds=rounds, trees=len(log),
        least_s=least, measured_s=measured)
    return 100.0 * least / measured
