"""Layer objectives. The pairs there are over the pair positions the
ranking objective's layout evaluates an iteration: ``pairs`` (the sum of
``n_q^2`` over the queries) / ``pair_slots``, both counters the objective
sets at init. A layout that pads every query to the longest reads
``sum n_q^2 / (Q x max^2)``, a few percent at uneven sizes; one that
follows the sizes reads tens of percent; over 100% says the layout
evaluates fewer positions than the queries' squares (the truncation
window bounds one side of a pair). Nothing where the run has no such
counters (another job kind, a parent commit)."""


def read(run):
    c = run.counters.get("objective")
    if not c or not c.get("pair_slots"):
        return None
    run.notes["objective.pair_slot_share"] = dict(
        c, slots_per_row=c["slots"] / max(run.shape["rows"], 1))
    return 100.0 * c["pairs"] / c["pair_slots"]
