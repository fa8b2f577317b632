"""Layer builder. What a round's row-wise passes cost a row: the device
seconds of the traced window under the stages ``apply`` (the relabel of
``row_leaf`` by the round's splits) and ``count`` (the rows in the round's
2W children), ``counters["stage_s"]``, over the row passes of the window's
trees (``GBDT.stage_work``: rounds that built for a leaf x the rows a
device holds). Nanoseconds a row a round, both stages together. Nothing
where the run kept no stage seconds or the program has no work function."""

from harness import stagework

NAME = "builder.apply_ns_per_row_round"


def read(run):
    return stagework.unit_cost(
        run, NAME, stagework.stage_seconds(run, ("apply", "count")), "apply",
        1e9)
