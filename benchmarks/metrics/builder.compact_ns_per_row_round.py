"""Layer builder. What a sorted element costs: the device seconds of the
traced window under the stage ``compact`` (membership and the one sort of
the row numbers that makes the compacted stream's index;
``counters["stage_s"]``) over the elements sorted in the window's trees
(``GBDT.stage_work``: rounds that built for a leaf x the rows a device
holds, from the round log and the step's shape). Nanoseconds a row a
round. Nothing where the run kept no stage seconds or the program has no
work function."""

from harness import stagework

NAME = "builder.compact_ns_per_row_round"


def read(run):
    return stagework.unit_cost(
        run, NAME, stagework.stage_seconds(run, ("compact",)), "compact",
        1e9)
