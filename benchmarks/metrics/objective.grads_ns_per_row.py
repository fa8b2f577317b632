"""Layer objectives. What the gradients cost a row: the device seconds of
the traced window under the fused step's stage ``grads`` and the ranking
stages nested under it (``rank_gather``, ``rank_sort``, ``rank_pairs``,
``rank_scatter``; ``counters["stage_s"]``) over rows x trees
(``GBDT.stage_work``: the rows a device holds). Nanoseconds a row a tree:
an elementwise objective reads a few hundredths, a ranking objective what
its lattices cost. Nothing where the run kept no stage seconds or the
program has no work function."""

from harness import stagework

NAME = "objective.grads_ns_per_row"
GRADS_STAGES = ("grads", "rank_gather", "rank_sort", "rank_pairs",
                "rank_scatter")


def read(run):
    return stagework.unit_cost(
        run, NAME, stagework.stage_seconds(run, GRADS_STAGES), "grads", 1e9)
