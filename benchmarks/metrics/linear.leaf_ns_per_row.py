"""Layer linear leaves. What linear leaves cost a row: the device seconds
of the traced window under the fused step's three linear-leaf stages
(``linear_gram``: the moments and Gram kernels; ``linear_solve``: path
masks, shift and the batched solve; ``linear_update``: each row's output
from its leaf's model; ``counters["stage_s"]``) over rows x trees
(``GBDT.stage_work``'s ``linear_update`` count: the rows a device holds,
from the ``gbdt.step_ready`` span's shape, times the window's linear
trees). Nanoseconds a row a tree, in units that do not move when the grow
loop does. Nothing where the run kept no stage seconds or the program has
no such stages (a parent commit)."""

from harness import stagework

NAME = "linear.leaf_ns_per_row"
STAGES = ("linear_gram", "linear_solve", "linear_update")


def read(run):
    return stagework.unit_cost(
        run, NAME, stagework.stage_seconds(run, STAGES), "linear_update",
        1e9)
