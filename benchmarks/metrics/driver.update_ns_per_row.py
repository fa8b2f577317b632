"""Layer driver. What the score update costs a row: the device seconds of
the traced window under the fused step's stage ``update`` (each row reads
its leaf's value and adds it to its score; ``counters["stage_s"]``) over
rows x trees (``GBDT.stage_work``: the rows a device holds, from the
``gbdt.step_ready`` span's shape fields). Nanoseconds a row a tree.
Nothing where the run kept no stage seconds or the program has no work
function."""

from harness import stagework

NAME = "driver.update_ns_per_row"


def read(run):
    return stagework.unit_cost(
        run, NAME, stagework.stage_seconds(run, ("update",)), "update", 1e9)
