"""Layer hist wrapper. What a gathered stream position costs: the device
seconds of the traced window under the stage ``hist_gather`` (the chunk
loop's two gathers by the chunk's index, and the per-row table's assembly
once a round; ``counters["stage_s"]``, the program's stage map) over the
stream positions the window's trees gathered (``GBDT.stage_work``:
the round log's ``sum(stream_rows)``; a chip's under a row-sharded plan).
Nanoseconds a position. Nothing where the run kept no stage seconds (job
kind ``train``) or the program has no work function (a parent commit)."""

from harness import stagework

NAME = "histwrap.gather_ns_per_position"


def read(run):
    return stagework.unit_cost(
        run, NAME, stagework.stage_seconds(run, ("hist_gather",)),
        "hist_gather", 1e9)
