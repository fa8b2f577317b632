"""Layer builder. What a scanned lattice position costs the split search:
the device seconds of the traced window under the stages ``find``,
``subtract`` and ``unbundle`` (``counters["stage_s"]``) over the positions
the rounds of the window's trees scanned (``GBDT.stage_work``: rounds x
the 2W slots of a round x the positions a slot, features x bins of the
widest feature; a chip's own block of the features where the merge is a
reduce-scatter; all from the ``gbdt.step_ready`` span's shape fields).
Padding of the lattice is counted: the scan pays for it
(``split.lattice_valid_share`` says how much of it holds a bin). The
root's scan lies under ``root_pass`` and is not in the seconds, except a
bundled matrix's ``unbundle`` at the root. Nanoseconds a position. Nothing
where the run kept no stage seconds or the program has no work function."""

from harness import stagework

NAME = "split.search_ns_per_position"


def read(run):
    return stagework.unit_cost(
        run, NAME,
        stagework.stage_seconds(run, ("find", "subtract", "unbundle")),
        "find", 1e9)
