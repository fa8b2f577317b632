"""Layer kernels. What a one-hot element sent through the MXU costs: the
self seconds of the program's histogram kernel in the traced window (its
custom-call events, root passes and in-loop calls together:
``run.trace.class_s["kernel"]``, a chip's mean) over the one-hot elements
its grid steps multiplied in the window's trees (``GBDT.stage_work``: the
rows the steps cover, a round's live rows rounded up to the kernel plan's
row block and the root's padded rows a tree, x chunks x feature chunk x
padded bins, all from the round log and the ``gbdt.step_ready`` span's
shape fields; padding is counted because the pass pays for it, row blocks
the kernel skips are not). Picoseconds an element; one 128-wide pass at a
v5e's peak is 1 / 7.70e11 s = 1.30 ps, and a reading under that would
mean the count holds elements the kernel never multiplied. Nothing without
a device trace or on a program without the work function."""

from harness import stagework

NAME = "kernels.hist_ps_per_onehot_element"


def read(run):
    if not run.trace:
        return None
    return stagework.unit_cost(
        run, NAME, run.trace.class_s.get("kernel", 0.0), "hist_kernel", 1e12)
