"""Layer kernels. The linear-leaf Gram against its roofline: the least
seconds the chip could take for the window's Gram work (harness/peaks.py
``roofline_seconds``) over the device seconds of the traced window under
the fused step's stage ``linear_gram`` (``counters["stage_s"]``: the
moments pass and the Gram pass). Both counts are made here from the
program's own counters (:func:`gram_counts`):

- operations: 2 x the Gram's useful multiply-adds over the window's trees
  (``GBDT.stage_work``'s ``linear_gram`` count: ``sum_l rows_l ((k_l + 1)
  (k_l + 2) / 2 + k_l + 1)``, ``k_l`` the leaf's path features), taken at
  the MXU's bfloat16 peak, so that the share cannot pass 100% whatever
  precision the kernel multiplies in;
- bytes: the raw values, ``g``, ``h`` and the row's leaf read once a tree,
  rows x (4 F + 12) (rows x trees: the ``linear_update`` count; ``F`` the
  Gram's width less its constant column, ``shape_linear_columns - 1`` of
  the step's shape).

Nothing where the run kept no stage seconds or the program has no such
stage (a parent commit)."""

from harness import peaks, stagework

NAME = "kernels.linear_gram_roofline"
SHAPE_FIELD = "shape_linear_columns"


def gram_counts(macs: float, row_trees: float, features: int):
    """(operations, bytes) of the Gram's work: ``macs`` multiply-adds,
    ``row_trees`` rows streamed, ``features`` raw values a row."""
    return 2.0 * macs, float(row_trees) * (4 * features + 12)


def _linear_columns(run):
    prog = getattr(run, "program", None)
    if prog is None:
        try:
            from lightgbm_tpu.boosting.gbdt import GBDT
            prog = GBDT.latest()
        except ImportError:
            return None
    shape = getattr(prog, "step_shape", None) or {}
    return shape.get(SHAPE_FIELD) or None


def read(run):
    seconds = stagework.stage_seconds(run, ("linear_gram",))
    work = stagework.window_work(run)
    columns = _linear_columns(run)
    if not seconds or seconds <= 0 or not work or not columns \
            or "linear_gram" not in work or "linear_update" not in work:
        return None
    ops, byts = gram_counts(work["linear_gram"][0], work["linear_update"][0],
                            columns - 1)
    least, bound = peaks.roofline_seconds(
        ops, byts, peaks.peaks_for(run.device["kind"]))
    run.notes[NAME] = {"bound": bound, "least_s": least, "seconds": seconds,
                       "ops": ops, "bytes": byts,
                       "trees": run.counters["trees"]}
    return 100.0 * least / seconds
