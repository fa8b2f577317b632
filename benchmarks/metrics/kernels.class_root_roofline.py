"""Layer kernels. The class-batched build's root pass against its
roofline: one call an iteration that histograms all rows for all K
classes at once (``build_root_histograms_classes``, the custom call
named ``pallas_hist_class_root``), the one-hot of the bins built once
and multiplied against K x 3 addend rows. The least time the chip could
take for that work, from the operations and bytes below and the peaks of
harness/peaks.py, over the mean duration of the kernel events outside
the grow loop (``Report.root_kernel_s``: in such a build the root calls
are these).

    operations  2 R (F B) (K 3)           the one-hot matmul, as
                                          peaks.hist_counts with K leaves
    bytes       R F                       uint8 bins in
              + R K 3 4                   float32 addends of K classes in
              + F B K 3 4                 float32 histograms out

Nothing where the cell has no ``classes`` in its shape or the trace no
root kernel events (a build that makes no class-root call)."""

from harness import peaks


def counts(rows: int, cols: int, bins: int, classes: int):
    """(operations, bytes) of one class-root pass."""
    ops = 2.0 * rows * (cols * bins) * (classes * peaks.HIST_CH)
    byts = (rows * cols + rows * classes * peaks.HIST_CH * 4
            + cols * bins * classes * peaks.HIST_CH * 4)
    return ops, float(byts)


def read(run):
    shape = run.shape
    if not run.trace or not run.trace.root_kernel_s or "classes" not in shape:
        return None
    ops, byts = counts(shape["rows"], shape["cols"], shape["bins"],
                       shape["classes"])
    least, bound = peaks.roofline_seconds(
        ops, byts, peaks.peaks_for(run.device["kind"]))
    calls = run.trace.root_kernel_s
    mean = sum(calls) / len(calls)
    run.notes["kernels.class_root_roofline"] = {
        "bound": bound, "least_s": least, "calls": len(calls), "mean_s": mean,
        "ops": ops, "bytes": byts, "classes": shape["classes"]}
    return 100.0 * least / mean
