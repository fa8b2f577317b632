"""Layer kernels. The root pass of the histogram kernel against its
roofline: the one call per tree whose row count is known (all rows, one
leaf). The least time the chip could take, from harness/peaks.py, over the
mean duration of the kernel events outside the grow loop. In-loop calls
run over a compacted stream whose live length the trace does not carry,
so they are not priced."""

from harness import peaks


def read(run):
    if not run.trace or not run.trace.root_kernel_s:
        return None
    shape = run.shape
    ops, byts = peaks.hist_counts(shape["rows"], shape["cols"], shape["bins"], 1)
    least, bound = peaks.roofline_seconds(
        ops, byts, peaks.peaks_for(run.device["kind"]))
    calls = run.trace.root_kernel_s
    mean = sum(calls) / len(calls)
    run.notes["kernels.hist_root_roofline"] = {
        "bound": bound, "least_s": least, "calls": len(calls), "mean_s": mean,
        "ops": ops, "bytes": byts}
    return 100.0 * least / mean
