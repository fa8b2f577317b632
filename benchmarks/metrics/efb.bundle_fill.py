"""Layer dataset. How full the stored columns are: the bundle bins the
plan uses (over its columns, 1 + the members' bins; a column of one
feature, that feature's bins) over the bins the kernel's lattice offers
(stored columns x the widest column's bins), both counters of the
program's Dataset (``ingest_counters``, kept by the job as
``counters["ingest"]``). Nothing where the run kept none or the matrix is
not bundled."""


def read(run):
    c = run.counters.get("ingest")
    if not c or not c.get("bundle_bins_offered"):
        return None
    run.notes["efb.bundle_fill"] = {
        k: c.get(k) for k in ("stored_columns", "bundle_bins_used",
                              "bundle_bins_offered", "sample_conflicts",
                              "efb.conflict_rows", "stored_values")}
    return 100.0 * c["bundle_bins_used"] / c["bundle_bins_offered"]
