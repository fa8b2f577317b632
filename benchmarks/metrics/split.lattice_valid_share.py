"""Layer builder. Of the positions the split search scans for a slot (the
feature-space lattice: features x bins of the widest feature), the share
that holds a bin of some feature: ``valid_feature_bins`` (the sum of the
features' own bin counts) over ``scanned_positions``, both counters of the
program's Dataset (``ingest_counters``, kept by the job as
``counters["ingest"]``). One-hot columns beside a few wide numeric ones
read a percent or two: the rest is padding the search makes and masks.
Nothing where the run kept no such counters (another job kind, a parent
commit)."""


def read(run):
    c = run.counters.get("ingest")
    if not c or not c.get("scanned_positions"):
        return None
    run.notes["split.lattice_valid_share"] = {
        k: c.get(k) for k in ("features_used", "valid_feature_bins",
                              "scanned_positions")}
    return 100.0 * c["valid_feature_bins"] / c["scanned_positions"]
