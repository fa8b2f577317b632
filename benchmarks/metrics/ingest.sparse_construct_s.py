"""Layer dataset. Host seconds the program's Dataset took to build from
the CSR matrix: its spans ``dataset.fit_bins`` (bin mappers from the
sample's stored values), ``dataset.plan_bundles`` (the bundle plan from the
sample's index sets) and ``dataset.apply_bins`` (every row binned and
written as stored columns; ``dataset.encode_bundles`` lies inside it), as
the job kept them (``counters["ingest_spans"]``). Nothing where the
program recorded no ``dataset.plan_bundles`` span (a parent commit, a
Dataset that was not bundled, another job kind)."""

PARTS = ("dataset.fit_bins", "dataset.plan_bundles", "dataset.apply_bins")


def read(run):
    spans = run.counters.get("ingest_spans")
    if not spans or any(k not in spans for k in PARTS):
        return None
    run.notes["ingest.sparse_construct_s"] = dict(spans)
    return sum(float(spans[k]["seconds"]) for k in PARTS)
