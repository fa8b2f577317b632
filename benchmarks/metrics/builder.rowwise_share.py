"""Layer builder. Share of device busy time in XLA operations inside the
grow loop whose largest array has at least ``rows`` elements and whose
result is smaller than rows x cols: per-row lookups into small tables,
compaction of the gradient stream, relabelling."""


def read(run):
    return run.trace.class_share("rowwise") if run.trace else None
