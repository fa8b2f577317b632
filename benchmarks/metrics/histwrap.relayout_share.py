"""Layer hist wrapper. Share of device busy time in XLA operations whose
result has at least rows x cols elements: the bin matrix gathered by the
compacted row order, cast and transposed for the kernel."""


def read(run):
    return run.trace.class_share("relayout") if run.trace else None
