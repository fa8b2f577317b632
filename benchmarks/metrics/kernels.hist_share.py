"""Layer kernels. Share of device busy time in the program's histogram
kernel (its custom-call events), root pass and in-loop passes together."""


def read(run):
    return run.trace.class_share("kernel") if run.trace else None
