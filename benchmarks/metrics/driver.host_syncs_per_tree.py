"""Layer driver. The program's ``gbdt.host_sync_count`` from the start of the
window to the fetch of the window's trees after it, over the trees: the
one fetch at the end is all there should be."""


def read(run):
    c = run.counters
    return c["host_syncs"] / c["trees"] if c.get("trees") else None
