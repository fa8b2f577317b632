"""Layer entry. Seconds of the benchmark's span around building the Booster,
the first ``update()`` and the wait for its scores: moving the data to the
device, compiling the step or loading it from the cache, and one tree."""


def read(run):
    return run.spans.get("setup.first_dispatch")
