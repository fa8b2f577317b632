"""Layer device. The fullest chip's high-water mark after the window, from
``memory_stats()``: ``peak_bytes_in_use`` (live buffers) plus
``peak_bytes_reserved`` (where a running program's temporaries sit)."""


def read(run):
    return run.memory["peak_bytes"] / 2.0 ** 30
