"""Layer entry. What of ``entry.first_dispatch_s`` is not tree 0: the
program's ``gbdt.to_device`` spans (bins, row ids, labels and weights to
the device, each ending in a block) plus its ``gbdt.step_ready`` span
(the first call of the fused step: trace, lowering, and the compile or
the load from the persistent cache)."""

from types import SimpleNamespace


def _program(run):
    """The program's span recorder and round log: handed in on ``run``
    (the tests do), else read from the process the job ran in. None
    where the program has neither (a parent commit)."""
    prog = getattr(run, "program", None)
    if prog is not None:
        return prog
    try:
        from lightgbm_tpu import profiler
        from lightgbm_tpu.boosting.gbdt import GBDT
        return SimpleNamespace(recorder=profiler.recorder,
                               round_log=GBDT.latest().round_log)
    except (ImportError, AttributeError):
        return None


def read(run):
    prog = _program(run)
    if prog is None:
        return None
    # the newest trainer's: its first call, and the puts since the first
    # call of the one before it (a process may have trained before)
    spans = [s for s in prog.recorder.spans()
             if s.name in ("gbdt.to_device", "gbdt.step_ready")]
    last = max((i for i, s in enumerate(spans)
                if s.name == "gbdt.step_ready"), default=None)
    if last is None:
        return None
    ready = spans[last]
    put = []
    for s in reversed(spans[:last]):
        if s.name == "gbdt.step_ready":
            break
        put.append(s)
    to_device_s = sum(s.seconds for s in put)
    fields = dict(ready.fields)
    run.notes["entry.step_ready_s"] = dict(
        fields, to_device_s=to_device_s, step_ready_s=ready.seconds,
        cache="hit" if fields.get("cache_hits") and
        not fields.get("cache_misses") else "miss")
    return to_device_s + ready.seconds
