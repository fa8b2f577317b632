"""Layer builder. Of the class-rounds the class-batched build's grow loop
ran, the share that built for at least one leaf. The batched loop runs as
many rounds as the class that needs the most, and a class whose tree is
done rides along frozen in the others' rounds: K x ``batched_rounds``
class-rounds an iteration (``RoundRecord.batched_rounds``, the same on
each of the iteration's K records), of which a class's own are its
rounds with ``leaves > 0``. ``sum(rounds with leaves > 0) /
sum(batched_rounds)`` over the window's trees, from the program's
``GBDT.round_log``. Nothing where the program keeps no
``batched_rounds`` (a parent commit) or the build was not class-batched."""

from harness import program


def read(run):
    log = program.window_log(run)
    if not log or any(getattr(rec, "batched_rounds", None) is None
                      for rec in log):
        return None
    ran = sum(int(rec.batched_rounds) for rec in log)
    if not ran:
        return None
    built = program.rounds_of(log)
    run.notes["builder.class_round_share"] = {
        "class_rounds_built": built, "class_rounds_run": ran,
        "trees": len(log)}
    return 100.0 * built / ran
