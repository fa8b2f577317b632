"""Layer builder. How much fuller the fullest shard's stream is than the
mean shard's: a round of the grow loop ends when its fullest shard has
built its histogram, so the live rows the fullest shard holds, summed over
the rounds of the window's trees, over the mean shard's, less one
(``GBDT.round_log``, whose ``rows`` are [shards, rounds] under a
row-sharded plan). 0% is rows that fall evenly on the shards in every
round. Nothing where the log keeps no shard axis (one shard, a parent
commit)."""

from harness import program


def read(run):
    log = program.window_log(run)
    if not log or any(rec.rows.ndim != 2 or rec.rows.shape[0] < 2
                      for rec in log):
        return None
    fullest = sum(int(rec.rows.max(axis=0).sum()) for rec in log)
    by_shard = sum(rec.rows.sum(axis=1).astype(float) for rec in log)
    mean = float(by_shard.mean())
    if mean <= 0:
        return None
    run.notes["builder.shard_live_skew"] = {
        "shards": len(by_shard),
        "live_rows_by_shard": [int(v) for v in by_shard],
        "fullest_rows": fullest, "mean_rows": mean, "trees": len(log)}
    return 100.0 * (fullest / mean - 1.0)
