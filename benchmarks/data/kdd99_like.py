"""KDD Cup 1999-shaped seeded data: the 41 columns of ``kddcup.names`` and
the 23 labels of the full ``kddcup.data`` (4,898,431 connection records),
each label exactly as often as it is there (:data:`LABEL_COUNTS`).

The **labels are a constant multiset and the label surface a constant of
the configuration**, as in ``higgs_like`` since PR 31: every seed is the
same task. Each label has a fixed profile (:func:`profiles`: one draw of a
generator seeded by the width alone, with the three large labels' known
traits written in: ``smurf`` is ICMP echo traffic, ``neptune`` SYN
floods with the S0 flag and high error rates, ``normal`` mostly logged-in
TCP), and a row's 41 values are drawn from its label's profile. The seed
places the labels (a permutation) and draws every value; nothing of the
task depends on it.

Columns, in the order of ``kddcup.names``:

- ``duration`` (0): mostly 0, else a heavy-tailed count of seconds;
- ``protocol_type`` (1, 3 values), ``service`` (2, 70), ``flag`` (3, 11):
  symbolic fields, integer-coded (a label's main value, else uniform);
- ``src_bytes``, ``dst_bytes`` (4, 5): heavy-tailed byte counts, zero
  with a label's probability;
- ``land`` (6), ``logged_in`` (11), ``root_shell`` (13),
  ``is_host_login`` (20), ``is_guest_login`` (21): binary flags;
- the zero-heavy content counts (7-10, 12, 14-18: ``wrong_fragment``,
  ``urgent``, ``hot``, ``num_failed_logins``, ``num_compromised``,
  ``su_attempted``, ``num_root``, ``num_file_creations``, ``num_shells``,
  ``num_access_files``): zero unless the row is one of its label's
  sessions with content, and then each nonzero with its own probability,
  so the counts of one session come together as they do in the data;
- ``num_outbound_cmds`` (19): always 0, as in the data;
- ``count``, ``srv_count`` (22, 23): 0 to 511;
- ``dst_host_count``, ``dst_host_srv_count`` (31, 32): 0 to 255;
- the 15 rates (24-30, 33-40): in [0, 1] at two decimals, with point
  masses at 0 and 1.
"""

import numpy as np

from harness import datagen

COLS = 41
# kddcup.data's label counts, largest first; a label's index is its class
LABEL_COUNTS = (
    ("smurf", 2807886), ("neptune", 1072017), ("normal", 972781),
    ("satan", 15892), ("ipsweep", 12481), ("portsweep", 10413),
    ("nmap", 2316), ("back", 2203), ("warezclient", 1020),
    ("teardrop", 979), ("pod", 264), ("guess_passwd", 53),
    ("buffer_overflow", 30), ("land", 21), ("warezmaster", 20),
    ("imap", 12), ("rootkit", 10), ("loadmodule", 9), ("ftp_write", 8),
    ("multihop", 7), ("phf", 4), ("perl", 3), ("spy", 2))
CLASSES = len(LABEL_COUNTS)
ROWS = sum(n for _, n in LABEL_COUNTS)      # 4,898,431

DURATION, PROTOCOL, SERVICE, FLAG, SRC_BYTES, DST_BYTES = 0, 1, 2, 3, 4, 5
SYMBOLS = {PROTOCOL: 3, SERVICE: 70, FLAG: 11}
FLAGS = (6, 11, 13, 20, 21)
CONTENT = (7, 8, 9, 10, 12, 14, 15, 16, 17, 18)
CONSTANT = (19,)
COUNTS_511 = (22, 23)
COUNTS_255 = (31, 32)
RATES = tuple(range(24, 31)) + tuple(range(33, 41))


def labels(rows: int, seed: int) -> np.ndarray:
    """[rows] int64 classes: at the published row count, each label exactly
    as often as in ``kddcup.data``; at another count the table scaled,
    every label kept at least once. The seed orders them."""
    n = np.array([c for _, c in LABEL_COUNTS], np.int64)
    if rows != ROWS:
        n = np.maximum(1, np.floor(n * (rows / ROWS)).astype(np.int64))
        n[0] += rows - int(n.sum())
        if n[0] < 1:
            raise ValueError(f"{rows} rows cannot hold {CLASSES} labels")
    y = np.repeat(np.arange(CLASSES), n)
    return np.random.default_rng([COLS, seed]).permutation(y)


def profiles() -> dict:
    """Each label's parameters, a function of the width alone: arrays
    with a leading axis of ``CLASSES``."""
    rng = np.random.default_rng([COLS, 0])
    u = lambda *s: rng.uniform(size=(CLASSES,) + s)  # noqa: E731
    p = {
        "duration_nonzero": 0.02 + 0.5 * u() ** 2,
        "duration_mu": rng.uniform(0.0, 6.0, CLASSES),
        "main_symbol": {j: rng.integers(0, k, CLASSES)
                        for j, k in SYMBOLS.items()},
        "main_share": {j: rng.uniform(0.5, 0.95, CLASSES)
                       for j in SYMBOLS},
        "bytes_zero": 0.6 * u(2) ** 2,
        "bytes_mu": rng.uniform(3.0, 9.0, (CLASSES, 2)),
        "flag_p": 0.2 * u(len(FLAGS)) ** 3,
        "content_session": 0.2 * u() ** 2,
        "content_p": rng.uniform(0.05, 0.6, (CLASSES, len(CONTENT))),
        "count_mu": rng.uniform(1.0, 511.0, (CLASSES, 2)),
        "host_mu": rng.uniform(1.0, 255.0, (CLASSES, 2)),
        "rate_zero": 0.7 * u(len(RATES)),
        "rate_one": 0.3 * u(len(RATES)),
        "rate_mu": u(len(RATES)),
    }
    # the three large labels as the data has them
    smurf, neptune, normal = 0, 1, 2
    p["main_symbol"][PROTOCOL][[smurf, neptune, normal]] = (2, 0, 0)
    p["main_share"][PROTOCOL][[smurf, neptune, normal]] = (0.99, 0.99, 0.8)
    p["main_symbol"][FLAG][[smurf, neptune, normal]] = (9, 5, 9)
    p["duration_nonzero"][[smurf, neptune]] = 0.0
    p["bytes_zero"][neptune] = (0.99, 0.99)
    p["count_mu"][[smurf, neptune]] = ((511.0, 511.0), (200.0, 15.0))
    p["flag_p"][normal, FLAGS.index(11)] = 0.7          # logged_in
    p["flag_p"][13, FLAGS.index(6)] = 0.95              # land's land
    p["content_session"][[smurf, neptune]] = 0.0
    p["content_session"][normal] = 0.05
    p["content_session"][11:] = 0.9     # the rare R2L / U2R labels
    return p


def _column(j: int, y: np.ndarray, session: np.ndarray, prof: dict,
            rng: np.random.Generator) -> np.ndarray:
    rows = len(y)
    if j in CONSTANT:
        return np.zeros(rows, np.float32)
    if j == DURATION:
        on = rng.random(rows) < prof["duration_nonzero"][y]
        v = np.floor(np.exp(prof["duration_mu"][y]
                            + 1.5 * rng.standard_normal(rows)))
        return np.where(on, np.minimum(v, 58329.0), 0.0).astype(np.float32)
    if j in SYMBOLS:
        main = prof["main_symbol"][j][y]
        other = rng.integers(0, SYMBOLS[j], rows)
        keep = rng.random(rows) < prof["main_share"][j][y]
        return np.where(keep, main, other).astype(np.float32)
    if j in (SRC_BYTES, DST_BYTES):
        k = j - SRC_BYTES
        zero = rng.random(rows) < prof["bytes_zero"][y, k]
        v = np.floor(np.exp(np.minimum(prof["bytes_mu"][y, k]
                                       + 2.0 * rng.standard_normal(rows),
                                       20.0)))
        return np.where(zero, 0.0, v).astype(np.float32)
    if j in FLAGS:
        return (rng.random(rows) < prof["flag_p"][y, FLAGS.index(j)]) \
            .astype(np.float32)
    if j in CONTENT:
        on = session & (rng.random(rows)
                        < prof["content_p"][y, CONTENT.index(j)])
        return np.where(on, 1.0 + rng.poisson(1.5, rows), 0.0) \
            .astype(np.float32)
    if j in COUNTS_511 + COUNTS_255:
        top, k, key = ((511, COUNTS_511.index(j), "count_mu")
                       if j in COUNTS_511
                       else (255, COUNTS_255.index(j), "host_mu"))
        mu = prof[key][y, k]
        v = np.round(mu + 0.25 * mu * rng.standard_normal(rows))
        return np.clip(v, 0, top).astype(np.float32)
    k = RATES.index(j)
    r = rng.random(rows)
    zero = r < prof["rate_zero"][y, k]
    one = ~zero & (r < prof["rate_zero"][y, k] + prof["rate_one"][y, k])
    v = np.clip(prof["rate_mu"][y, k] + 0.15 * rng.standard_normal(rows),
                0.0, 1.0)
    return np.round(np.where(zero, 0.0, np.where(one, 1.0, v)), 2) \
        .astype(np.float32)


def generate(rows: int, cols: int, seed: int, params: dict):
    """([cols, rows] float32 columns, [rows] float32 labels 0..22)."""
    if cols != COLS:
        raise ValueError(f"kddcup.names has {COLS} columns, not {cols}")
    y = labels(rows, seed)
    prof = profiles()
    session = (np.random.default_rng([COLS + 1, seed]).random(rows)
               < prof["content_session"][y])
    x = np.empty((cols, rows), np.float32)

    def fill(lo, hi):
        for j in range(lo, hi):
            x[j] = _column(j, y, session, prof,
                           np.random.default_rng([j, seed]))
    datagen._each_block(cols, fill, block=1)
    return x, y.astype(np.float32)
