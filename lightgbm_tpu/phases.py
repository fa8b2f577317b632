"""Canonical profiler/auditor phase names — ONE source of truth.

Three layers are coupled through these strings:

1. ``profiler.phase`` (around eager dispatches: a host span and a
   ``jax.named_scope``) and ``profiler.stage`` (inside traced code: the
   ``jax.named_scope`` alone) emit them, so every XLA op staged under a
   phase carries ``<name>/`` in its HLO ``op_name`` metadata;
2. the collective-traffic auditor (``parallel/comms.py``) attributes
   histogram traffic by searching compiled-HLO op names for
   :data:`HIST_MERGE` / :data:`WINNER_SYNC`;
3. the trace doctor (``analysis/hlo_lint.py``) treats any sizeable
   collective whose op name carries NONE of a program's allowed phase
   tags as out-of-phase (rule TD103).

Before this module the names were retyped string literals in each
layer, so renaming a phase at an emission site silently broke the
auditors' attribution (they would just stop matching). Now the emission
side (``profiler.phase`` / ``profiler.stage``) asserts membership in :data:`KNOWN_PHASES` at
annotation time, and every consumer imports the constant instead of
retyping it — a rename is a one-line change here or an immediate
ValueError, never a silent attribution miss.
"""

from __future__ import annotations

__all__ = ["GRADS", "SAMPLING", "BUILD", "UPDATE", "EVAL",
           "INGEST_SKETCH", "INGEST_WRITE", "PREFETCH",
           "HIST_MERGE", "WINNER_SYNC", "ROOT_PASS", "POP", "APPLY",
           "COUNT", "COMPACT", "HIST_GATHER", "HIST_RELAYOUT",
           "HIST_KERNEL", "UNBUNDLE", "SUBTRACT", "FIND", "RANK_GATHER",
           "RANK_SORT",
           "RANK_PAIRS", "RANK_SCATTER", "TRAIN_PHASES",
           "INGEST_PHASES", "COLLECTIVE_PHASES", "BUILD_STAGES",
           "GRADS_STAGES", "KNOWN_PHASES", "HOST_SPANS",
           "PLAN_SHARDS", "PLAN_ROWS_PER_SHARD",
           "PLAN_COLLECTIVES_PER_ROUND", "PLAN_ROUND_BYTES_BY_STAGE",
           "PLAN_TREE_BYTES_BY_STAGE", "PLAN_COUNTERS",
           "SHAPE_ROWS", "SHAPE_STORED_COLUMNS", "SHAPE_STORED_BINS",
           "SHAPE_SEARCH_POSITIONS", "SHAPE_SLOTS",
           "SHAPE_STREAM_CHUNK_ROWS", "SHAPE_STREAM_COMPACTED",
           "SHAPE_KERNEL_ROW_BLOCK", "SHAPE_KERNEL_ROOT_ROW_BLOCK",
           "SHAPE_KERNEL_FEATURE_CHUNK", "SHAPE_KERNEL_CHUNKS",
           "SHAPE_KERNEL_PADDED_BINS", "SHAPE_KERNEL_LANES",
           "SHAPE_ROUNDS_BOUND", "STEP_SHAPE", "STAGE_WORK",
           "UNIT_POSITIONS", "UNIT_ELEMENTS", "UNIT_ONEHOT",
           "UNIT_SORTED", "UNIT_ROW_PASSES", "UNIT_LATTICE", "UNIT_ROWS",
           "UNIT_PAIR_SLOTS", "UNIT_BYTES"]

# training phases (both drivers, boosting/gbdt.py + engine.train's eval)
GRADS = "grads"
SAMPLING = "sampling"
BUILD = "build"
UPDATE = "update"
EVAL = "eval"

# out-of-core ingest/streaming phases (data/ingest.py sketch + shard
# write passes; data/prefetch.py host->device staging during chunked
# training)
INGEST_SKETCH = "ingest_sketch"
INGEST_WRITE = "ingest_write"
PREFETCH = "prefetch"

# collective phases (ops/histogram.merge_histograms,
# boosting/tree_builder._sync_best) — these reach compiled HLO as
# op-name prefixes and carry the auditors' traffic attribution
HIST_MERGE = "hist_merge"
WINNER_SYNC = "winner_sync"

# stages of one tree build, nested under ``build`` (named after the
# steps of boosting/tree_builder.py's docstring and of the histogram
# wrapper in ops/histogram.py). Inside the compiled step they are
# ``jax.named_scope`` prefixes and nothing else; the deepest one on an
# instruction's ``op_name`` path is its stage
# (telemetry/costmodel.instruction_phase_map), which is how a device
# event inside the grow ``while`` gets a source line.
ROOT_PASS = "root_pass"          # root histogram, totals, root split
POP = "pop"                      # top-k over cached gains + their takes
APPLY = "apply"                  # tree scatter, bounds, row_leaf relabel
COUNT = "count"                  # rows in the round's 2W children: one
#                                  compare-and-sum over R (slot_counts)
COMPACT = "compact"              # membership, n_small, and c_idx as one
#                                  sort of the row numbers: the making
#                                  of the index, no row moves
HIST_GATHER = "hist_gather"      # a chunk (pallas) or a block a trip, two
#                                  gathers by row_gather: the bin rows, and
#                                  the per-row table [R, 4] that carries gh
#                                  and row_leaf together (assembled once a
#                                  call, outside the loop, in this stage too)
HIST_RELAYOUT = "hist_relayout"  # cast, pad, transpose for the kernel; of
#                                  a compacted stream, chunk by chunk
HIST_KERNEL = "hist_kernel"      # the pallas_call (or the XLA block loop)
UNBUNDLE = "unbundle"            # an EFB matrix only: the bundle-space
#                                  histogram gathered to the feature-space
#                                  lattice [slots, F, B, 3] the search scans,
#                                  most frequent bins restored (hist_finish)
SUBTRACT = "subtract"            # parent minus child, cache scatters
FIND = "find"                    # best_for / fused split + cache scatter

# stages of a ranking objective's gradients, nested under ``grads``
# (ranking.py: queries in buckets by length, one lattice a bucket)
RANK_GATHER = "rank_gather"      # scores (XE-NDCG: and the draw) by row
#                                  index into each bucket's [Q_b, W] lattice
RANK_SORT = "rank_sort"          # each doc's rank in its query's score order
#                                  (a [Q_b, W, W] count, no sort op) and the
#                                  pick of the window's docs by rank
RANK_PAIRS = "rank_pairs"        # the [Q_b, T, W] pair lattice and its two
#                                  reductions (XE-NDCG: the softmax a query)
RANK_SCATTER = "rank_scatter"    # every bucket's g, h back to rows, each row
#                                  reading its slot (an index fixed at init)

# host spans of the span record (profiler.span): boundaries that happen
# once a tree or more rarely. Each is also a TraceAnnotation named
# ``lgbtpu:<name>`` in a profiler capture.
HOST_SPANS = frozenset({
    "dataset.fit_bins", "dataset.apply_bins",      # Dataset.construct
    "dataset.plan_bundles",    # the EFB plan from the sample's columns;
    #                            the layout's counters ride on it as fields
    #                            (Dataset._plan_counters)
    "dataset.encode_bundles",  # inside apply_bins: the [R, G] matrix from
    #                            the columns; field ``conflict_rows``
    "objective.init",      # a ranking objective's query layout and max-DCG
    #                        tables; its counters ride on it as fields
    "gbdt.to_device",      # H2D of bins, row_leaf0, labels, weights and
    #                        a ranking objective's lattices
    "gbdt.step_ready",     # first call of the fused step: trace..compile;
    #                        the step's shape (STEP_SHAPE) rides on it as
    #                        fields, and under a parallel plan the plan's
    #                        counters (PLAN_COUNTERS)
    "gbdt.dispatch",       # each fused dispatch
    "gbdt.sync.wait",      # the device_get of the pending ring
    "gbdt.sync.trees",     # host Trees from the fetched ring
    "engine.eval", "engine.checkpoint"})

# counters of a parallel plan's compiled fused step
# (parallel/comms.plan_counters), read once from the step's own text and
# kept as fields of the ``gbdt.step_ready`` span. Bytes are what one chip
# puts on the wire under ring algorithms (CollectiveOp.wire_bytes); a
# "round" is the body of the grow loop, a "tree" what runs once outside it
# (the root pass, the step's guards). The merge's and the winner sync's
# bytes a round are the entries HIST_MERGE and WINNER_SYNC of the round's.
PLAN_SHARDS = "plan_shards"
PLAN_ROWS_PER_SHARD = "plan_rows_per_shard"
PLAN_COLLECTIVES_PER_ROUND = "plan_collectives_per_round"    # {kind: n}
PLAN_ROUND_BYTES_BY_STAGE = "plan_round_bytes_by_stage"      # {stage: B}
PLAN_TREE_BYTES_BY_STAGE = "plan_tree_bytes_by_stage"        # {stage: B}
PLAN_COUNTERS = (PLAN_SHARDS, PLAN_ROWS_PER_SHARD,
                 PLAN_COLLECTIVES_PER_ROUND, PLAN_ROUND_BYTES_BY_STAGE,
                 PLAN_TREE_BYTES_BY_STAGE)

# the step's shape: integer fields of the ``gbdt.step_ready`` span under
# every plan and on one device alike, each computed on the host by the
# function the traced builder sizes itself with
# (boosting/tree_builder.step_shape). All are ONE device's.
SHAPE_ROWS = "shape_rows"                    # rows a device holds, padded
SHAPE_STORED_COLUMNS = "shape_stored_columns"  # columns of the bin matrix
#                            the stream gathers and the kernel reads
#                            (bundles where the matrix is bundled)
SHAPE_STORED_BINS = "shape_stored_bins"      # bins of that lattice
SHAPE_SEARCH_POSITIONS = "shape_search_positions"  # positions a slot the
#                            split search scans: features x bins of the
#                            widest feature; a chip's own block of the
#                            features where each chip searches its block
SHAPE_SLOTS = "shape_slots"                  # lattice slots a round, 2 W
SHAPE_STREAM_CHUNK_ROWS = "shape_stream_chunk_rows"  # rows a trip of the
#                            compacted stream's loop
#                            (ops/histogram.stream_chunk_rows)
SHAPE_STREAM_COMPACTED = "shape_stream_compacted"  # 1 where a round sorts
#                            an index and streams the small children's
#                            rows through it, else 0
# the histogram kernel's plan (ops/histogram.kernel_plan) of a round's
# call, and the row block of the root's call (its lanes may differ)
SHAPE_KERNEL_ROW_BLOCK = "shape_kernel_row_block"
SHAPE_KERNEL_ROOT_ROW_BLOCK = "shape_kernel_root_row_block"
SHAPE_KERNEL_FEATURE_CHUNK = "shape_kernel_feature_chunk"
SHAPE_KERNEL_CHUNKS = "shape_kernel_chunks"
SHAPE_KERNEL_PADDED_BINS = "shape_kernel_padded_bins"
SHAPE_KERNEL_LANES = "shape_kernel_lanes"
SHAPE_ROUNDS_BOUND = "shape_rounds_bound"    # the grow loop's bound
STEP_SHAPE = (SHAPE_ROWS, SHAPE_STORED_COLUMNS, SHAPE_STORED_BINS,
              SHAPE_SEARCH_POSITIONS, SHAPE_SLOTS, SHAPE_STREAM_CHUNK_ROWS,
              SHAPE_STREAM_COMPACTED, SHAPE_KERNEL_ROW_BLOCK,
              SHAPE_KERNEL_ROOT_ROW_BLOCK, SHAPE_KERNEL_FEATURE_CHUNK,
              SHAPE_KERNEL_CHUNKS, SHAPE_KERNEL_PADDED_BINS,
              SHAPE_KERNEL_LANES, SHAPE_ROUNDS_BOUND)

# the unit a stage's work is counted in (telemetry/costmodel.stage_work:
# the counts come from the round log and the step's shape, at the
# boundaries of the stage scopes, so that seconds over count is what one
# unit costs). A stage that is not here has no count.
UNIT_POSITIONS = "stream_positions"    # gathered through the index
UNIT_ELEMENTS = "relaid_elements"      # positions x stored columns
UNIT_ONEHOT = "onehot_elements"        # sent through the MXU, padding too
UNIT_SORTED = "sorted_elements"        # of the one sort a round
UNIT_ROW_PASSES = "row_passes"         # one elementwise pass over a row
UNIT_LATTICE = "lattice_positions"     # slots x positions a slot
UNIT_ROWS = "rows"                     # rows x trees
UNIT_PAIR_SLOTS = "pair_slots"         # a ranking objective's layout
UNIT_BYTES = "wire_bytes"              # one chip's, ring estimates
STAGE_WORK = {
    HIST_GATHER: UNIT_POSITIONS, HIST_RELAYOUT: UNIT_ELEMENTS,
    HIST_KERNEL: UNIT_ONEHOT, COMPACT: UNIT_SORTED,
    APPLY: UNIT_ROW_PASSES, COUNT: UNIT_ROW_PASSES,
    FIND: UNIT_LATTICE, SUBTRACT: UNIT_LATTICE, UNBUNDLE: UNIT_LATTICE,
    ROOT_PASS: UNIT_LATTICE, UPDATE: UNIT_ROWS, GRADS: UNIT_ROWS,
    RANK_PAIRS: UNIT_PAIR_SLOTS, HIST_MERGE: UNIT_BYTES,
    WINNER_SYNC: UNIT_BYTES}

TRAIN_PHASES = frozenset({GRADS, SAMPLING, BUILD, UPDATE, EVAL})
INGEST_PHASES = frozenset({INGEST_SKETCH, INGEST_WRITE, PREFETCH})
COLLECTIVE_PHASES = frozenset({HIST_MERGE, WINNER_SYNC})
BUILD_STAGES = frozenset({ROOT_PASS, POP, APPLY, COUNT, COMPACT,
                          HIST_GATHER, HIST_RELAYOUT, HIST_KERNEL,
                          UNBUNDLE, SUBTRACT, FIND}) | COLLECTIVE_PHASES
GRADS_STAGES = frozenset({RANK_GATHER, RANK_SORT, RANK_PAIRS,
                          RANK_SCATTER})
KNOWN_PHASES = (TRAIN_PHASES | INGEST_PHASES | BUILD_STAGES
                | GRADS_STAGES)
