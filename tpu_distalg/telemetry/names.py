"""The names the program owns in a profiler trace.

Device side: ``jax.named_scope`` strings set where the work is done, so
they exist at trace time only and ride in every op's ``op_name``
metadata (XLA keeps it through fusion; a profiler trace stores each
program's HLO, ``op_name`` included, beside its events). A reader finds a step's parts by these
and not by the names XLA gives its instructions, which a refactor or a
compiler release moves. Host side: every ``telemetry.span(name)`` is
``tda:<name>`` on the profiler's clock (``events.ANNOTATION_PREFIX``).

Stdlib-only, like the rest of this package.
"""

# the four parts of an SSGD step (models/ssgd.py, ops/sampling.py);
# local_sgd's rounds draw through the same sampling function
SSGD_DRAW = "tda.ssgd.draw"      # threefry words, the selection of the least
SSGD_KERNEL = "tda.ssgd.kernel"  # the Mosaic call and what XLA puts round it
SSGD_SYNC = "tda.ssgd.sync"      # the psum (dense) or the comm schedule
SSGD_UPDATE = "tda.ssgd.update"  # the rest of a step: reg, update, eval
# a step over hashed rows (ops/pallas_hashed.py) has two passes where the
# packed rows have one kernel; benchmarks/layer_metrics/
# gather_ms_per_step.lr, scatter_ms_per_step.lr and hashed_pass_roofline
# read them
SSGD_GATHER = "tda.ssgd.gather"    # margins (w at each row's slots),
#                                    labels, validity, residuals
SSGD_SCATTER = "tda.ssgd.scatter"  # the residuals added up slot by slot
# inside either pass of an indexed table: whatever serves the fields
# whose ranges are past VMEM (pallas_hashed.field_form 'hbm': the DMA
# gather kernel, XLA's scatter-add);
# benchmarks/layer_metrics/hbm_fields_ms_per_step.lr reads it, and
# update_ms_per_step.lr the 219 MB update under SSGD_UPDATE
SSGD_TABLE_HBM = "tda.ssgd.table_hbm"
# inside either pass of a table of ragged (feature, value) rows
# (ops/pairs.py): the sum over a row's pairs and a row's residual handed
# back to its pairs; benchmarks/layer_metrics/rowsum_ms_per_step.lr
# reads it. The pairs against the table are under SSGD_TABLE_HBM there.
SSGD_ROWSUM = "tda.ssgd.rowsum"
# the three parts of a fused PageRank sweep (models/pagerank.py); the
# benchmark's spmv_ms_per_sweep.graph and pagerank_spmv_roofline read
# the first, sync_ms_per_sweep.graph and sync_exposed_ms_per_sweep.graph
# the last
PAGERANK_SPMV = "tda.pagerank.spmv"      # the ranks' table and the kernel
PAGERANK_UPDATE = "tda.pagerank.update"  # a shard's table back to a
#                                          vector, the teleport on its range
PAGERANK_SYNC = "tda.pagerank.sync"      # across shards: the dangling
#                                          mass (a scalar psum), the new
#                                          ranges all-gathered
# the parts of a Lloyd iteration (models/kmeans.py)
KMEANS_ASSIGN = "tda.kmeans.assign"  # distances and argmin; on the lanes
#                                      layout the one kernel that also
#                                      accumulates the partial sums; on
#                                      the wide one the assign kernel
#                                      and the split of the centres
KMEANS_STATS = "tda.kmeans.stats"    # one-hot sums and counts; on the
#                                      lanes layout what is left outside
#                                      the kernel: the partial sums'
#                                      fold; on the wide one the stats
#                                      kernel and its transpose
KMEANS_SYNC = "tda.kmeans.sync"      # the psum of (sums, counts)
KMEANS_UPDATE = "tda.kmeans.update"  # new centres, the convergence shift
# the parts of a sparse ALS half-sweep (ops/als_sparse.py); the
# benchmark's gather_ms_per_sweep.als, gram_ms_per_sweep.als,
# solve_ms_per_sweep.als and the two als_*_roofline metrics read them
ALS_GATHER = "tda.als.gather"  # the other side's rows fetched by index
ALS_GRAM = "tda.als.gram"      # the rating's and validity's lanes, the
#                                per-owner products on the MXU, the
#                                pieces of a large owner added up
ALS_SOLVE = "tda.als.solve"    # the ridge, the Cholesky factorisation
#                                along the lanes, the two substitutions
ALS_SYNC = "tda.als.sync"      # the all-gather of a half's rows and the
#                                psum of its sums; empty on one shard
ALS_UPDATE = "tda.als.update"  # what is left: the rows' write, the
#                                training and held-out RMSE
# the two parts of a dense closure round (models/transitive_closure.py);
# the benchmark's compose_ms_per_round.closure and closure_mxu_roofline
# read the first, count_ms_per_round.closure the second
CLOSURE_COMPOSE = "tda.closure.compose"  # the boolean product or-ed into
#                                          the paths: the byte kernel with
#                                          its per-tile counts, or XLA's
#                                          product and the rows' sums
CLOSURE_COUNT = "tda.closure.count"      # the partials' sum in two words
#                                          and the fixpoint test
# the two other parts of a pair-set closure round (the same module's
# make_sparse_round_fn, which counts under CLOSURE_COUNT too); the
# benchmark's join_ms_per_round.closure reads the first,
# distinct_ms_per_round.closure the second, closure_sparse_roofline both
CLOSURE_JOIN = "tda.closure.join"          # delta joined with the arcs:
#                                            the segmented expand and its
#                                            gathers
CLOSURE_DISTINCT = "tda.closure.distinct"  # the one sort of set and
#                                            candidates, the duplicates
#                                            marked, the merged set and
#                                            the new pairs brought to
#                                            the front
