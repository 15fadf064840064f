"""Command-line entry points — one subcommand per reference script.

The reference exposes its workloads as ``python <script>.py`` with
module-global knobs edited by hand (SURVEY.md §5 config); here every knob
is a CLI flag with the same name and default, e.g.::

    python -m tpu_distalg.cli ssgd --n-iterations 1500 --eta 0.1 \
        --mini-batch-fraction 0.1 --plot ssgd_acc_plot.png

Run ``--emulate N`` to execute on N virtual CPU devices (Spark
``local[*]``-style) when no TPU is attached.
"""

from __future__ import annotations

import argparse
import re
import sys
import time


def parse_mesh_shape(text: str) -> tuple[int, int]:
    """``'DxM'`` → ``(data, model)`` — the 2-D mesh config the
    partition-rule engine makes a knob instead of a code path
    (``parallel/partition.py``). ``'8x1'`` is pure data parallel,
    ``'2x4'`` puts 4-way model parallelism inside each data replica."""
    m = re.fullmatch(r"(\d+)[xX](\d+)", text.strip())
    if not m or int(m.group(1)) < 1 or int(m.group(2)) < 1:
        raise ValueError(
            f"--mesh-shape wants DATAxMODEL (e.g. 4x2), got {text!r}")
    return int(m.group(1)), int(m.group(2))


def _add_mesh_shape(p) -> None:
    """The one definition of the ``--mesh-shape`` flag (six subcommands
    carry it — a copy per parser would drift like the ``--n-slices``
    duplication it extends)."""
    p.add_argument("--mesh-shape", type=str, default=None,
                   metavar="DxM",
                   help="full 2-D mesh geometry data x model (e.g. "
                        "2x2); placement falls out of the workload's "
                        "partition rule table — replaces --n-slices")


def _mesh(args):
    from tpu_distalg.parallel import MeshContext

    # MeshContext is the SparkSession analogue: the one runtime object
    # every workload receives (its .mesh)
    shape = getattr(args, "mesh_shape", None)
    if shape:
        if getattr(args, "n_slices", 0) > 0:
            raise SystemExit(
                "--mesh-shape and --n-slices both set: --mesh-shape "
                "IS the full (data x model) geometry; drop --n-slices")
        try:
            data, model = parse_mesh_shape(shape)
        except ValueError as e:
            raise SystemExit(str(e)) from None
        # only the workloads whose rule tables name a model-axis
        # placement consume model>1 — everywhere else those devices
        # would be silent passengers, so say so instead of wasting
        # them quietly (ssgd validates and engages the tp split in
        # its own branch; als shards V over the model axis)
        if model > 1 and getattr(args, "cmd", None) not in (
                "ssgd", "als"):
            print(
                f"[mesh] warning: --mesh-shape {data}x{model} puts "
                f"{model}-way model parallelism on a workload whose "
                f"rule table has no model-axis placement — those "
                f"devices will idle; use --mesh-shape "
                f"{data * model}x1 (or --n-slices {data * model}) "
                f"for full data parallelism", file=sys.stderr)
        return MeshContext.create(data=data, model=model).mesh
    return MeshContext.create(
        data=args.n_slices if args.n_slices > 0 else None
    ).mesh


def _add_common(p, n_iterations, eta=None, frac=None, sync=False):
    p.add_argument("--n-slices", type=int, default=0,
                   help="data-axis size; 0 = all devices")
    _add_mesh_shape(p)
    p.add_argument("--n-iterations", type=int, default=n_iterations)
    if eta is not None:
        p.add_argument("--eta", type=float, default=eta)
        # the gradient/parameter sync schedule (parallel/comms.py) —
        # SGD-family trainers only (the others have no per-round model
        # sync to re-schedule)
        p.add_argument(
            "--comm", default="dense", metavar="SCHED",
            help="cross-shard sync schedule: dense (bitwise the "
                 "classic psum — default), bucketed[:elems] "
                 "(ppermute-chunk ring), hier[:groups] "
                 "(reduce-scatter intra-group / ring across groups / "
                 "all-gather), bf16, int8[:seed[:bucket]] (native "
                 "int8 wire: seeded stochastic rounding, int8 in both "
                 "ring phases), topk[:frac] (sparse allreduce + error "
                 "feedback). bucketed/int8 overlap their bucket "
                 "exchange with compute by default; append @seq for "
                 "the bitwise-identical sequential exchange (a no-op "
                 "for the single-bucket topk/hier). Emits "
                 "comm.bytes_wire/bytes_logical/rounds telemetry "
                 "counters per run")
    if sync:
        # stale-synchronous & elastic training (parallel/ssp.py +
        # parallel/membership.py) — the SGD-family trainers only
        p.add_argument(
            "--sync", default="bsp", metavar="MODE",
            help="synchronization discipline: bsp (lock-step, one "
                 "collective per step/round — bitwise the classic "
                 "trainer; default) or ssp[:s[:decay]] (stale-"
                 "synchronous: shards run up to s steps ahead of the "
                 "slowest, the merge runs once per s-tick window with "
                 "staleness-weighted averaging / delayed gradients, "
                 "and a clock vector gates bound violations — a "
                 "straggler no longer serializes every step). Seeded "
                 "shard:straggle / shard:leave --fault-plan rules "
                 "compile into deterministic straggler and elastic-"
                 "membership schedules; the same plan replays bitwise. "
                 "A checkpointed ssp run resumed with a different "
                 "--n-slices renegotiates the ring (membership epoch) "
                 "instead of rejecting")
    if frac is not None:
        p.add_argument("--mini-batch-fraction", type=float, default=frac)
        # TPU perf knobs; the samplers are ssgd.SAMPLERS, which says
        # what each is (the local-update family takes the same three)
        from tpu_distalg.models.ssgd import SAMPLERS

        p.add_argument("--sampler", default="bernoulli",
                       choices=SAMPLERS)
        p.add_argument("--x-dtype", default="float32",
                       choices=["float32", "bfloat16"])
        p.add_argument("--gather-block-rows", type=int, default=1024)
        p.add_argument("--fused-pack", type=int, default=16)
        p.add_argument("--shuffle-seed", type=int, default=None)
        p.add_argument("--mega-steps", type=int, default=None,
                       help="steps per megakernel launch "
                            "(sampler=fused_train); default auto-picks "
                            "the largest divisor of --n-iterations "
                            "<= 125 so any iteration count works")
    p.add_argument("--plot", type=str, default=None,
                   help="save an accuracy plot PNG here")
    p.add_argument("--quiet", action="store_true")
    _add_ckpt(p, 500)


def _add_data_backend(p, block_rows: int):
    """The data-placement knob (tpu_distalg/data/): where the workload's
    dataset bytes live — on-device HBM, host RAM, or a disk packed
    cache streamed block by block. A PLACEMENT knob, not an algorithm
    knob: staged batches are bitwise-identical across backends."""
    p.add_argument("--data-backend", default="resident",
                   choices=["resident", "virtual", "streamed"],
                   help="where the dataset lives: resident = device "
                        "HBM, virtual = host RAM, streamed = disk "
                        "packed cache (needs --stream-cache); virtual/"
                        "streamed stage sampled blocks through the "
                        "prefetch pipeline (tpu_distalg/data/)")
    p.add_argument("--stream-cache", type=str, default=None,
                   metavar="PATH",
                   help="packed-cache path for --data-backend "
                        "streamed (created on first use)")
    p.add_argument("--block-rows", type=int, default=block_rows,
                   help="rows per gathered block (the out-of-core "
                        "transfer granularity)")


def _add_telemetry(p):
    """Telemetry + chaos flags — on EVERY subcommand: structured JSONL
    runtime events (marks, spans, heartbeats, stalls, restarts) for the
    run, summarized by ``tda report DIR`` (tpu_distalg/telemetry/), and
    the deterministic fault-injection plan (tpu_distalg/faults/)."""
    p.add_argument("--telemetry-dir", type=str, default=None,
                   metavar="DIR",
                   help="write structured JSONL runtime events here "
                        "($TDA_TELEMETRY_DIR is the default when "
                        "unset); summarize with 'tda report DIR'")
    p.add_argument("--fault-plan", type=str, default=None,
                   metavar="SPEC",
                   help="deterministic fault-injection plan: inline "
                        "'seed=N;point@hit=kind[:arg];...' or a JSON "
                        "plan file ($TDA_FAULT_PLAN is the default; "
                        "points: ckpt:write, ckpt:read, cache:write, "
                        "data:gather, data:h2d, backend:init, "
                        "segment:run; kinds: oserror, hang, corrupt, "
                        "kill). The same plan+seed replays the same "
                        "failure sequence bitwise — see 'tda chaos'")
    p.add_argument("--tune", type=str, default="off", metavar="MODE",
                   help="platform-aware geometry (tpu_distalg/tune/): "
                        "'off' = the hand-pinned default tables, "
                        "'auto' = resolve comm schedule, bucket "
                        "elems, mesh shape, ps-shards/mode, block "
                        "sizes and pull-refresh cadence from this "
                        "rig's newest measured profile (run 'tda "
                        "tune' once), or a RIGPROFILE_*.json path. "
                        "Explicit flags always win; every resolved "
                        "knob logs a tune.* event with its WHY. "
                        "Tuning changes geometry, never determinism")


def _add_ckpt(p, every_default):
    """Checkpoint/watchdog flags — on EVERY subcommand, optimizer or
    not: the task-retry capability Spark gives every reference script
    (r4 verdict ask #5). State is tiny in each case (weights / centers
    / rank vector / path buffer / factor matrices)."""
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   help="segmented checkpoint/resume directory")
    p.add_argument("--checkpoint-every", type=int, default=every_default)
    p.add_argument("--max-restarts", type=int, default=0,
                   help="auto-restart the run up to N times on crash or "
                        "NaN-guard trip; with --checkpoint-dir each "
                        "restart resumes from the latest checkpoint "
                        "(bitwise-identical to an uninterrupted run)")
    _add_telemetry(p)


def _centers_line(centers, whole: int = 4096) -> str:
    """What ``tda kmeans`` prints of its centres: all of them up to
    ``whole`` numbers, else their shape and a summary (a codebook of
    4096 x 784 is 3.2M numbers)."""
    import numpy as np

    c = np.asarray(centers)
    if c.size <= whole:
        return f"Final centers: {c.tolist()}"
    norms = np.linalg.norm(c, axis=1)
    return (f"Final centers: {c.shape[0]} x {c.shape[1]} "
            f"{c.dtype} (not printed: {c.size} numbers); norm min "
            f"{norms.min():.6g} mean {norms.mean():.6g} max "
            f"{norms.max():.6g}; first {c[0, :4].tolist()} ...")


def _report_optimizer(name, res, args, t):
    from tpu_distalg.utils import metrics

    if hasattr(res, "heldout_log_loss"):
        # a weight table (hashed or indexed) is not printed
        print(res.forms)
        print(f"Held-out accuracy: {res.heldout_acc:.6f}  log-loss: "
              f"{res.heldout_log_loss:.6f}")
    else:
        if not args.quiet:
            print(f"Final w: {list(map(float, res.w))}")
        print(f"Final acc: {res.final_acc:.6f}")
    print(f"[{name}] {args.n_iterations} iterations in {t:.3f}s "
          f"({args.n_iterations / t:.1f} steps/s)")
    if args.plot:
        metrics.draw_acc_plot(res.accs, args.plot)
        print(f"saved plot: {args.plot}")


def main(argv=None):
    parser = argparse.ArgumentParser(prog="tpu_distalg")
    parser.add_argument("--emulate", type=int, default=0, metavar="N",
                        help="run on N virtual CPU devices")
    parser.add_argument("--profile", type=str, default=None, metavar="DIR",
                        help="capture a jax.profiler device trace of the "
                             "run into DIR (TensorBoard / Perfetto)")
    parser.add_argument("--multihost", action="store_true",
                        help="initialise the multi-host runtime "
                             "(jax.distributed over DCN) before building "
                             "the mesh; run the same command on every "
                             "host of the slice group")
    parser.add_argument("--coordinator-address", type=str, default=None,
                        help="host:port of process 0's coordinator "
                             "(with --multihost); omit on TPU pods and "
                             "managed clusters, where jax.distributed "
                             "auto-detects the topology")
    parser.add_argument("--num-processes", type=int, default=None,
                        help="total process count (with "
                             "--coordinator-address)")
    parser.add_argument("--process-id", type=int, default=None,
                        help="this process's rank (with "
                             "--coordinator-address)")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("lr", help="full-batch logistic regression")
    _add_common(p, 1500, eta=0.1)

    p = sub.add_parser("ssgd", help="synchronous minibatch SGD")
    _add_common(p, 1500, eta=0.1, frac=0.1, sync=True)
    p.add_argument("--lam", type=float, default=0.0)
    p.add_argument("--reg-type", default="l2",
                   choices=["none", "l2", "l1", "elastic_net"])
    p.add_argument("--stream-cache", type=str, default=None,
                   metavar="PATH",
                   help="train the streamed >HBM path from a disk-"
                        "backed packed dataset at PATH (created via "
                        "utils.datasets.streamed_packed_cache if "
                        "missing — see --stream-rows); sampled blocks "
                        "are host-gathered and staged per step "
                        "(models/ssgd_stream.py). Ignores --sampler/"
                        "--x-dtype/--shuffle-seed (the cache fixes "
                        "the bf16 dtype and row layout); rejects "
                        "--mega-steps.")
    p.add_argument("--stream-rows", type=int, default=1 << 22,
                   help="rows to generate when --stream-cache is new")
    p.add_argument("--hashed-rows", type=int, default=0, metavar="N",
                   help="train on N seeded click-log rows made ON "
                        "DEVICE whose fields are hashed one-hot into a "
                        "table of 2**--hash-bits weights (a row is "
                        "--fields int32 slots and a label, not "
                        "columns: models/ssgd.py's second row format). "
                        "The rows decide the trainer: block-sampled BSP "
                        "(--sampler is taken as fused_gather), held-out "
                        "accuracy and log-loss printed at the end")
    p.add_argument("--fields", type=int, default=39,
                   help="fields a row for --hashed-rows / "
                        "--indexed-rows (39: Criteo's 13 integer + 26 "
                        "categorical)")
    p.add_argument("--hash-bits", type=int, default=20,
                   help="log2 of the weight table for --hashed-rows")
    p.add_argument("--indexed-rows", type=int, default=0, metavar="N",
                   help="as --hashed-rows, but nothing is hashed: the "
                        "fields' ranges lie end to end in the weight "
                        "table and every value of every field is its "
                        "own weight (a LIBSVM one-hot file's indices; "
                        "Criteo's 39 fields are 33.8M weights, KDD Cup "
                        "2012's eleven 54.7M). A table wider than VMEM "
                        "stays in HBM; each field takes the form its "
                        "size gives it (by value, by address in VMEM, "
                        "in HBM: ops/pallas_hashed.field_form), which "
                        "tda report prints; no option chooses a form")
    p.add_argument("--field-values", default=None, metavar="N,N,...",
                   help="distinct values of each field for "
                        "--indexed-rows (their count is --fields); "
                        "default: the click log's cardinalities")
    p.add_argument("--row-format", default=None, choices=["pairs"],
                   help="'pairs' trains on --pair-rows ragged rows, "
                        "each a list of (int32 feature, float32 value) "
                        "pairs of its own length, unit-length rows: a "
                        "LIBSVM file's SparseVectors (webspam's "
                        "trigrams), the weights in HBM, a block a fixed "
                        "number of pair slots that holds whole rows "
                        "(ops/pairs.py); tda report prints the rows, "
                        "pairs, padding and the form of each pass. The "
                        "two one-hot formats are named by their tables, "
                        "--hashed-rows and --indexed-rows")
    p.add_argument("--pair-rows", type=int, default=1 << 14, metavar="N",
                   help="rows of the --row-format pairs table")
    p.add_argument("--features", type=int, default=1 << 20, metavar="D",
                   help="features (weights) of the pairs table; a "
                        "pair's feature is a power-law rank scattered "
                        "over the ids by a fixed bijection")
    p.add_argument("--length-mu", type=float, default=5.0,
                   help="mu of the pairs table's row lengths: "
                        "log-normal, sigma 1, clipped to --min-pairs .. "
                        "--max-pairs (5.0: 245 pairs a row on average)")
    p.add_argument("--min-pairs", type=int, default=8)
    p.add_argument("--max-pairs", type=int, default=1 << 16)
    p.add_argument("--pair-block-slots", type=int, default=1 << 18,
                   help="pair slots a block of the pairs table (whole "
                        "vectors of 128; at least the longest row)")

    for name in ("ma", "bmuf", "easgd"):
        p = sub.add_parser(name)
        _add_common(p, 1500 if name == "easgd" else 300, eta=0.1,
                    frac=0.1, sync=True)
        p.add_argument("--n-local-iterations", type=int,
                       default=1 if name == "easgd" else 5)
        p.add_argument("--resample-per-local-step", action="store_true")

    p = sub.add_parser("kmeans")
    p.add_argument("--n-slices", type=int, default=0)
    _add_mesh_shape(p)
    p.add_argument("--k", type=int, default=2)
    p.add_argument("--n-iterations", type=int, default=5)
    p.add_argument("--converge-dist", type=float, default=None)
    p.add_argument("--n-points", type=int, default=0,
                   help="0 = the reference's toy 6x2 matrix; else a "
                        "Gaussian mixture of this many points "
                        "(host-materialized, like the reference)")
    p.add_argument("--scale-points", type=int, default=0,
                   help="scale path: synthesize this many mixture "
                        "points ON DEVICE (host RAM O(k); overrides "
                        "--n-points)")
    p.add_argument("--dim", type=int, default=16,
                   help="point dimension for --scale-points")
    p.add_argument("--generating-clusters", type=int, default=0,
                   help="components of the --scale-points mixture "
                        "(0 = as many as --k; HiBench draws 5 and "
                        "fits 10, a codebook fits thousands round a "
                        "few)")
    p.add_argument("--plot", type=str, default=None,
                   help="save a cluster scatter PNG (2-D data)")
    _add_data_backend(p, block_rows=2048)
    p.add_argument("--mini-batch-blocks", type=int, default=4,
                   help="blocks per shard per minibatch step "
                        "(minibatch engine)")
    p.add_argument("--minibatch-steps", type=int, default=0,
                   help="run the minibatch engine for N steps over the "
                        "ShardedDataset (0 = classic full-batch Lloyd "
                        "when --data-backend resident, 100 otherwise)")
    _add_ckpt(p, 100)

    p = sub.add_parser("pagerank")
    p.add_argument("--n-slices", type=int, default=0)
    _add_mesh_shape(p)
    p.add_argument("--n-iterations", type=int, default=10)
    p.add_argument("--q", type=float, default=0.15)
    p.add_argument("--mode", default=None,
                   choices=["reference", "standard"],
                   help="default: reference for the resident backend, "
                        "standard for the streamed/virtual engine "
                        "(reference-parity needs resident per-vertex "
                        "receive masks)")
    p.add_argument("--scatter", default="auto",
                   choices=["auto", "pallas", "xla", "spmv"],
                   help="standard-mode sweep path: the Pallas windowed "
                        "one-hot-MXU scatter (when the graph admits a "
                        "window plan), the XLA segment_sum, or the "
                        "fully-fused tiled SpMV kernel ('spmv': gather "
                        "AND scatter in one Pallas launch)")
    p.add_argument("--n-vertices", type=int, default=0,
                   help="0 = the reference's 4-edge toy graph; else an "
                        "Erdős–Rényi graph of this many vertices")
    p.add_argument("--rmat-scale", type=int, default=0, metavar="SCALE",
                   help="rank a Graph500 Kronecker graph of 2**SCALE "
                        "vertices drawn, deduplicated and planned on "
                        "the device (overrides --n-vertices; standard "
                        "mode, the fused sweep; --seed picks the graph)")
    p.add_argument("--edge-factor", type=int, default=16,
                   help="generated edges a vertex (--rmat-scale)")
    p.add_argument("--rmat-abcd", type=float, nargs=4,
                   default=None, metavar=("A", "B", "C", "D"),
                   help="the generator's quadrant probabilities "
                        "(default Graph500's 0.57 0.19 0.19 0.05)")
    p.add_argument("--seed", type=int, default=0,
                   help="the generated graph's seed (--rmat-scale)")
    p.add_argument("--edge-file", type=str, default=None,
                   help="load the graph from a '#'-commented whitespace "
                        "edge-list file (overrides --n-vertices); parsed "
                        "by the native C++ ingest runtime")
    p.add_argument("--edge-capacity", type=int, default=1 << 24,
                   help="max edges the file parser may return")
    p.add_argument("--data-backend", default="resident",
                   choices=["resident", "virtual", "streamed"],
                   help="where the EDGE SET lives: resident = device "
                        "HBM (the fused-SpMV/Pallas/XLA sweeps; "
                        "the fused sweep keeps 4 B a vertex in VMEM "
                        "and caps itself at 26M vertices), streamed = a dst-sorted CSR edge-"
                        "block disk cache swept out-of-core "
                        "(tpu_distalg/graphs/ — only O(V) state in "
                        "HBM; sparse rank combine), virtual = the "
                        "same engine from host RAM. A resident "
                        "request past the guard warns and degrades "
                        "to streamed instead of dying")
    p.add_argument("--stream-cache", type=str, default=None,
                   metavar="PATH",
                   help="edge-block cache path for the streamed/"
                        "virtual engine (default: a geometry-keyed "
                        "path under $TMPDIR, built on first use)")
    p.add_argument("--block-edges", type=int, default=1 << 16,
                   help="edges per streamed block (the out-of-core "
                        "transfer granularity)")
    p.add_argument("--combine", default="auto",
                   choices=["auto", "sparse", "dense"],
                   help="streamed engine's cross-shard rank combine: "
                        "sparse = ring all-gather of each shard's "
                        "distinct-destination (value, index) pairs "
                        "(comms.sparse_allreduce — the power-law "
                        "win), dense = O(V) psum; auto picks by wire-"
                        "byte accounting")
    _add_ckpt(p, 5)

    p = sub.add_parser(
        "closure",
        help="transitive closure: a V x V byte matrix whose round "
             "doubles the path length (dense), or a sorted set of "
             "pairs whose round joins the pairs the last round found "
             "new with the arcs (--sparse, --tree-height)")
    p.add_argument("--n-slices", type=int, default=0)
    _add_mesh_shape(p)
    p.add_argument("--n-vertices", type=int, default=0)
    p.add_argument("--grid-side", type=int, default=0,
                   help="close BigDatalog's Grid<N> (SIGMOD'16, Table "
                        "2): an (N+1) x (N+1) grid, arcs right and "
                        "down, labels permuted by --seed; the form is "
                        "picked from the bytes each would hold "
                        "(Grid250: 63 001 vertices, 1 000 140 875 "
                        "pairs, dense)")
    p.add_argument("--tree-height", type=int, default=0,
                   help="close BigDatalog's Tree<N> (SIGMOD'16, Table "
                        "2): a tree of N + 2 levels whose non-leaf "
                        "vertices have 2 to 6 children, its shape and "
                        "labels drawn from --seed; the form is picked "
                        "from the bytes each would hold and the three "
                        "buffers sized from the tree's closed form "
                        "(Tree17: 13 766 856 vertices, 237 977 708 "
                        "pairs: the pair set, 18 rounds)")
    p.add_argument("--seed", type=int, default=0,
                   help="permutes the grid's vertex labels (--grid-side);"
                        " draws the tree's shape and labels "
                        "(--tree-height)")
    p.add_argument("--sparse", action="store_true",
                   help="the pair-set closure (O(closure) memory: what "
                        "a matrix of V x V bytes cannot hold): the set "
                        "as a sorted buffer of (x, z) pairs, a round "
                        "semi-naive: the pairs the round before found "
                        "new joined with the arcs, one sort of set and "
                        "candidates, what the set held taken out, until "
                        "a round finds nothing new. "
                        "NOTE: with "
                        "--n-vertices the generated graph is a chain "
                        "forest, not the dense mode's Erdős–Rényi graph "
                        "(an ER closure is an inherently quadratic "
                        "output); results are not comparable across "
                        "modes")
    p.add_argument("--capacity", type=int, default=0,
                   help="pairs the pair set can hold (a static shape); "
                        "0 = 8x edges, or the next power of two over the "
                        "closed form with --tree-height. A round's new "
                        "pairs get as many slots and its candidates "
                        "twice as many (the tree: the next power of two "
                        "over its arcs each); a closure, or a round, "
                        "that overflows fails the run, it is never "
                        "truncated")
    _add_ckpt(p, 8)

    p = sub.add_parser("als", help="ALS matrix decomposition")
    p.add_argument("--n-slices", type=int, default=0)
    _add_mesh_shape(p)
    p.add_argument("--m", type=int, default=100)
    p.add_argument("--n", type=int, default=500)
    p.add_argument("--k", type=int, default=10)
    p.add_argument("--lam", type=float, default=0.01)
    p.add_argument("--n-iterations", type=int, default=5)
    p.add_argument("--ratings", type=int, default=0,
                   help="R as a seeded list of this many explicit "
                        "ratings of --users by --items, made and held "
                        "on the device (power-law degrees from 20, a "
                        "planted rank-k model on a 0 to 100 scale); 0 "
                        "= the dense rank-k R of --m x --n")
    p.add_argument("--users", type=int, default=0)
    p.add_argument("--items", type=int, default=0)
    p.add_argument("--heldout", type=int, default=-1,
                   help="with --ratings: pairs never trained on that "
                        "are scored every sweep; -1 = ratings / 64")
    p.add_argument("--data-seed", type=int, default=0)
    _add_data_backend(p, block_rows=256)
    p.add_argument("--rmse-every", type=int, default=1,
                   help="streamed/virtual backends: stream one extra "
                        "RMSE evaluation pass every N sweeps (0 = once "
                        "after the final sweep — each pass re-reads R)")
    _add_ckpt(p, 5)

    p = sub.add_parser(
        "serve",
        help="micro-batched online serving from checkpointed "
             "artifacts: bounded queue -> deadline-or-size dispatch -> "
             "one batched predict per micro-batch -> scatter replies; "
             "ALS top-k rides the fused Pallas matmul+top-k kernel "
             "with model-axis-sharded item factors; runs a closed-loop "
             "demo load and prints qps/p50/p99")
    p.add_argument("--artifact", action="append", required=True,
                   metavar="PATH",
                   help="checkpoint directory to serve (repeatable); "
                        "training CLIs run with --checkpoint-dir print "
                        "the machine-readable 'artifact_path: PATH' "
                        "line this flag consumes")
    p.add_argument("--n-slices", type=int, default=0,
                   help="data-axis size; 0 = all devices")
    p.add_argument("--model-slices", type=int, default=1,
                   help="mesh model-axis size: ALS item factors are "
                        "sharded across it; per-shard top-k candidates "
                        "merge via the --comm schedule")
    p.add_argument("--max-batch", type=int, default=16,
                   help="dispatch a micro-batch at this many requests")
    p.add_argument("--max-delay-ms", type=float, default=5.0,
                   help="... or this many ms after its first request")
    p.add_argument("--queue-depth", type=int, default=128,
                   help="bounded request queue; a full queue SHEDS "
                        "(reply carries ServeOverloadError) instead of "
                        "growing or dying")
    p.add_argument("--k-top", type=int, default=10,
                   help="ALS recommendations per request")
    p.add_argument("--comm", default="sparse",
                   choices=["sparse", "dense"],
                   help="ALS cross-shard candidate merge: sparse = "
                        "ring all-gather of each shard's k (value, "
                        "index) pairs (8k(S-1) B/request), dense = "
                        "all-gather of the full score blocks (the O(N) "
                        "baseline)")
    p.add_argument("--requests", type=int, default=256,
                   help="closed-loop demo load per served model")
    p.add_argument("--concurrency", type=int, default=4,
                   help="closed-loop worker count")
    _add_telemetry(p)

    p = sub.add_parser(
        "cluster",
        help="multi-process elastic runtime (tpu_distalg/cluster/): a "
             "coordinator process plus N worker processes exchanging "
             "staleness-weighted deltas with a parameter-server tier "
             "over a framed TCP transport — kill -9 a worker mid-"
             "window and training continues at reduced quorum; a "
             "fresh worker rejoins by pulling the center")
    p.add_argument("--role", default="local",
                   choices=["coordinator", "worker", "local",
                            "replica", "router"],
                   help="coordinator = serve rendezvous/clock/PS on "
                        "--host:--port; worker = join a coordinator at "
                        "--connect; local = spawn a coordinator plus "
                        "--workers N workers on this machine (the "
                        "test/bench mode); replica = one serving "
                        "replica of the distributed serving plane "
                        "(loads --artifact, scores over the framed "
                        "transport, hot-swappable); router = the "
                        "serving front end dispatching at --replicas")
    p.add_argument("--workers", type=int, default=3,
                   help="worker slot count (coordinator/local roles)")
    p.add_argument("--spawn", default="process",
                   choices=["process", "thread"],
                   help="local role: real worker processes (kill -9 is "
                        "the genuine article) or threads (same "
                        "protocol/sockets, fast for tests)")
    p.add_argument("--coordinator-spawn", default="inproc",
                   choices=["inproc", "process"],
                   help="local role: run the coordinator in-process "
                        "(a cluster:coordinator kill cell slams its "
                        "sockets) or as a REAL subprocess (the kill "
                        "is a genuine kill -9 of the control plane; "
                        "the launcher respawns it on the same port "
                        "and it recovers from the durable WAL — "
                        "needs --checkpoint-dir)")
    p.add_argument("--connect", type=str, default=None,
                   metavar="HOST:PORT",
                   help="worker role: the coordinator's address")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="coordinator bind port (0 = ephemeral, "
                        "printed at start)")
    p.add_argument("--slot", type=int, default=None,
                   help="worker role: requested slot (default: any "
                        "free)")
    p.add_argument("--rejoin", action="store_true",
                   help="worker role: this is a replacement for a "
                        "departed slot")
    p.add_argument("--admit-at", type=int, default=None,
                   help="worker role: pin admission to this window "
                        "(the launcher's replay-determinism hook)")
    p.add_argument("--n-windows", type=int, default=24,
                   help="merge windows to train (each = s local ticks "
                        "per worker)")
    p.add_argument("--sync", default="ssp:4", metavar="MODE",
                   help="staleness discipline ssp[:s[:decay]] — the "
                        "cluster is stale-synchronous by construction "
                        "(parallel/ssp.py semantics over the wire); "
                        "s = ticks per window AND the clock gate's "
                        "bound, decay = the PS merge weight decay^age")
    p.add_argument("--algo", default="ssgd",
                   choices=["ssgd", "local_sgd"],
                   help="the existing trainer each worker wraps "
                        "between push/pull seams")
    p.add_argument("--ps-shards", type=int, default=2,
                   help="parameter-server tier width: the center is "
                        "split across this many PS shards per the "
                        "model's partition rule table (uneven splits "
                        "are first-class)")
    p.add_argument("--comm", default="dense", metavar="SCHED",
                   help="cluster wire schedule: dense (f32 snapshots, "
                        "the pre-compression protocol bit-for-bit), "
                        "int8[:seed] (seeded stochastic rounding, "
                        "~1 byte/elem both directions) or topk[:frac] "
                        "((value,index) pairs with worker-side error "
                        "feedback; pulls ride the int8 codec) — "
                        "compressed pushes overlap the next window's "
                        "compute on a background sender; append @seq "
                        "to force synchronous pushes (e.g. int8@seq)")
    p.add_argument("--ps-mode", default="replicated",
                   choices=["replicated", "rowstore"],
                   help="PS tier state layout: replicated = each "
                        "shard holds dense slices, merges whole "
                        "deltas (the pre-rowstore protocol "
                        "bit-for-bit); rowstore = shards own disjoint "
                        "leading-dim row ranges (partition rule "
                        "table), pushes carry {leaf}.rows index "
                        "arrays and merge row-wise with per-row "
                        "versions — sparse pulls/pushes for models "
                        "bigger than one host")
    p.add_argument("--pull-refresh-windows", type=int, default=None,
                   metavar="N",
                   help="compressed-pull refresh cadence: every Nth "
                        "commit ships a dense version-pinned pull "
                        "bounding the pull-noise random walk "
                        "(default: the tuner's table value; --tune "
                        "auto re-derives it from the measured wire)")
    p.add_argument("--policy", default="elastic",
                   choices=["elastic", "restart"],
                   help="death handling: elastic = continue at "
                        "reduced quorum + rejoin; restart = abort and "
                        "respawn everything from the checkpoint (the "
                        "measured BSP-restart baseline)")
    p.add_argument("--rejoin-after", type=int, default=3,
                   help="local elastic role: windows a killed slot "
                        "stays away before its replacement is "
                        "admitted")
    p.add_argument("--heartbeat-timeout", type=float, default=5.0,
                   help="seconds of worker silence before the "
                        "coordinator declares it dead (EOF on its "
                        "connection is detected immediately)")
    p.add_argument("--heartbeat-interval", type=float, default=0.5,
                   help="seconds between worker liveness beats")
    p.add_argument("--rpc-deadline", type=float, default=30.0,
                   help="bound on any single blocking transport "
                        "round trip")
    p.add_argument("--reconnect-grace", type=float, default=1.0,
                   help="seconds a connection's EOF leaves its slot "
                        "SUSPECT before the death fires — the window "
                        "a reconnecting worker's re-dial has to race "
                        "the EOF sweep without burning a membership "
                        "epoch")
    p.add_argument("--n-rows", type=int, default=4096,
                   help="training rows of the shared synthetic task")
    p.add_argument("--train-json", type=str, default=None,
                   metavar="JSON",
                   help="coordinator role plumbing: the EXACT "
                        "TrainTask as JSON (the local launcher's "
                        "subprocess handoff — every field, not just "
                        "--algo/--n-rows; overrides both)")
    p.add_argument("--artifact", type=str, default=None,
                   metavar="CKPT_DIR",
                   help="replica role: checkpoint directory to serve "
                        "(the artifact_path: line a training CLI "
                        "prints)")
    p.add_argument("--replica-shards", type=int, default=1,
                   help="replica role: total model-axis shard count "
                        "of the fleet this replica belongs to")
    p.add_argument("--shard", type=int, default=0,
                   help="replica role: this replica's model-axis "
                        "shard index")
    p.add_argument("--k-top", type=int, default=10,
                   help="serving plane: top-k candidates per ALS "
                        "retrieval request")
    p.add_argument("--merge", default="sparse",
                   choices=["sparse", "dense"],
                   help="serving plane: cross-replica ALS candidate "
                        "merge — sparse (value,index) pair merge or "
                        "the dense score-block all-gather baseline")
    p.add_argument("--replicas", type=str, default=None,
                   metavar="HOST:PORT[,HOST:PORT...]",
                   help="router role: the replica fleet's addresses")
    p.add_argument("--dispatch", default="least_loaded",
                   choices=["least_loaded", "consistent_hash"],
                   help="router role: dispatch policy")
    p.add_argument("--serve-mode", default="routed",
                   choices=["routed", "sharded"],
                   help="router role: routed = each request to ONE "
                        "replica (redundancy, re-route on death); "
                        "sharded = fan out to every model-axis shard "
                        "and merge candidates")
    p.add_argument("--wal-dir", type=str, default=None,
                   metavar="DIR",
                   help="router role: durable admission/routing WAL — "
                        "a restarted router replays it and rebinds "
                        "the same port")
    p.add_argument("--deadline", type=float, default=600.0,
                   help="local/coordinator roles: give up if the run "
                        "is still incomplete after this many seconds")
    _add_ckpt(p, 8)

    p = sub.add_parser("mc", help="Monte-Carlo pi")
    p.add_argument("--n-slices", type=int, default=0)
    _add_mesh_shape(p)
    p.add_argument("--n", type=int, default=400_000)
    p.add_argument("--max-restarts", type=int, default=0,
                   help="retry the (stateless, deterministic) estimate "
                        "up to N times on a device crash")
    _add_telemetry(p)

    p = sub.add_parser(
        "chaos",
        help="run a small workload twice — undisturbed, then under an "
             "injected fault schedule with the full recovery stack "
             "armed — and verify the recovered final state is bitwise-"
             "equal (rc 1 on mismatch)")
    p.add_argument("--workload", default="lr",
                   choices=["lr", "ssgd", "kmeans", "als",
                            "kmeans_stream", "pagerank_stream",
                            "serve", "ssp", "cluster",
                            "cluster_serve", "rowstore"])
    p.add_argument("--n-slices", type=int, default=0)
    _add_mesh_shape(p)
    p.add_argument("--n-iterations", type=int, default=None,
                   help="override the workload's small default")
    p.add_argument("--checkpoint-every", type=int, default=None)
    p.add_argument("--max-restarts", type=int, default=3,
                   help="restart budget for the chaos run")
    p.add_argument("--spawn", default="thread",
                   choices=["thread", "process"],
                   help="cluster workload only: thread-mode workers "
                        "(fast smoke — the bench fast path runs this) "
                        "or real worker processes (a cluster:"
                        "coordinator kill is then a mid-window kill "
                        "of the in-process coordinator either way; "
                        "the genuine subprocess kill -9 is 'tda "
                        "cluster --coordinator-spawn process')")
    p.add_argument("--comm", default="dense", metavar="SCHED",
                   help="cluster/rowstore workloads only: the wire "
                        "schedule both the undisturbed and the chaos "
                        "run use (dense/int8[:seed]/topk[:frac]) — "
                        "the compression×chaos composition acceptance "
                        "is 'tda chaos --workload cluster --comm "
                        "int8' (and --workload rowstore for the "
                        "sparse row wire)")
    p.add_argument("--workdir", type=str, default=None,
                   help="checkpoint scratch directory (default: a "
                        "fresh temp dir, removed on success)")
    _add_telemetry(p)

    p = sub.add_parser(
        "tune",
        help="measure this rig — framed-TCP loopback bandwidth/RTT, "
             "host memcpy, matmul FLOP/s, host RAM, per---comm codec "
             "throughput, backend init time, optionally a device "
             "collective — and persist a versioned rig-tagged "
             "RigProfile JSON; every subcommand's '--tune auto' then "
             "resolves its geometry from the newest profile via the "
             "cost model (tune/resolve.py)")
    p.add_argument("--out-dir", type=str, default=None, metavar="DIR",
                   help="profile directory (default $TDA_PROFILE_DIR "
                        "or ./.tda_profiles)")
    p.add_argument("--seed", type=int, default=0,
                   help="measurement seed (profiles are seeded and "
                        "deterministic modulo the measured timings)")
    p.add_argument("--quick", action="store_true",
                   help="smaller working sets (smoke/CI tier; the "
                        "artifact records quick=true)")
    p.add_argument("--no-backend-init", action="store_true",
                   help="skip the subprocess-timed backend init "
                        "measurement (the slowest pass)")
    p.add_argument("--collective", action="store_true",
                   help="also measure a device collective (imports "
                        "jax and builds the mesh; omit for the "
                        "jax-free host-only profile)")
    p.add_argument("--n-slices", type=int, default=0,
                   help="with --collective: data-axis size; 0 = all "
                        "devices")
    _add_mesh_shape(p)
    p.add_argument("--telemetry-dir", type=str, default=None,
                   metavar="DIR",
                   help="record the profiling pass as telemetry "
                        "events (a 'tune' span)")

    p = sub.add_parser(
        "lint",
        help="static analysis for the framework's own invariants "
             "(TDA0xx rules: determinism, trace purity, concurrency, "
             "fault-seam coverage, Pallas hygiene); exits 1 on "
             "un-baselined violations; chain-runs ruff when installed")
    from tpu_distalg.analysis import cli as lint_cli

    lint_cli.add_parser_args(p)
    p.add_argument("--telemetry-dir", type=str, default=None,
                   metavar="DIR",
                   help="record the lint run as telemetry events "
                        "(a 'lint' span + per-rule counters)")

    p = sub.add_parser(
        "protocol",
        help="extract the cluster wire contract from source (frame "
             "kinds, payload keys, reply pairings, fencing, WAL "
             "records) as a deterministic table; --check pins "
             "docs/PROTOCOL.md against it")
    lint_cli.add_protocol_args(p)
    p.add_argument("--telemetry-dir", type=str, default=None,
                   metavar="DIR",
                   help="record the extraction as telemetry events "
                        "(a 'protocol' span)")

    p = sub.add_parser("report",
                       help="summarize a telemetry event log: phase "
                            "durations, stalls, backend-init attempts, "
                            "restarts, last heartbeat, metrics; "
                            "several dirs (or a parent of per-worker "
                            "dirs, e.g. a 'tda cluster' telemetry "
                            "root) render ONE merged report with "
                            "per-worker columns for the ssp.*/"
                            "cluster.* counters")
    p.add_argument("dir", nargs="+",
                   help="telemetry directory (of events-*.jsonl), one "
                        "event file, a parent directory of per-worker "
                        "telemetry dirs, or several of these")
    p.add_argument("--json", action="store_true",
                   help="print the full summary as JSON (for CI)")

    args = parser.parse_args(argv)

    if args.cmd == "lint":
        # pure source analysis — no backend, no mesh, no jax import
        from tpu_distalg import telemetry
        from tpu_distalg.analysis import cli as lint_cli

        telemetry.configure(args.telemetry_dir)
        return lint_cli.run_lint(args)

    if args.cmd == "protocol":
        # pure source analysis — no backend, no mesh, no jax import
        from tpu_distalg import telemetry
        from tpu_distalg.analysis import cli as lint_cli

        telemetry.configure(args.telemetry_dir)
        return lint_cli.run_protocol(args)

    if args.cmd == "tune":
        # host-only measurement — jax-free unless --collective asks
        # for the device pass
        from tpu_distalg import telemetry

        telemetry.configure(args.telemetry_dir)
        return _run_tune(args)

    if args.cmd == "report":
        # pure log analysis — no backend, no mesh, no jax import
        from tpu_distalg.telemetry import report as treport

        try:
            return treport.report_main(args.dir, as_json=args.json)
        except FileNotFoundError as e:
            # a typo'd path is the expected human error here — message,
            # not traceback
            print(f"tda report: {e}", file=sys.stderr)
            return 2

    from tpu_distalg import faults, telemetry

    tdir = getattr(args, "telemetry_dir", None)
    if args.cmd == "cluster" and args.role == "local" and tdir:
        # per-process telemetry layout: the coordinator's events land
        # under DIR/coordinator, each spawned worker's under
        # DIR/worker-N — 'tda report DIR' merges them with per-worker
        # columns (configured here so no stray root event file is
        # left behind)
        import os as _os

        tdir = _os.path.join(tdir, "coordinator")
    telemetry.configure(tdir)
    if args.cmd != "chaos":
        # the chaos harness owns the registry lifecycle itself (it runs
        # an undisturbed reference first); everywhere else the plan is
        # live for the whole run
        faults.configure(getattr(args, "fault_plan", None))
    if getattr(args, "checkpoint_dir", None):
        # SIGTERM/SIGINT become a graceful "checkpoint at the next
        # segment boundary, then exit PREEMPTED_RC" request
        # (faults/preempt.py) — the spot-VM/eviction contract every
        # production scheduler assumes. Only when a checkpoint dir
        # exists to satisfy the request: a non-checkpointed run has no
        # boundary to save at, and swallowing its SIGTERM/first-SIGINT
        # would make it HARDER to stop, not more graceful.
        faults.preempt.install()

    # platform-aware geometry: BEFORE the jax import and mesh build
    # (the resolver may set --mesh-shape) and with the raw argv in
    # hand so explicitly spelled flags win over resolved values
    _apply_tune(args, argv if argv is not None else sys.argv[1:])

    if args.emulate:
        from tpu_distalg.parallel.mesh import emulate_devices

        emulate_devices(args.emulate)

    if args.multihost:
        from tpu_distalg.parallel.mesh import multihost_initialize

        if (args.coordinator_address is None
                and (args.num_processes is not None
                     or args.process_id is not None)):
            parser.error(
                "--num-processes/--process-id require "
                "--coordinator-address (omit all three to auto-detect)"
            )
        kwargs = {
            k: v for k, v in (
                ("coordinator_address", args.coordinator_address),
                ("num_processes", args.num_processes),
                ("process_id", args.process_id),
            ) if v is not None
        }
        multihost_initialize(**kwargs)

    import jax  # after emulation setup

    from tpu_distalg.parallel.mesh import NoAcceleratorError
    from tpu_distalg.utils import compile_cache, profiling

    compile_cache.configure()

    # stall threshold well above the legitimately silent multi-minute
    # phases a healthy run contains (first XLA/Mosaic compiles, the
    # spmv plan's host sorts) — marks land at phase boundaries, not
    # inside them, and a stall line on a healthy run muddies the one
    # signal built to diagnose real hangs
    hb = telemetry.start_heartbeat(stall_after=600.0)
    try:
        with profiling.maybe_trace(args.profile):
            with telemetry.span(f"cli:{args.cmd}"):
                return _dispatch(args, jax)
    except NoAcceleratorError as e:
        # never a quiet CPU run: the default backend is not a TPU and
        # neither --emulate nor JAX_PLATFORMS=cpu asked for the host
        print(f"tda {args.cmd}: {e}", file=sys.stderr)
        return 1
    except faults.Preempted as e:
        # the graceful exit: the boundary checkpoint is already on
        # disk — re-running the same command resumes bitwise
        print(f"[preempted] checkpoint saved at step {e.step}; "
              f"re-run the same command to resume "
              f"(rc={faults.PREEMPTED_RC})", file=sys.stderr)
        return faults.PREEMPTED_RC
    finally:
        if hb is not None:
            hb.stop()


#: --tune knob -> (argparse dest, the CLI option strings that mark it
#: explicitly spelled). A knob is applied only where the subcommand
#: actually grew the flag; explicit flags always win.
_TUNE_FLAG_KNOBS = (
    ("comm", "comm", ("--comm",)),
    ("mesh_shape", "mesh_shape", ("--mesh-shape",)),
    ("ps_shards", "ps_shards", ("--ps-shards",)),
    ("ps_mode", "ps_mode", ("--ps-mode",)),
    ("block_rows", "block_rows", ("--block-rows",)),
    ("block_edges", "block_edges", ("--block-edges",)),
    ("pull_refresh_windows", "pull_refresh_windows",
     ("--pull-refresh-windows",)),
)


def _spelled_options(argv) -> set:
    """The long-option strings the user actually typed (``--opt`` and
    ``--opt=value`` spellings both count)."""
    return {a.split("=", 1)[0] for a in argv if a.startswith("--")}


def _tune_workload(args, ttune):
    """The workload descriptor the resolver prices this subcommand
    against."""
    if args.cmd == "cluster":
        # the coordinator's TrainTask: breast-cancer-shaped synthetic
        # two-class rows (30 features + bias), host TCP wire
        return ttune.Workload(
            d=31, n_rows=getattr(args, "n_rows", 0) or 0,
            n_workers=getattr(args, "workers", None)
            or ttune.defaults.CLUSTER_SLOTS,
            family="data", transport="host")
    family = {"kmeans": "kmeans", "als": "als", "pagerank": "graph",
              "closure": "graph"}.get(args.cmd, "data")
    # the reference task's model dim (breast-cancer: 30 features +
    # bias); graph/kmeans block knobs scale from bandwidth, not d
    return ttune.Workload(
        d=31, n_rows=getattr(args, "n_rows", 0) or 0,
        family=family, transport="device",
        n_shards=getattr(args, "n_slices", 0) or None)


def _apply_tune(args, argv) -> None:
    """Resolve ``--tune`` into the args namespace (tentpole wiring):
    load the requested profile, price the workload, and overwrite
    every resolved knob the subcommand exposes — except knobs the
    user explicitly spelled, which always win. Logged per knob as
    ``tune.*`` telemetry with the WHY."""
    mode = getattr(args, "tune", "off") or "off"
    if mode == "off":
        return
    import socket

    from tpu_distalg import tune as ttune

    if mode == "auto":
        profile, _ = ttune.newest_profile(rig=socket.gethostname())
        if profile is None:
            print("tda --tune auto: no profile for this rig (run "
                  "'tda tune' once); table defaults stand",
                  file=sys.stderr)
            return
    else:
        try:
            profile = ttune.load_profile(mode)
        except ttune.ProfileError as e:
            raise SystemExit(f"--tune: {e}")
    spelled = _spelled_options(argv)
    explicit = {
        knob: getattr(args, dest)
        for knob, dest, opts in _TUNE_FLAG_KNOBS
        if hasattr(args, dest) and any(o in spelled for o in opts)}
    res = ttune.resolve(profile, _tune_workload(args, ttune),
                        explicit=explicit)
    for knob, dest, _opts in _TUNE_FLAG_KNOBS:
        if not hasattr(args, dest):
            continue
        c = res.choices[knob]
        if c.source != "resolved" or c.value is None:
            continue
        setattr(args, dest,
                res.comm_string() if knob == "comm" else c.value)
    args._tune_profile_id = res.profile_id
    ttune.emit_resolution(res)
    if not getattr(args, "quiet", False):
        for knob in ttune.KNOBS:
            c = res.choices[knob]
            print(f"tune[{knob}]: {c.value} ({c.source}) {c.why}",
                  file=sys.stderr)


def _run_tune(args):
    """``tda tune`` — the seeded profiling pass: measure the rig,
    persist the versioned rig-tagged RigProfile artifact."""
    import os

    from tpu_distalg import telemetry
    from tpu_distalg import tune as ttune

    collective = None
    backend = (os.environ.get("JAX_PLATFORMS") or "cpu"
               ).split(",")[0] or "cpu"
    with telemetry.span("cli:tune"):
        # the probe child must reach the chip BEFORE this process
        # builds a mesh on it — one process per chip
        init_s = (None if args.no_backend_init
                  else ttune.measure_backend_init())
        if args.collective:
            import jax

            backend = jax.default_backend()
            collective = ttune.measure_collective(_mesh(args))
        m = ttune.measure_rig(
            seed=args.seed, quick=args.quick,
            backend_init_s=init_s, collective=collective)
        # the one wall-clock read: created_unix orders profile
        # artifacts on disk and tags when the rig was measured — it
        # never influences run behavior or replay
        created = time.time()  # tda: ignore[TDA001] -- artifact timestamp, not run state
        profile = ttune.build_profile(
            m, created_unix=created, seed=args.seed, backend=backend)
        path = ttune.save_profile(profile, args.out_dir)
    lb = m["loopback"]
    print(f"tune: rig={profile['rig']} backend={backend} "
          f"id={profile['profile_id']}")
    print(f"tune: loopback {lb['bandwidth_bytes_s'] / 1e6:.0f} MB/s "
          f"rtt {lb['rtt_s'] * 1e6:.0f}us | memcpy "
          f"{m['memcpy_bytes_s'] / 1e9:.1f} GB/s | matmul "
          f"{m['matmul_flops_s'] / 1e9:.1f} GFLOP/s")
    for name, rates in sorted(m["codecs"].items()):
        print(f"tune: codec {name}: encode "
              f"{rates['encode_elems_s'] / 1e6:.1f} Melem/s, decode "
              f"{rates['decode_elems_s'] / 1e6:.1f} Melem/s")
    if collective:
        print(f"tune: collective "
              f"{collective['bandwidth_bytes_s'] / 1e6:.0f} MB/s "
              f"rtt {collective['rtt_s'] * 1e6:.0f}us over "
              f"{collective['n_shards']} shards")
    if m.get("backend_init_s") is not None:
        print(f"tune: backend init {m['backend_init_s']:.1f}s")
    print(f"tune: wrote {path}")
    return 0


def _run_cluster(args):
    """``tda cluster`` — the multi-process elastic runtime."""
    import json as _json
    import os

    from tpu_distalg import cluster as clus
    from tpu_distalg import telemetry
    from tpu_distalg.parallel import ssp as pssp

    if args.role in ("replica", "router"):
        return _run_serving_plane(args)
    spec = pssp.SyncSpec.parse(args.sync)
    if not spec.is_ssp:
        raise SystemExit(
            "the cluster runtime is stale-synchronous by construction "
            "— --sync ssp[:s[:decay]] (a BSP cluster is the restart-"
            "policy baseline the bench measures, not a mode)")
    err = lambda m: print(m, file=sys.stderr)  # noqa: E731
    if args.role == "worker":
        if not args.connect:
            raise SystemExit("--role worker needs --connect HOST:PORT")
        host, _, port = args.connect.rpartition(":")
        stats = clus.run_worker(
            host or "127.0.0.1", int(port), slot=args.slot,
            rejoin=args.rejoin, admit_at=args.admit_at, logger=err)
        print("cluster_worker: " + _json.dumps(
            {k: v for k, v in stats.items()
             if not isinstance(v, list)}))
        return 0
    plan = args.fault_plan or os.environ.get("TDA_FAULT_PLAN") or None
    train = (clus.TrainTask(**_json.loads(args.train_json))
             if args.train_json
             else clus.TrainTask(algo=args.algo, n_rows=args.n_rows))
    extra = {}
    if args.pull_refresh_windows is not None:
        extra["pull_refresh_windows"] = args.pull_refresh_windows
    cfg = clus.ClusterConfig(
        n_slots=args.workers, n_windows=args.n_windows,
        staleness=spec.staleness, decay=spec.decay,
        ps_shards=args.ps_shards, host=args.host, port=args.port,
        heartbeat_timeout=args.heartbeat_timeout,
        heartbeat_interval=args.heartbeat_interval,
        rpc_deadline=args.rpc_deadline,
        reconnect_grace=args.reconnect_grace,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        policy=args.policy, plan_spec=plan, comm=args.comm,
        ps_mode=args.ps_mode,
        tune_profile=getattr(args, "_tune_profile_id", None),
        train=train, **extra)
    if args.role == "coordinator":
        coord = clus.Coordinator(cfg).start()
        print(f"cluster_coordinator: listening on "
              f"{cfg.host}:{coord.port}", flush=True)
        coord.wait(timeout=args.deadline)
        # linger briefly for the workers' byes (their stats ride
        # them): done fires at the final commit, a breath before the
        # last deferred acks + byes drain; the result snapshots AFTER
        coord_deadline = time.monotonic() + 10.0
        while time.monotonic() < coord_deadline and any(
                st.status == "active"
                for st in coord.slots.values()):
            time.sleep(0.05)
        res = coord.result()
        coord.stop()
    else:
        # (main() already pointed this process's telemetry at
        # DIR/coordinator; spawned workers get DIR/worker-N)
        res = clus.run_local_cluster(
            cfg, spawn=args.spawn,
            coordinator_spawn=args.coordinator_spawn,
            rejoin_after=args.rejoin_after,
            telemetry_dir=args.telemetry_dir, timeout=args.deadline,
            logger=err)
    from tpu_distalg.cluster.local import event_digest

    # machine-readable tail line: the replay acceptance compares the
    # event digest of two runs under the same plan. A subprocess
    # coordinator already digested its own sequences (its result line
    # is what the launcher parsed) — pass that through verbatim.
    print("cluster_result: " + _json.dumps({
        "accuracy": round(res["accuracy"], 6),
        "version": res["version"],
        "gen": res["gen"],
        "merges": res.get("merges",
                          len(res.get("merge_sequence", ()))),
        "respawns": res.get("respawns", 0),
        "restarts": res.get("restarts", 0),
        "recoveries": res.get(
            "coordinator_recoveries",
            1 if res.get("recovered") else 0),
        "recovery_ms": res.get("recovery_ms", []),
        "wal_records_replayed": res.get("wal_records_replayed", 0),
        "event_digest": res.get("event_digest",
                                None) or event_digest(res),
    }, default=float))
    return 0


def _run_serving_plane(args):
    """``tda cluster --role {replica,router}`` — the distributed
    serving plane's two process kinds. Both park until --deadline (or
    a kill); the port announcement line is the launcher handshake."""
    err = lambda m: print(m, file=sys.stderr)  # noqa: E731
    if args.role == "replica":
        from tpu_distalg.cluster import serve as cserve

        if not args.artifact:
            raise SystemExit("--role replica needs --artifact "
                             "CKPT_DIR")
        rep = cserve.run_replica(
            args.slot or 0, args.artifact, shard=args.shard,
            n_shards=args.replica_shards, k_top=args.k_top,
            merge=args.merge, comm=args.comm, host=args.host,
            port=args.port, logger=err)
        print(f"cluster_replica: listening on "
              f"{args.host}:{rep.port}", flush=True)
        deadline = time.monotonic() + args.deadline
        try:
            while (time.monotonic() < deadline
                   and not rep._stop.is_set()):
                time.sleep(0.2)
        finally:
            rep.stop()
        return 0
    from tpu_distalg.cluster.router import Router, RouterConfig

    if not args.replicas:
        raise SystemExit("--role router needs --replicas "
                         "HOST:PORT[,HOST:PORT...]")
    addrs = []
    for tok in args.replicas.split(","):
        host, _, port = tok.strip().rpartition(":")
        addrs.append((host or "127.0.0.1", int(port)))
    router = Router(RouterConfig(
        replicas=tuple(addrs), mode=args.serve_mode,
        policy=args.dispatch, comm=args.comm, port=args.port,
        wal_dir=args.wal_dir, k_top=args.k_top, merge=args.merge,
        hb_interval=args.heartbeat_interval,
        hb_timeout=args.heartbeat_timeout,
        rpc_deadline=args.rpc_deadline), logger=err).start()
    print(f"cluster_router: listening on "
          f"{args.host}:{router.port}", flush=True)
    deadline = time.monotonic() + args.deadline
    try:
        while (time.monotonic() < deadline
               and not router._stop.is_set()):
            time.sleep(0.2)
        router.emit_gauges()
    finally:
        router.stop()
    return 0


def _dispatch(args, jax):
    if args.cmd == "cluster":
        return _run_cluster(args)
    if args.cmd in ("lr", "ssgd", "ma", "bmuf", "easgd"):
        from tpu_distalg.utils import datasets

        pairs = args.cmd == "ssgd" and args.row_format == "pairs"
        hashed = args.cmd == "ssgd" and (args.hashed_rows > 0
                                         or args.indexed_rows > 0
                                         or pairs)
        data = None if hashed else datasets.breast_cancer_split()
        mesh = _mesh(args)
        t0 = time.perf_counter()
        if hashed:
            from tpu_distalg.models import ssgd as m

            if args.stream_cache is not None:
                raise SystemExit(
                    "--hashed-rows / --indexed-rows / --row-format pairs "
                    "build their table on the device; --stream-cache "
                    "streams packed columns from disk")
            if (args.hashed_rows > 0) + (args.indexed_rows > 0) + pairs > 1:
                raise SystemExit(
                    "--hashed-rows, --indexed-rows and --row-format pairs "
                    "each name a table, and two tables were named: give "
                    "one")
            indexed = args.indexed_rows > 0
            cards = None
            if args.field_values is not None:
                if not indexed:
                    raise SystemExit(
                        "--field-values sizes the fields' ranges of "
                        "--indexed-rows")
                cards = tuple(int(v) for v in args.field_values.split(","))
            # the options that describe the data pick the trainer; the
            # one sampler that takes rows of indices is the default's
            # stand-in, any other named one is refused by the builder
            sampler = ("fused_gather" if args.sampler == "bernoulli"
                       else args.sampler)
            cfg = m.SSGDConfig(
                n_iterations=args.n_iterations, eta=args.eta,
                mini_batch_fraction=args.mini_batch_fraction,
                lam=args.lam, reg_type=args.reg_type, sampler=sampler,
                gather_block_rows=args.gather_block_rows,
                comm=args.comm, sync=args.sync, eval_test=False)

            def run_once():
                if pairs:
                    from tpu_distalg.models import ssgd_pairs

                    return ssgd_pairs.train(
                        ssgd_pairs.PairsSpec(
                            n_rows=args.pair_rows,
                            n_features=args.features,
                            length_mu=args.length_mu,
                            block_slots=args.pair_block_slots,
                            length_min=args.min_pairs,
                            length_max=args.max_pairs),
                        mesh, cfg, checkpoint_dir=args.checkpoint_dir,
                        checkpoint_every=args.checkpoint_every)
                if indexed:
                    return m.train_hashed(
                        args.indexed_rows,
                        len(cards) if cards else args.fields, 0, mesh,
                        cfg, cardinalities=cards, row_format="indexed",
                        checkpoint_dir=args.checkpoint_dir,
                        checkpoint_every=args.checkpoint_every)
                return m.train_hashed(
                    args.hashed_rows, args.fields, args.hash_bits, mesh,
                    cfg, checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every)
        elif args.cmd == "lr":
            from tpu_distalg.models import logistic_regression as m

            def run_once():
                return m.train(
                    *data, mesh, m.LRConfig(
                        n_iterations=args.n_iterations, eta=args.eta,
                        comm=args.comm),
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every)
        elif args.cmd == "ssgd" and args.stream_cache is not None:
            from tpu_distalg.models import ssgd as m
            from tpu_distalg.models import ssgd_stream
            from tpu_distalg.utils import datasets

            if args.mega_steps is not None:
                raise SystemExit(
                    "--mega-steps applies to sampler=fused_train only; "
                    "the streamed path runs one kernel per step")
            if args.comm != "dense":
                raise SystemExit(
                    "--comm applies to the in-memory trainers; the "
                    "streamed trainer (--stream-cache) stages blocks "
                    "host->device per step and syncs dense")
            if args.sync != "bsp":
                raise SystemExit(
                    "--sync ssp applies to the in-memory trainers; "
                    "the streamed trainer (--stream-cache) runs BSP")
            n_shards = int(mesh.shape["data"])
            X2, meta, (X_te, y_te) = datasets.streamed_packed_cache(
                args.stream_cache, n_rows=args.stream_rows,
                n_features=125, n_shards=n_shards,
                pack=args.fused_pack,
                gather_block_rows=args.gather_block_rows)
            cfg = m.SSGDConfig(
                n_iterations=args.n_iterations, eta=args.eta,
                mini_batch_fraction=args.mini_batch_fraction,
                lam=args.lam, reg_type=args.reg_type,
                fused_pack=args.fused_pack,
                gather_block_rows=args.gather_block_rows,
                sampler="fused_gather", shuffle_seed=None,
                eval_every=max(1, args.n_iterations // 10))

            def run_once():
                return ssgd_stream.train(
                    X2, meta, mesh, cfg, X_te, y_te,
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every)
        elif args.cmd == "ssgd":
            from tpu_distalg.models import ssgd as m

            kw = dict(
                n_iterations=args.n_iterations, eta=args.eta,
                mini_batch_fraction=args.mini_batch_fraction,
                lam=args.lam, reg_type=args.reg_type,
                sampler=args.sampler, x_dtype=args.x_dtype,
                gather_block_rows=args.gather_block_rows,
                fused_pack=args.fused_pack,
                shuffle_seed=args.shuffle_seed,
                comm=args.comm, sync=args.sync)
            n_model = int(mesh.shape["model"])
            if n_model > 1:
                # a 2-D --mesh-shape IS the tp request: the feature
                # dim shards over the model axis per the ssgd_tp /
                # ssgd_feature_sharded rule tables — a config, not a
                # code path (parallel/partition.py)
                if args.sampler not in ("bernoulli", "fused_gather"):
                    raise SystemExit(
                        f"--mesh-shape with model={n_model} shards "
                        f"the feature dim, which composes with "
                        f"sampler=bernoulli or fused_gather (got "
                        f"{args.sampler!r})")
                kw["feature_sharded"] = True
            if args.sampler != "fused_train" and \
                    args.mega_steps is not None:
                raise SystemExit(
                    f"--mega-steps applies to sampler=fused_train "
                    f"only (got {args.sampler})"
                )
            if args.sampler == "fused_train":
                mega = args.mega_steps
                if mega is not None and mega < 1:
                    raise SystemExit(
                        f"--mega-steps must be >= 1 (got {mega})")
                if mega is None and args.n_iterations < 1:
                    mega = m.SSGDConfig().mega_steps  # nothing to run
                elif mega is None:
                    # auto-pick: largest divisor of EVERY segment the
                    # run will execute (checkpoint segments, remainder,
                    # resume offset included) within the default launch
                    # size — e.g. 300 iterations picks 100 instead of
                    # failing the divisibility check at trace time
                    import math

                    segs = m.fused_train_segment_lengths(
                        args.checkpoint_dir,
                        (args.checkpoint_every if args.checkpoint_dir
                         else args.n_iterations),
                        args.n_iterations)
                    g = math.gcd(*segs) if segs else args.n_iterations
                    cap = min(m.SSGDConfig().mega_steps, g)
                    mega = max(d for d in range(1, cap + 1)
                               if g % d == 0)
                    if mega < min(m.SSGDConfig().mega_steps,
                                  args.n_iterations) // 2:
                        print(
                            f"[ssgd] note: auto-picked mega_steps="
                            f"{mega} is far below the default launch "
                            f"size — iteration/checkpoint counts with "
                            f"a larger common divisor run faster"
                        )
                kw["mega_steps"] = mega
                # the megakernel evaluates at launch boundaries only
                # (max guards the degenerate n_iterations=0 run)
                kw["eval_every"] = max(1, min(mega, args.n_iterations))

            def run_once():
                return m.train(
                    *data, mesh, m.SSGDConfig(**kw),
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every)
        else:
            mod = {
                "ma": "MAConfig", "bmuf": "BMUFConfig", "easgd": "EASGDConfig"
            }
            import importlib

            m = importlib.import_module(f"tpu_distalg.models.{args.cmd}")
            cfg_cls = getattr(m, mod[args.cmd])
            if args.mega_steps is not None:
                raise SystemExit(
                    f"{args.cmd}: --mega-steps applies to ssgd only — "
                    "local-update megakernels launch n-local-iterations "
                    "steps per round"
                )
            def run_once(m=m, cfg_cls=cfg_cls):
                return m.train(
                    *data, mesh, cfg_cls(
                        n_iterations=args.n_iterations, eta=args.eta,
                        mini_batch_fraction=args.mini_batch_fraction,
                        n_local_iterations=args.n_local_iterations,
                        resample_per_local_step=(
                            args.resample_per_local_step),
                        sampler=args.sampler, x_dtype=args.x_dtype,
                        gather_block_rows=args.gather_block_rows,
                        fused_pack=args.fused_pack,
                        shuffle_seed=args.shuffle_seed,
                        comm=args.comm, sync=args.sync),
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every)
        from tpu_distalg.utils import checkpoint as ckpt

        # the watchdog: crash / NaN-guard trips re-run the job, which
        # resumes from the newest checkpoint (utils/checkpoint.py)
        res = ckpt.run_with_restarts(
            run_once, max_restarts=args.max_restarts)
        jax.block_until_ready(res.w)
        _report_optimizer(args.cmd, res, args, time.perf_counter() - t0)

    elif args.cmd == "kmeans":
        from tpu_distalg.models import kmeans as m
        from tpu_distalg.utils import checkpoint as ckpt
        from tpu_distalg.utils import datasets

        mesh = _mesh(args)
        if args.data_backend != "resident" or args.minibatch_steps:
            # the out-of-core engine: the mixture lives behind a
            # ShardedDataset (host RAM or a disk cache — >HBM fine) and
            # minibatch k-means streams sampled blocks per step
            from tpu_distalg.data import builders

            if args.checkpoint_dir:
                raise SystemExit(
                    "--checkpoint-dir is not supported by the "
                    "minibatch engine yet (state is tiny; rerun "
                    "instead)")
            if args.data_backend == "streamed" and not args.stream_cache:
                raise SystemExit(
                    "--data-backend streamed needs --stream-cache PATH "
                    "(the on-disk packed cache to create or reopen)")
            n_rows = args.scale_points or args.n_points or (1 << 20)
            ds, _ = builders.gaussian_points_dataset(
                mesh, n_rows, dim=args.dim, k=args.k, seed=0,
                block_rows=args.block_rows,
                backend=args.data_backend, path=args.stream_cache)
            steps = args.minibatch_steps or 100

            def run_once():
                return m.fit_minibatch(
                    ds, m.KMeansConfig(k=args.k), n_steps=steps,
                    mini_batch_blocks=args.mini_batch_blocks)

            res = ckpt.run_with_restarts(
                run_once, max_restarts=args.max_restarts)
            print(f"Final centers: {res.centers.tolist()}")
            print(f"minibatch steps run: {res.n_iterations_run} "
                  f"(backend={args.data_backend})")
            return 0
        if args.scale_points:
            make_rows, _ = datasets.gaussian_mixture_rows(
                k=args.generating_clusters or args.k, dim=args.dim,
                seed=0)

            def run_once():
                return m.fit_scaled(
                    mesh, args.scale_points, make_rows,
                    m.KMeansConfig(k=args.k,
                                   n_iterations=args.n_iterations,
                                   converge_dist=args.converge_dist,
                                   init="farthest"),
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every,
                    data_seed=0)

            pts = None  # points never leave the devices (O(k) host RAM)
        else:
            pts = (datasets.toy_kmeans_matrix() if args.n_points == 0
                   else datasets.gaussian_mixture(args.n_points,
                                                  k=args.k))

            def run_once():
                return m.fit(pts, mesh, m.KMeansConfig(
                    k=args.k, n_iterations=args.n_iterations,
                    converge_dist=args.converge_dist),
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every)

        res = ckpt.run_with_restarts(
            run_once, max_restarts=args.max_restarts)
        print(_centers_line(res.centers))
        print(f"iterations run: {res.n_iterations_run}")
        if args.plot and pts is None:
            print("--plot ignored with --scale-points (points stay "
                  "on device)")
        elif args.plot:
            from tpu_distalg.utils import metrics

            import numpy as np

            metrics.display_clusters(
                pts, np.asarray(res.assignments)[: len(pts)], args.plot,
                k=args.k,
            )
            print(f"saved plot: {args.plot}")

    elif args.cmd == "pagerank":
        from tpu_distalg.models import pagerank as m
        from tpu_distalg.utils import datasets

        import numpy as np

        from tpu_distalg.utils import checkpoint as ckpt

        if args.rmat_scale:
            edges = None
            n_v = 1 << args.rmat_scale
        else:
            if args.edge_file is not None:
                from tpu_distalg import native

                edges = native.parse_edges_text(
                    args.edge_file, args.edge_capacity)
            elif args.n_vertices == 0:
                edges = datasets.toy_graph_edges()
            else:
                edges = datasets.erdos_renyi_edges(args.n_vertices)
            # the edge content is authoritative for --edge-file (it
            # documents itself as overriding --n-vertices, and an
            # undersized count must never reach the degree histogram);
            # the synthetic path keeps its isolated tail vertices
            n_v = int(np.asarray(edges).max()) + 1 if len(edges) else 1
            if args.edge_file is None and args.n_vertices:
                n_v = max(n_v, args.n_vertices)
        mesh = _mesh(args)
        backend, warn = m.choose_data_backend(
            args.data_backend, n_v, scatter=args.scatter,
            n_shards=int(mesh.shape["data"]))
        if warn:
            print(warn, file=sys.stderr)
        if backend != "resident" and args.mode == "reference":
            raise SystemExit(
                "[pagerank] the reference-parity mode is resident-only "
                "(per-vertex receive masks); the streamed engine runs "
                "mode='standard' — drop --mode reference or use "
                "--data-backend resident on a smaller graph")
        mode = args.mode or ("reference" if backend == "resident"
                             and edges is not None else "standard")
        t0 = time.perf_counter()
        if edges is None:
            if backend != "resident":
                raise SystemExit(
                    f"[pagerank] 2**{args.rmat_scale} vertices are past "
                    f"the resident fused sweep on this mesh (the "
                    f"warning above says how many data shards hold "
                    f"them), and --rmat-scale draws its graph on the "
                    f"device only: write the edges to a file for "
                    f"--data-backend streamed")
            res = ckpt.run_with_restarts(
                lambda: m.run_rmat(
                    mesh, m.PageRankConfig(
                        n_iterations=args.n_iterations, q=args.q,
                        mode=mode, scatter=args.scatter),
                    args.rmat_scale, args.edge_factor, args.rmat_abcd,
                    args.seed, checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every),
                max_restarts=args.max_restarts)
            ranks = np.asarray(res.ranks)
            mask = np.ones(len(ranks), bool)
            tail = (f" [fused sweep over a Kronecker graph of "
                    f"2**{args.rmat_scale} vertices drawn on the device]")
        elif backend == "resident":
            res = ckpt.run_with_restarts(
                lambda: m.run(edges, mesh, m.PageRankConfig(
                    n_iterations=args.n_iterations, q=args.q,
                    mode=mode, scatter=args.scatter),
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every),
                max_restarts=args.max_restarts)
            ranks = np.asarray(res.ranks)
            mask = np.asarray(res.has_rank) > 0
            tail = ""
        else:
            import hashlib
            import os
            import tempfile

            from tpu_distalg import graphs

            n_shards = int(mesh.shape["data"])
            # the default path is keyed on the edge CONTENT too — two
            # different graphs sharing a vertex count must not collide
            # on one stale tmp cache
            sha = hashlib.sha1(
                np.ascontiguousarray(edges, np.int64).tobytes()
            ).hexdigest()
            path = args.stream_cache or os.path.join(
                tempfile.gettempdir(),
                f"tda_graph_cache_v{n_v}_s{n_shards}"
                f"_b{args.block_edges}_{sha[:12]}")
            if args.stream_cache is None:
                print(f"[pagerank] edge-block cache: {path} "
                      f"(set --stream-cache to keep it elsewhere)",
                      file=sys.stderr)
            graphs.build_edge_block_cache(
                edges, path, n_shards=n_shards,
                block_edges=args.block_edges, n_vertices=n_v,
                source={"kind": "edges", "sha1": sha})
            gd = graphs.open_graph_dataset(path, mesh, backend=backend)
            cfg = graphs.StreamedPageRankConfig(
                n_iterations=args.n_iterations, q=args.q,
                combine=args.combine)
            res = ckpt.run_with_restarts(
                lambda: graphs.run_streamed_pagerank(
                    gd, cfg, checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every),
                max_restarts=args.max_restarts)
            ranks = np.asarray(res.ranks)
            mask = np.ones(len(ranks), bool)
            st = res.comm_stats
            wire = (st["bytes_wire"] if res.combine == "sparse"
                    else st["bytes_dense_ring"])
            tail = (f" [{backend} engine, combine={res.combine}: "
                    f"{wire} B wire/sweep; accounting sparse "
                    f"{st['bytes_wire']} B vs dense-ring "
                    f"{st['bytes_dense_ring']} B]")
        jax.block_until_ready(res.ranks)
        dt = time.perf_counter() - t0
        shown = np.argsort(-ranks)[:10]
        for v in shown:
            if mask[v]:
                print(f"{v} has rank: {ranks[v]}.")
        print(f"[pagerank] {args.n_iterations} iterations in {dt:.3f}s "
              f"({args.n_iterations / dt:.2f} iter/s){tail}")

    elif args.cmd == "closure":
        from tpu_distalg.models import transitive_closure as m
        from tpu_distalg.utils import datasets

        pairs_bound = sparse_config = None
        if args.tree_height:
            edges = datasets.tree_edges(args.tree_height, args.seed)
            pairs_bound = datasets.tree_closure_pairs(args.tree_height)
            # no round of a tree joins or finds more pairs than it has
            # arcs; powers of two, so that Tree17's buffers are the ones
            # the benchmark's configuration states
            room = 1 << (len(edges) - 1).bit_length()
            sparse_config = m.SparseClosureConfig(
                capacity=args.capacity
                or 1 << (pairs_bound - 1).bit_length(),
                delta_capacity=room, join_capacity=room)
        elif args.grid_side:
            edges = datasets.grid_edges(args.grid_side, args.seed)
            pairs_bound = datasets.grid_closure_pairs(args.grid_side)
        elif args.n_vertices == 0:
            edges = datasets.toy_graph_edges()
        elif args.sparse:
            # bounded-closure graph: an ER graph's closure is Θ(V²) pairs
            # (inherently quadratic output) — chains keep it linear in V
            edges = datasets.chain_forest_edges(args.n_vertices)
        else:
            edges = datasets.erdos_renyi_edges(args.n_vertices, 2.0)
        from tpu_distalg.utils import checkpoint as ckpt

        mesh = _mesh(args)
        sparse = args.sparse
        if pairs_bound is not None and not sparse:
            # the generator knows its answer's size: the form comes from
            # the bytes each would hold (models/transitive_closure.py)
            picked = m.choose_form(
                len(edges) + 1 if args.tree_height
                else int(edges.max()) + 1, len(edges), mesh,
                pairs_bound=pairs_bound)
            sparse = picked["closure_form"] == "sparse"
            print(f"[closure] {picked['closure_form']}: two byte matrices "
                  f"{picked['dense_bytes'] / 1e9:.3f} GB, a pair set "
                  f"{picked['sparse_bytes'] / 1e9:.3f} GB, budget "
                  f"{picked['budget_bytes'] / 1e9:.3f} GB")
        if sparse:
            if sparse_config is None:
                sparse_config = m.SparseClosureConfig(
                    capacity=args.capacity or None)

            def run_once():
                # the pairs stay on the device: a count is what is shown
                return m.run_sparse(
                    edges, mesh, sparse_config,
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every,
                    keep_paths=False)
        else:
            def run_once():
                return m.run(edges, mesh,
                             checkpoint_dir=args.checkpoint_dir,
                             checkpoint_every=args.checkpoint_every)
        res = ckpt.run_with_restarts(
            run_once, max_restarts=args.max_restarts)
        print(f"The original graph has {res.n_paths} paths "
              f"({res.n_rounds} rounds)")
        if pairs_bound is not None:
            print(f"[closure] the {'tree' if args.tree_height else 'grid'}'s "
                  f"closed form: {pairs_bound} pairs"
                  f" ({'equal' if res.n_paths == pairs_bound else 'NOT EQUAL'})")
            if res.n_paths != pairs_bound:
                return 1

    elif args.cmd == "als":
        from tpu_distalg.models import als as m
        from tpu_distalg.utils import checkpoint as ckpt

        mesh = _mesh(args)
        cfg = m.ALSConfig(lam=args.lam, m=args.m, n=args.n, k=args.k,
                          n_iterations=args.n_iterations)
        if args.ratings:
            # R is a ratings list: the loader says so in its meta and
            # als picks the sparse trainer from that (models/als.py)
            if args.users < 1 or args.items < 1:
                raise SystemExit(
                    "--ratings needs --users and --items (the two "
                    "sides' sizes)")
            if args.data_backend != "resident":
                raise SystemExit(
                    "--ratings is held on the device: --data-backend "
                    f"{args.data_backend} does not apply")
            cfg = m.ALSConfig(lam=args.lam, m=args.users, n=args.items,
                              k=args.k, n_iterations=args.n_iterations)
            n_heldout = args.ratings // 64 if args.heldout < 0 \
                else args.heldout
            arrays, meta = m.build_ratings_table(
                args.ratings, args.users, args.items, args.k, mesh,
                data_seed=args.data_seed, n_heldout=n_heldout)
            print(f"ratings: {meta['n_ratings']} of {meta['n_users']} "
                  f"users by {meta['n_items']} items, rank {meta['k']}; "
                  f"blocks a side {meta['blocks']}, slots held / "
                  f"ratings {meta['padding_share']:.4f}, "
                  f"{(meta['ratings_bytes'] + meta['factor_bytes']) / 1e9:.3f}"
                  f" GB resident")
            res = ckpt.run_with_restarts(
                lambda: m.fit_ratings(
                    mesh, cfg, arrays, meta,
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every),
                max_restarts=args.max_restarts)
        elif args.data_backend != "resident":
            # R behind a ShardedDataset: host RAM or a disk cache —
            # each sweep streams the row blocks per solve epoch, so R
            # is bounded by disk, not HBM (models/als.fit_streamed)
            from tpu_distalg.data import builders

            if args.checkpoint_dir:
                raise SystemExit(
                    "--checkpoint-dir is not supported by the "
                    "streamed ALS path yet")
            if args.data_backend == "streamed" and not args.stream_cache:
                raise SystemExit(
                    "--data-backend streamed needs --stream-cache PATH "
                    "(the on-disk packed cache to create or reopen)")
            ds, _ = builders.rank_k_rows_dataset(
                mesh, args.m, args.n, args.k, seed=cfg.seed,
                block_rows=args.block_rows,
                backend=args.data_backend, path=args.stream_cache)
            res = ckpt.run_with_restarts(
                lambda: m.fit_streamed(ds, cfg,
                                       rmse_every=args.rmse_every),
                max_restarts=args.max_restarts)
        else:
            res = ckpt.run_with_restarts(
                lambda: m.fit(mesh, cfg,
                              checkpoint_dir=args.checkpoint_dir,
                              checkpoint_every=args.checkpoint_every),
                max_restarts=args.max_restarts)
        import numpy as np

        # ONE device fetch for the whole history: float(e) per element
        # is a D2H round-trip per line (the per-step-host-sync shape
        # TDA011 polices); values print bitwise-identically
        held = None if res.heldout_history is None \
            else np.asarray(res.heldout_history)
        for t, e in enumerate(np.asarray(res.rmse_history)):
            tail = "" if held is None else \
                f", held-out rmse: {float(held[t]):f}"
            print(f"iterations: {t}, rmse: {float(e):f}{tail}")
        if args.checkpoint_dir:
            # machine-readable artifact handoff: `tda serve --artifact`
            # consumes this exact line (and the telemetry event) — no
            # directory globbing needed to find where the factors went
            from tpu_distalg.telemetry import events as tevents

            tevents.emit("artifact_path", workload="als",
                         path=args.checkpoint_dir)
            print(f"artifact_path: {args.checkpoint_dir}")

    elif args.cmd == "chaos":
        import os
        import tempfile

        from tpu_distalg import faults
        from tpu_distalg.faults import chaos

        spec = args.fault_plan or os.environ.get(faults.registry.ENV_PLAN)
        if not spec:
            raise SystemExit(
                "tda chaos needs a fault schedule: pass --fault-plan "
                "'seed=N;point@hit=kind[:arg];...' (or a JSON plan "
                "file, or export $TDA_FAULT_PLAN)")
        mesh = _mesh(args)
        workdir = args.workdir
        made_tmp = workdir is None
        if made_tmp:
            workdir = tempfile.mkdtemp(prefix="tda-chaos-")
        res = None
        try:
            res = chaos.run_chaos(
                args.workload, mesh, plan=spec, workdir=workdir,
                n_iterations=args.n_iterations,
                checkpoint_every=args.checkpoint_every,
                max_restarts=args.max_restarts,
                spawn=args.spawn, comm=args.comm,
                logger=lambda m: print(f"[chaos] {m}"))
        finally:
            if made_tmp:
                if res is not None and res.equal:
                    import shutil

                    shutil.rmtree(workdir, ignore_errors=True)
                else:
                    # a mismatch (or a blown restart budget) is exactly
                    # when the checkpoints + quarantined files matter —
                    # keep the evidence
                    print(f"[chaos] scratch kept for debugging: "
                          f"{workdir}", file=sys.stderr)
        print(res.verdict())
        return 0 if res.equal else 1

    elif args.cmd == "serve":
        import numpy as np

        from tpu_distalg import serve as serve_pkg
        from tpu_distalg.parallel import MeshContext
        from tpu_distalg.serve.server import run_closed_loop

        mesh = MeshContext.create(
            data=args.n_slices if args.n_slices > 0 else None,
            model=args.model_slices).mesh
        cfg = serve_pkg.ServeConfig(
            max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
            queue_depth=args.queue_depth, k_top=args.k_top,
            merge=args.comm)
        server = serve_pkg.Server(mesh, cfg)
        try:
            for path in args.artifact:
                model = server.add_artifact(path)
                print(f"[serve] {model.kind} model {model.name!r} from "
                      f"{path} (meta: {model.meta})")
            rng = np.random.default_rng(0)
            for name, model in server.models.items():
                if model.kind == "als":
                    n_users = max(1, model.meta["n_users"])
                    payloads = [np.int32(int(v) % n_users)
                                for v in rng.integers(
                                    0, n_users, size=args.requests)]
                elif model.kind == "kmeans":
                    payloads = list(rng.normal(size=(
                        args.requests, model.meta["dim"])
                    ).astype(np.float32))
                else:
                    payloads = list(rng.normal(size=(
                        args.requests, model.meta["d"])
                    ).astype(np.float32))
                _, info = run_closed_loop(
                    server, name, payloads,
                    concurrency=args.concurrency, retries=2)
                print(f"[serve] {name}: {info['ok']}/{len(payloads)} "
                      f"replies at {info['qps']} req/s (closed loop, "
                      f"{info['concurrency']} workers, "
                      f"{info['retries']} retries)")
            s = server.emit_counters()
            print(f"[serve] total: {s['replies']} replies in "
                  f"{s['batches']} micro-batch(es), p50 {s['p50_ms']} "
                  f"ms / p99 {s['p99_ms']} ms, {s['shed']} shed, max "
                  f"queue depth {s['max_queue_depth']}")
        finally:
            server.close()

    elif args.cmd == "mc":
        from tpu_distalg.models import monte_carlo as m
        from tpu_distalg.utils import checkpoint as ckpt

        mesh = _mesh(args)
        pi, n_used = ckpt.run_with_restarts(
            lambda: m.estimate_pi(mesh, m.MonteCarloConfig(n=args.n)),
            max_restarts=args.max_restarts)
        print(f"Pi is roughly {pi:f}")

    return 0


if __name__ == "__main__":
    sys.exit(main())
