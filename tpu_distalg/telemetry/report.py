"""Event-log summarization — ``tda report <dir>``.

Turns a telemetry JSONL log into the 3-line diagnosis round 5 lacked:
phase durations (spans as a tree, each with its self time: duration
minus what its child spans cover), what JAX traced, lowered, compiled
and loaded by function (``jit:*`` spans), stall/retry/restart counts, backend-init
attempt history and resolution, last heartbeat age, and every recorded
metric/gauge — for humans (default rendering) and CI (``--json``).
Tolerates torn tail lines (a killed process loses at most the line it
was writing) and multiple runs' files in one directory.
"""

from __future__ import annotations

import glob
import json
import os


def load_events(path: str) -> list[dict]:
    """All events under ``path`` (a directory of ``events-*.jsonl`` or
    one file), in file order; undecodable lines are skipped (the torn
    tail of a killed run), counted in a synthetic leading
    ``{"ev": "_torn_lines"}`` record when any were dropped."""
    if os.path.isfile(path):
        paths = [path]
    else:
        # oldest first BY MTIME (run ids are random hex, so a name sort
        # is arbitrary): "last wins" fields — last_heartbeat, resolution,
        # metrics — must come from the NEWEST run in a reused directory
        paths = sorted(glob.glob(os.path.join(path, "events-*.jsonl")),
                       key=lambda p: (os.path.getmtime(p), p))
        if not paths:
            raise FileNotFoundError(
                f"no events-*.jsonl under {path!r} (and it is not a "
                f"file) — was the run started with --telemetry-dir?")
    out: list[dict] = []
    torn = 0
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    torn += 1
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    if torn:
        out.insert(0, {"ev": "_torn_lines", "count": torn})
    return out


def span_tree(evts: list[dict]) -> list[dict]:
    """Spans as a tree, depth first in order of first appearance: one
    node per distinct path of names from a root span down, with its
    count, total and largest duration, and ``self_seconds`` = total
    minus what its direct child spans cover. A span's parent is the
    ``parent`` id it recorded (the span open on its thread when it
    began); spans of logs older than the ids are roots. A node whose
    spans sampled the devices' memory (``events.memory``: the fullest
    device's numbers here) also has ``hbm_rise_bytes`` (in use at a
    span's end less at its start, added up), ``hbm_in_use_bytes`` (at
    its last span's end) and ``hbm_set_peak``: true on the one node
    under which the run's peak last rose."""
    names: dict[tuple, tuple] = {}     # (run, id) -> (name, parent key)
    ended: list[tuple] = []            # (key, seconds, ok, span_end)
    for n, e in enumerate(evts):
        if e.get("ev") not in ("span_start", "span_end"):
            continue
        run, sid = e.get("run"), e.get("id")
        key = (run, sid) if sid is not None else (run, f"_{n}")
        parent = e.get("parent")
        names[key] = (e.get("name", "?"),
                      (run, parent) if parent is not None else None)
        if e["ev"] == "span_end":
            ended.append((key, float(e.get("seconds", 0.0)),
                          bool(e.get("ok", True)), e))

    def path_of(key) -> tuple:
        out, seen = [], set()
        while key in names and key not in seen:
            seen.add(key)
            name, key = names[key]
            out.append(name)
        return tuple(reversed(out))

    nodes: dict[tuple, dict] = {}
    peak_run, peak, peak_path = None, 0, None
    for key, seconds, ok, e in ended:
        path = path_of(key)
        for depth in range(1, len(path) + 1):   # ancestors first, so an
            nodes.setdefault(path[:depth], {    # open parent has a node
                "path": list(path[:depth]), "name": path[depth - 1],
                "depth": depth - 1, "count": 0, "total_seconds": 0.0,
                "max_seconds": 0.0, "child_seconds": 0.0, "errors": 0})
        node = nodes[path]
        node["count"] += 1
        node["total_seconds"] += seconds
        node["max_seconds"] = max(node["max_seconds"], seconds)
        node["errors"] += not ok
        if len(path) > 1:
            nodes[path[:-1]]["child_seconds"] += seconds
        if e.get("hbm_in_use"):
            start = max(e.get("hbm_in_use_start") or [0])
            node["hbm_rise_bytes"] = node.get("hbm_rise_bytes", 0) \
                + max(e["hbm_in_use"]) - start
            node["hbm_in_use_bytes"] = max(e["hbm_in_use"])
            if key[0] != peak_run:          # a new process, a new peak
                peak_run, peak, peak_path = key[0], 0, None
            # the peak rose under this span if it stands over what any
            # span that ended before it saw and over what was in use
            # when it began (children end first: the innermost is told)
            top = max(e.get("hbm_peak") or [0])
            if top > max(peak, start):
                peak_path = path
            peak = max(peak, top)
    if peak_path is not None:
        nodes[peak_path]["hbm_set_peak"] = True
    first = {path: n for n, path in enumerate(nodes)}
    out = []
    for path in sorted(nodes, key=lambda p: [
            first[p[:d]] for d in range(1, len(p) + 1)]):
        node = nodes[path]
        child = node.pop("child_seconds")
        node["self_seconds"] = round(
            max(node["total_seconds"] - child, 0.0), 6)
        node["total_seconds"] = round(node["total_seconds"], 6)
        node["max_seconds"] = round(node["max_seconds"], 6)
        out.append(node)
    return out


JIT_PREFIX = "jit:"   # utils/compile_cache.py's spans, and a row's
JIT_COLUMN = {"jit:trace": "trace_s", "jit:lower": "lower_s",  # column
              "jit:compile": "compile_s", "jit:cache_load": "load_s"}


def jit_functions(evts: list[dict]) -> list[dict]:
    """What JAX did to each function, from the ``jit:*`` spans: one row
    a ``fun`` with how often it was traced, its trace / lower / compile
    seconds, of the compile seconds those a persistent cache's load
    took, the cache's hits and misses, and under which program span
    (``name#id``, the nearest ancestor that is no ``jit:*`` span) each
    trace happened, in order. Largest total first."""
    spans: dict[tuple, dict] = {}
    for e in evts:
        if e.get("ev") in ("span_start", "span_end") and "id" in e:
            spans[(e.get("run"), e["id"])] = e
    rows: dict[str, dict] = {}
    for e in evts:
        column = JIT_COLUMN.get(e.get("name"))
        if e.get("ev") != "span_end" or column is None:
            continue
        row = rows.setdefault(str(e.get("fun", "?")), {
            "fun": str(e.get("fun", "?")), "traced": 0, "trace_s": 0.0,
            "lower_s": 0.0, "compile_s": 0.0, "load_s": 0.0, "hits": 0,
            "misses": 0, "under": []})
        row[column] = round(row[column] + float(e.get("seconds", 0.0)), 6)
        if column == "trace_s":
            row["traced"] += 1
            up, seen = e, set()
            while up is not None and id(up) not in seen and \
                    up.get("name", "").startswith(JIT_PREFIX):
                seen.add(id(up))
                up = spans.get((up.get("run"), up.get("parent")))
            row["under"].append(
                f"{up.get('name', '?')}#{up.get('id')}" if up else "-")
        elif column == "compile_s" and "hit" in e:
            row["hits" if e["hit"] else "misses"] += 1
    return sorted(rows.values(), key=lambda r: -_jit_total(r))


def hbm_summary(evts: list[dict]) -> dict | None:
    """The newest run's device memory as its spans sampled it (the
    fullest device): the largest ``hbm_peak`` any span ended with, what
    was in use at the last sampled span's end, and how many devices a
    sample covered (:func:`summarize` adds ``set_under``, the path of
    the tree's ``hbm_set_peak`` node); ``None`` where no span sampled
    (the CPU, a log from before the fields)."""
    got = [e for e in evts if e.get("ev") == "span_end"
           and e.get("hbm_in_use")]
    if not got:
        return None
    got = [e for e in got if e.get("run") == got[-1].get("run")]
    return {"peak_bytes": max(max(e.get("hbm_peak") or [0]) for e in got),
            "in_use_bytes": max(got[-1]["hbm_in_use"]),
            "last_span": got[-1].get("name", "?"),
            "devices": len(got[-1]["hbm_in_use"])}


def _jit_total(row: dict) -> float:
    """A function's seconds (the load lies inside the compile)."""
    return row["trace_s"] + row["lower_s"] + row["compile_s"]


def summarize(evts: list[dict]) -> dict:
    """Aggregate an event list into one report dict (see keys below)."""
    phases: dict[str, dict] = {}
    open_spans: dict[str, int] = {}
    stalls: list[dict] = []
    init_attempts: list[dict] = []
    metrics: dict[str, dict] = {}
    gauges: dict[str, object] = {}
    counters: dict[str, int] = {}
    faults_injected: list[dict] = []
    preemptions: list[dict] = []
    restarts = quarantines = checkpoints = marks = heartbeats = 0
    last_heartbeat = None
    resolution = None
    runs: list[str] = []
    draw_forms: list[str] = []
    sums_forms: list[str] = []
    dist_forms: list[str] = []
    row_formats: list[str] = []
    pair_tables: list[tuple] = []
    pass_forms: dict[str, list[str]] = {"gather": [], "scatter": []}
    field_splits: list[tuple] = []
    addr_calls: list[tuple] = []
    pairs_passes: list[tuple] = []
    field_scatters: list[tuple] = []
    als_forms: list[str] = []
    ranks_forms: list[str] = []
    closure_forms: list[str] = []
    t_wall = [e["t_wall"] for e in evts if "t_wall" in e]
    for e in evts:
        ev = e.get("ev")
        run = e.get("run")
        if run and run not in runs:
            runs.append(run)
        if ev in ("span_start", "span_end") and e.get("ranks_form"):
            # PageRank's fused sweep says how it reads the ranks table
            # (one gather group: resident; more: a group's window at a
            # time), its windows and the bf16 MXU passes of its one-hot
            # scatter product (pagerank:prepare, train:segment)
            form = (f"{e['ranks_form']} (rg {e.get('rg', '?')}, ws "
                    f"{e.get('ws', '?')})")
            if e.get("ranks_out_form") == "range":
                # sharded by destination range: a shard writes its own
                # rows of the output table (PR 44)
                form += ", written a shard's range"
            if "scatter_passes" in e:    # (a log from before PR 39: 6)
                form += f", scatter passes {e['scatter_passes']}"
            if "spmv_overlap" in e:      # (none before PR 45)
                # the chunk loop pipelined: a chunk's gather under the
                # chunk before's scatter, and on which share of chunks
                form += (f", overlap {e['spmv_overlap']} on "
                         f"{e.get('overlapped_chunk_share', 0):.4f} of "
                         f"the chunks")
            if form not in ranks_forms:
                ranks_forms.append(form)
        if ev in ("span_start", "closure_form") and e.get("closure_form"):
            # the closure says which form ran (closure:fit) and, where
            # the form was picked from the bytes each would hold
            # (transitive_closure.choose_form), what decided it
            if ev == "closure_form":
                form = (f"{e['closure_form']} picked: two byte matrices "
                        f"{e.get('dense_bytes', 0) / 1e9:.3f} GB against a "
                        f"pair buffer of {e.get('sparse_bytes', 0) / 1e9:.3f}"
                        f" GB, budget {e.get('budget_bytes', 0) / 1e9:.3f} "
                        f"GB")
            elif e["closure_form"] == "sparse":
                form = (f"sparse, a set of {e.get('capacity', '?')} pairs "
                        f"(a round's new {e.get('delta_capacity', '?')}, "
                        f"its candidates {e.get('join_capacity', '?')}) "
                        f"over {e.get('vertices', '?')} vertices, "
                        f"{e.get('resident_bytes', 0) / 1e9:.3f} GB "
                        f"carried")
            else:
                form = (f"{e['closure_form']}, compose "
                        f"{e.get('compose_form', '?')} over "
                        f"{e.get('v_padded', '?')} of "
                        f"{e.get('vertices', '?')} vertices held, a matrix "
                        f"{e.get('matrix_bytes', 0) / 1e9:.3f} GB")
            if form not in closure_forms:
                closure_forms.append(form)
        if ev == "span_start":
            open_spans[e.get("name", "?")] = \
                open_spans.get(e.get("name", "?"), 0) + 1
            # the training spans of the block-drawing samplers say how
            # the draw selected (sampling.draw_form)
            form = e.get("draw_form")
            if form and form not in draw_forms:
                draw_forms.append(form)
            # and those of k-means' scale path how a pass adds up the
            # per-cluster sums (pallas_lloyd.sums_form: mxu / vpu;
            # pallas_lloyd_wide.sums_form: scatter / mxu)
            form = e.get("sums_form")
            if form and form not in sums_forms:
                sums_forms.append(form)
            # and how it scores the distances (vpu: the lanes kernel;
            # mxu6: the wide pass's six bfloat16 passes, over how many
            # rows a tile of centres contracts: 128-deep slabs x 128)
            form = e.get("dist_form")
            if form and e.get("dist_depth"):
                form = f"{form} (depth {e['dist_depth']})"
            if form and form not in dist_forms:
                dist_forms.append(form)
            # and SSGD's how its rows are held (packed columns say
            # nothing; hashed rows say so) and the form of each of a
            # hashed step's passes (pallas_hashed.pass_form: vmem / xla)
            form = e.get("row_format")
            if form and form not in row_formats:
                row_formats.append(form)
            for which, seen in pass_forms.items():
                form = e.get(which + "_form")
                if form and form not in seen:
                    seen.append(form)
            # and how many of a row's fields those passes read by value
            # against their dictionaries (pallas_hashed.field_form)
            if "dict_fields" in e:
                split = (e["dict_fields"], e.get("dict_values", 0),
                         e.get("addr_fields", 0))
                if e.get("gather_form") == "fields":
                    # an indexed table: each field its own form, the
                    # ranges past VMEM left in HBM (field_form 'hbm')
                    split += (e.get("fields_hbm", 0),
                              e.get("table_bytes", 0),
                              e.get("fields_hbm_scatter_vmem", 0),
                              e.get("fields_hbm_scatter_xla", 0))
                if split not in field_splits:
                    field_splits.append(split)
            # and what a table of ragged (feature, value) rows holds
            # (models/ssgd_pairs.fields)
            if "pair_slots" in e:
                got = (e.get("pairs_rows", 0), e.get("pairs", 0),
                       e["pair_slots"], e.get("longest_row", 0),
                       e.get("pair_blocks_used", 0),
                       e.get("pair_blocks", 0),
                       e.get("pair_block_slots", 0),
                       e.get("table_bytes", 0),
                       e.get("rowsum_form", "?"),
                       e.get("gather_form", "xla"))
                if got not in pair_tables:
                    pair_tables.append(got)
            # and ALS' how R is held (a dense R says nothing; a ratings
            # list says so) with the form of each piece of a half-sweep
            # (models/als.segment_fields: xla / mosaic)
            if "als_gram_form" in e:
                gather = e.get("als_gather_form", "?")
                if e.get("gather_resident_share"):
                    # how often the resident range engages
                    gather += (f" with {e['gather_resident_share']} of "
                               f"the slots resident")
                if e.get("gather_cold_list") == "loader":
                    # what the kernel is handed ready made
                    # (models/als._gather_fields)
                    gather += (
                        f", {e.get('gather_cold_share', '?')} cold and "
                        f"listed by the loader ("
                        f"{sum(e.get('gather_cold_slots', []))} slots, "
                        f"{e.get('gather_list_bytes', 0)} B), a slot's "
                        f"rows by the {e.get('gather_slot_rows', '?')}, "
                        f"lanes by the {e.get('gather_lanes', '?')}")
                elif e.get("gather_cold_list") == "no room":
                    gather += " (no room for the kernel's lists)"
                solve = e.get("als_solve_form", "?")
                if e.get("solve_tile_systems"):
                    # the systems a tile holds in VMEM from the Gramian
                    # to the solved row
                    solve += f" in tiles of {e['solve_tile_systems']}"
                gram = e.get("als_gram_form", "?")
                if e.get("als_gram_layout"):
                    # how a batch reaches the solve: as the product
                    # makes it, or through a copy along the lanes
                    gram += f" by {e['als_gram_layout']}"
                form = (f"{e.get('layout', '?')} (gather: {gather}, "
                        f"gramians: {gram}, solve: {solve})")
                if form not in als_forms:
                    als_forms.append(form)
        elif ev == "span_end":
            name = e.get("name", "?")
            open_spans[name] = open_spans.get(name, 1) - 1
            p = phases.setdefault(
                name, {"count": 0, "total_seconds": 0.0,
                       "max_seconds": 0.0, "errors": 0})
            s = float(e.get("seconds", 0.0))
            p["count"] += 1
            p["total_seconds"] = round(p["total_seconds"] + s, 6)
            p["max_seconds"] = round(max(p["max_seconds"], s), 6)
            if not e.get("ok", True):
                p["errors"] += 1
        elif ev == "ssgd:addr_call":
            # what one by-address call of a hashed or indexed step runs,
            # said when it is traced (pallas_hashed._addr_call): the
            # rows a trip of its loop follow the fields it serves, the
            # index rows a chunk brings into SMEM are the block's
            call = (e.get("kernel", "?"), tuple(e.get("fields") or ()),
                    e.get("rows"), e.get("pairs"), e.get("smem_rows"))
            if call not in addr_calls:
                addr_calls.append(call)
        elif ev == "ssgd:field_scatter":
            # which form the sums of one field in HBM take, said when
            # the step is traced (pallas_hashed._slot_sums_fields)
            call = (e.get("kernel", "?"), e.get("form", "?"),
                    e.get("field"), e.get("range_slots", 0),
                    e.get("pieces", 0), e.get("vmem_bytes", 0))
            if call not in field_scatters:
                field_scatters.append(call)
        elif ev == "ssgd:pairs_pass":
            # what one pass over rows of (feature, value) pairs runs,
            # said when it is traced (ops/pairs.py, ops/pallas_pairs.py)
            call = (e.get("kernel", "?"), e.get("form", "?"),
                    e.get("vmem_bytes", 0), e.get("trip_pairs", 0),
                    e.get("blocks", 0))
            if call not in pairs_passes:
                pairs_passes.append(call)
        elif ev == "mark":
            marks += 1
        elif ev == "heartbeat":
            heartbeats += 1
            last_heartbeat = {
                "phase": e.get("phase"),
                "seconds_since_mark": e.get("seconds_since_mark"),
                "t_wall": e.get("t_wall"),
            }
        elif ev == "stall":
            stalls.append({"phase": e.get("phase"),
                           "seconds_since_mark":
                               e.get("seconds_since_mark")})
        elif ev == "backend_init":
            init_attempts.append({"attempt": e.get("attempt"),
                                  "outcome": e.get("outcome"),
                                  "seconds": e.get("seconds")})
            if e.get("outcome") == "ok":
                resolution = "ok"
        elif ev == "backend_unavailable":
            resolution = "backend_unavailable"
        elif ev == "restart":
            restarts += 1
        elif ev == "fault_injected":
            # chaos bookkeeping: a run under an injected fault plan
            # records every fire, so the report separates INJECTED
            # failures from organic ones (the restart/stall/quarantine
            # lines below count both)
            faults_injected.append({"point": e.get("point"),
                                    "hit": e.get("hit"),
                                    "kind": e.get("kind")})
        elif ev == "preempted":
            preemptions.append({"step": e.get("step"),
                                "tag": e.get("tag")})
        elif ev == "quarantine":
            quarantines += 1
        elif ev == "checkpoint_saved":
            checkpoints += 1
        elif ev == "metric" and "metric" in e:
            metrics[e["metric"]] = {
                "value": e.get("value"), "unit": e.get("unit"),
                "vs_baseline": e.get("vs_baseline")}
        elif ev == "gauge" and "name" in e:
            gauges[e["name"]] = e.get("value")
        elif ev == "counters":
            for k, v in (e.get("counters") or {}).items():
                counters[k] = counters.get(k, 0) + int(v)
    tree, hbm = span_tree(evts), hbm_summary(evts)
    if hbm:
        hbm["set_under"] = next(
            (p["path"] for p in tree if p.get("hbm_set_peak")), None)
    return {
        "runs": runs,
        "n_events": len(evts),
        "wall_seconds": (round(max(t_wall) - min(t_wall), 3)
                         if t_wall else 0.0),
        "phases": phases,
        "span_tree": tree,
        "hbm": hbm,
        "jit_functions": jit_functions(evts),
        "draw_forms": draw_forms,
        "sums_forms": sums_forms,
        "dist_forms": dist_forms,
        "row_formats": row_formats,
        "pair_tables": pair_tables,
        "pass_forms": pass_forms,
        "field_splits": field_splits,
        "addr_calls": addr_calls,
        "pairs_passes": pairs_passes,
        "field_scatters": field_scatters,
        "als_forms": als_forms,
        "ranks_forms": ranks_forms,
        "closure_forms": closure_forms,
        "unfinished_phases": sorted(
            k for k, v in open_spans.items() if v > 0),
        "marks": marks,
        "heartbeats": heartbeats,
        "last_heartbeat": last_heartbeat,
        "stalls": stalls,
        "backend_init": {"attempts": init_attempts,
                         "resolution": resolution},
        "restarts": restarts,
        "quarantines": quarantines,
        "checkpoints_saved": checkpoints,
        "faults_injected": faults_injected,
        "preemptions": preemptions,
        "counters": counters,
        "gauges": gauges,
        "metrics": metrics,
        "torn_lines": next((e["count"] for e in evts
                            if e.get("ev") == "_torn_lines"), 0),
    }


JIT_ROW_MIN_SECONDS = 0.01   # smaller functions share one line


def _render_jit_functions(rows: list[dict]) -> list[str]:
    """The per-function table: a row for every function that took
    ``JIT_ROW_MIN_SECONDS`` or was traced more than once (marked
    ``*``, with the span each trace happened under), one line for the
    rest."""
    if not rows:
        return []
    shown, rest = [], []
    for r in rows:
        (shown if _jit_total(r) >= JIT_ROW_MIN_SECONDS or r["traced"] > 1
         else rest).append(r)
    lines = ["compiles by function (s; load is the part of compile a "
             "cache hit took; * traced more than once):"]
    width = max([len(r["fun"]) for r in shown] + [8])
    lines.append(f"  {'function'.ljust(width)}  traced    trace    lower"
                 f"  compile     load  cache  under")
    for r in shown:
        cache = (f"{r['hits']}h/{r['misses']}m"
                 if r["hits"] or r["misses"] else "-")
        lines.append(
            f"  {r['fun'].ljust(width)}  {r['traced']:>4} "
            f"{'*' if r['traced'] > 1 else ' '} {r['trace_s']:>8.3f} "
            f"{r['lower_s']:>8.3f} {r['compile_s']:>8.3f} "
            f"{r['load_s']:>8.3f}  {cache:>5}  "
            f"{', '.join(r['under']) or '-'}")
    if rest:
        lines.append(
            f"  {len(rest)} more under {JIT_ROW_MIN_SECONDS} s each: "
            f"{sum(map(_jit_total, rest)):.3f} s")
    return lines


def render(s: dict) -> str:
    """Human rendering of :func:`summarize`'s dict."""
    lines = [
        f"runs: {len(s['runs'])} ({', '.join(s['runs']) or '-'})",
        f"events: {s['n_events']}  wall: {s['wall_seconds']}s  "
        f"marks: {s['marks']}  heartbeats: {s['heartbeats']}",
    ]
    if s["span_tree"]:
        lines.append("phase durations (self = duration minus child "
                     "spans):")
        if s.get("hbm"):
            lines.append("  (hbm: the rise of the bytes in use over the "
                         "span -> in use at its end; *peak where the "
                         "peak last rose)")
        for p in s["span_tree"]:
            err = f"  errors: {p['errors']}" if p["errors"] else ""
            hbm = ""
            if "hbm_in_use_bytes" in p:
                hbm = (f", hbm {p['hbm_rise_bytes'] / 1e9:+.3f} GB -> "
                       f"{p['hbm_in_use_bytes'] / 1e9:.3f} GB"
                       + (" *peak" if p.get("hbm_set_peak") else ""))
            lines.append(
                f"  {'  ' * p['depth']}{p['name']}: "
                f"{p['total_seconds']}s total over {p['count']} span(s), "
                f"max {p['max_seconds']}s, self {p['self_seconds']}s"
                f"{hbm}{err}")
        hbm = s.get("hbm")
        if hbm:
            lines.append(
                f"hbm: peak {hbm['peak_bytes'] / 1e9:.3f} GB set under "
                f"{' > '.join(hbm['set_under'] or ['no span'])}, "
                f"{hbm['in_use_bytes'] / 1e9:.3f} GB in use at "
                f"the last span's end ({hbm['last_span']})"
                + (f"; the fullest of {hbm['devices']} devices"
                   if hbm["devices"] > 1 else ""))
    for name in s["unfinished_phases"]:
        lines.append(f"  {name}: UNFINISHED (no span_end recorded)")
    lines.extend(_render_jit_functions(s.get("jit_functions") or []))
    if s.get("row_formats"):
        lines.append(f"row format: {', '.join(s['row_formats'])}")
    if s.get("draw_forms"):
        lines.append(f"block draw: {', '.join(s['draw_forms'])}")
    for which, seen in (s.get("pass_forms") or {}).items():
        if seen:
            lines.append(f"{which} pass: {', '.join(seen)}")
    for n_dict, n_values, n_addr, *indexed in s.get("field_splits") or ():
        line = (f"fields by value: {n_dict} ({n_values} values), "
                f"by address: {n_addr}")
        if indexed:
            line += (f", in HBM: {indexed[0]} (a table of "
                     f"{indexed[1] / 1e6:.1f} MB; the sums of "
                     f"{indexed[2]} in VMEM a call, of {indexed[3]} "
                     f"through XLA)")
        lines.append(line)
    for (rows, pairs, slots, longest, used, blocks, block_slots, table,
         rowsum, form) in s.get("pair_tables") or ():
        where = "resident in VMEM a pass" if form == "vmem" else "in HBM"
        lines.append(
            f"pairs: {rows} rows of {pairs} (feature, value) pairs, "
            f"longest {longest}, in {used} of {blocks} blocks of "
            f"{block_slots} slots ({slots - pairs} of {slots} slots hold "
            f"no pair: {(1 - pairs / max(slots, 1)) * 100:.2f}%); "
            f"{table / 1e6:.1f} MB of weights {where}, row sums by "
            f"{rowsum}")
    for kernel, form, vmem, trip, blocks in s.get("pairs_passes") or ():
        line = f"pairs pass: {kernel} ({form}) over {blocks} blocks a call"
        if form == "vmem":
            line += (f", {vmem / 1e6:.1f} MB of VMEM asked, {trip} pairs "
                     f"a trip")
        lines.append(line)
    for kernel, fields, rows, pairs, smem_rows in s.get("addr_calls") or ():
        lines.append(f"by-address call: {kernel} over fields "
                     f"{list(fields)}: {rows} rows a trip ({pairs} pairs), "
                     f"{smem_rows} index rows a chunk in SMEM")
    for kernel, form, field, slots, pieces, vmem in \
            s.get("field_scatters") or ():
        line = (f"field scatter: {kernel} ({form}) over field {field}, a "
                f"range of {slots} slots")
        if form == "vmem":
            line += (f" in {pieces} piece(s) of an accumulator in VMEM, "
                     f"{vmem / 1e6:.1f} MB asked")
        lines.append(line)
    if s.get("als_forms"):
        lines.append(f"R layout: {', '.join(s['als_forms'])}")
    if s.get("ranks_forms"):
        lines.append(f"ranks table: {', '.join(s['ranks_forms'])}")
    if s.get("closure_forms"):
        c = s.get("counters") or {}
        lines.append(
            f"closure: {'; '.join(s['closure_forms'])}; "
            f"{c.get('closure.rounds', '?')} round(s), "
            f"{c.get('closure.pairs', '?')} pairs"
            + (f" ({c['closure.sparse.candidates']} candidates joined, "
               f"{c.get('closure.sparse.new_pairs', 0)} found new"
               + (", A BUFFER OVERFLOWED"
                  if c.get("closure.sparse.overflow") else "") + ")"
               if "closure.sparse.candidates" in c else ""))
    if s.get("dist_forms"):
        lines.append(f"distances: {', '.join(s['dist_forms'])}")
    if s.get("sums_forms"):
        lines.append(f"cluster sums: {', '.join(s['sums_forms'])}")
    hb = s["last_heartbeat"]
    lines.append(
        "last heartbeat: "
        + (f"phase={hb['phase']} seconds_since_mark="
           f"{hb['seconds_since_mark']}" if hb else "none recorded"))
    lines.append(
        f"stalls: {len(s['stalls'])}"
        + ("".join(f"\n  stalled in {st['phase']} "
                   f"({st['seconds_since_mark']}s since last mark)"
                   for st in s["stalls"]) if s["stalls"] else ""))
    bi = s["backend_init"]
    if bi["attempts"] or bi["resolution"]:
        outcomes = ", ".join(
            f"#{a['attempt']} {a['outcome']} ({a['seconds']}s)"
            for a in bi["attempts"])
        lines.append(f"backend init: {outcomes or '-'} -> "
                     f"{bi['resolution'] or 'unresolved'}")
    lines.append(f"restarts: {s['restarts']}  "
                 f"quarantines: {s['quarantines']}  "
                 f"checkpoints saved: {s['checkpoints_saved']}")
    if s.get("faults_injected"):
        fired = ", ".join(f"{f['point']}#{f['hit']}={f['kind']}"
                          for f in s["faults_injected"])
        lines.append(
            f"injected faults: {len(s['faults_injected'])} ({fired}) — "
            f"failures above include these ON-PURPOSE ones")
    if s.get("preemptions"):
        steps = ", ".join(str(p["step"]) for p in s["preemptions"])
        lines.append(
            f"preemptions: {len(s['preemptions'])} (graceful boundary "
            f"exit at step {steps}; resume is bitwise)")
    if s["counters"]:
        lines.append("counters: " + ", ".join(
            f"{k}={v}" for k, v in sorted(s["counters"].items())))
        bw = s["counters"].get("comm.bytes_wire")
        bl = s["counters"].get("comm.bytes_logical")
        if bw and bl:
            # the comms layer's achieved ratio (parallel/comms.py):
            # logical f32 payload vs bytes actually put on the wire by
            # the selected --comm schedule. Uncompressed f32 schedules
            # legitimately put MORE on the wire than the payload (a
            # ring allreduce moves 2(n-1)/n of it) — say so instead of
            # printing a "0.7x compression" that reads as a bug.
            if bl >= bw:
                desc = f"({bl / bw:.1f}x compression)"
            else:
                desc = (f"({bw / bl:.1f}x wire/logical — "
                        f"uncompressed ring allreduce moves "
                        f"2(n-1)/n of the payload)")
            lines.append(
                f"comm: {bw} bytes wire / {bl} logical {desc} over "
                f"{s['counters'].get('comm.syncs', 0)} sync(s), "
                f"{s['counters'].get('comm.rounds', 0)} collective "
                f"round(s)")
        gw = s["counters"].get("graph.combine_bytes_wire")
        gdr = s["counters"].get("graph.combine_bytes_dense_ring")
        if gw and gdr:
            # the graph engine's sparse rank combine (graphs/engine.py
            # via comms.emit_rank_combine_counters): pair-exchange
            # bytes actually accounted vs what a dense O(V) ring psum
            # of the rank vector would have moved — <1x means the
            # graph was dense enough that combine='dense' was (or
            # should have been) selected
            lines.append(
                f"graph rank combine: {gw} bytes wire vs {gdr} "
                f"dense-ring equivalent ({gdr / gw:.1f}x sparser) over "
                f"{s['counters'].get('graph.combine_syncs', 0)} "
                f"sweep(s)")
        sreq = s["counters"].get("serve.requests")
        if sreq:
            # the serving layer's latency line (serve/server.py
            # emit_counters): request/batch/shed counters + the
            # qps/p50/p99/queue-depth gauges of the newest run
            g = s["gauges"]
            shed = s["counters"].get("serve.shed", 0)
            lines.append(
                f"serve: {sreq} request(s) in "
                f"{s['counters'].get('serve.batches', 0)} "
                f"micro-batch(es), {g.get('serve.qps', '?')} req/s, "
                f"p50 {g.get('serve.p50_ms', '?')} ms / "
                f"p99 {g.get('serve.p99_ms', '?')} ms, {shed} shed, "
                f"max queue depth {g.get('serve.queue_depth', '?')}")
        creq = s["counters"].get("serve.cluster_requests")
        if creq:
            # the distributed serving plane (cluster/router.py
            # emit_gauges + counters): router-side client latency,
            # degradation evidence (sheds / re-routes), hot-swaps
            g = s["gauges"]
            lines.append(
                f"cluster serve: {creq} request(s), "
                f"{s['counters'].get('serve.cluster_replies', 0)} "
                f"replied, {g.get('serve.cluster_qps', '?')} req/s, "
                f"p50 {g.get('serve.cluster_p50_ms', '?')} ms / "
                f"p99 {g.get('serve.cluster_p99_ms', '?')} ms, "
                f"{s['counters'].get('serve.cluster_sheds', 0)} "
                f"shed, "
                f"{s['counters'].get('serve.cluster_reroutes', 0)} "
                f"re-route(s), "
                f"{s['counters'].get('serve.cluster_swaps', 0)} "
                f"hot-swap(s)")
            cmb = s["counters"].get("serve.cluster_merge_bytes_wire")
            if cmb:
                lines.append(
                    f"cluster serve merge: {cmb} candidate bytes "
                    f"over the wire (sharded top-k)")
        merges = s["counters"].get("ssp.merges")
        if merges:
            # the stale-synchronous layer (parallel/ssp.py): observed
            # contribution staleness (mean/max ages at the merges),
            # ticks the seeded straggle schedule claimed, ticks the
            # clock-vector gate held back, membership epochs
            # (parallel/membership.py ring renegotiations), and — when
            # the bench's BSP A/B ran — the measured stall time the
            # window structure avoided
            g = s["gauges"]
            c = s["counters"]
            line = (f"ssp: {merges} merge(s) at bound "
                    f"{g.get('ssp.bound', '?')}, staleness mean "
                    f"{g.get('ssp.mean_staleness', '?')} / max "
                    f"{g.get('ssp.max_staleness', 0)}, "
                    f"{c.get('ssp.straggle_ticks', 0)} straggled / "
                    f"{c.get('ssp.gated_ticks', 0)} gated tick(s), "
                    f"{c.get('ssp.membership_epochs', 0)} membership "
                    f"epoch(s)")
            stall = c.get("ssp.stall_ms_avoided")
            if stall is not None:
                line += (f", {stall} ms stall avoided vs BSP "
                         f"(measured A/B)")
            lines.append(line)
        hid = s["counters"].get("comm.overlap_hidden_ms")
        exposed = s["counters"].get("comm.sync_ms")
        if hid is not None or exposed is not None:
            # overlap efficiency (parallel/comms.py bucket pipeline):
            # hidden = comm time the double-buffered schedule removed
            # vs its sequential A/B (measured host-side), exposed =
            # comm time still visible over the dense-compute baseline;
            # the fraction is how much of the schedule's comm the
            # pipeline hid behind compute
            hid = hid or 0
            total = hid + (exposed or 0)
            frac = (hid / total) if total else 0.0
            lines.append(
                f"comm overlap: {hid} ms hidden behind compute "
                f"({frac:.0%} of {total} ms comm time)")
        recov = s["counters"].get("cluster.recoveries")
        if recov:
            # coordinator crash tolerance (cluster/wal.py +
            # coordinator recovery): how many times the control plane
            # died and came back, the median detect->recover->first-
            # recommitted-window latency (launcher-measured gauge),
            # and how many durable ledger records the recoveries
            # replayed; reconnect/retry behavior shows per-worker in
            # the cluster.* column table
            g = s["gauges"]
            c = s["counters"]
            lines.append(
                f"coordinator: {recov} recover(ies), median "
                f"{g.get('cluster.recovery_ms_p50', '?')} ms, "
                f"{c.get('cluster.wal_records_replayed', 0)} WAL "
                f"record(s) replayed "
                f"({c.get('cluster.wal_quarantines', 0)} torn-tail "
                f"quarantine(s), {c.get('cluster.reconnects', 0)} "
                f"worker reconnect(s), "
                f"{c.get('cluster.heartbeat_retries', 0)} heartbeat "
                f"retr(ies), {c.get('cluster.dedup_pushes', 0)} "
                f"deduped re-push(es))")
        wire_tx = (s["counters"].get("cluster.wire_push_bytes", 0)
                   + s["counters"].get("cluster.wire_center_bytes", 0))
        if wire_tx:
            # compressed cluster wire (cluster/ + the comms host
            # codecs): measured frame bytes by direction, how many
            # pulls rode version deltas vs fell back to dense
            # snapshots (resume/rejoin), and how many pushes
            # overlapped the next window's compute
            c = s["counters"]

            def _mb(n):
                return (f"{n / 1e6:.2f} MB" if n >= 10_000
                        else f"{n / 1e3:.1f} KB")

            lines.append(
                f"cluster wire: "
                f"{_mb(c.get('cluster.wire_push_bytes', 0))} pushed "
                f"/ {_mb(c.get('cluster.wire_center_bytes', 0))} "
                f"pulled "
                f"({c.get('cluster.delta_pulls', 0)} delta pull(s), "
                f"{c.get('cluster.pull_dense_fallbacks', 0)} dense "
                f"fallback(s), {c.get('cluster.async_pushes', 0)} "
                f"overlapped push(es))")
        rs_pulled = s["counters"].get("rowstore.rows_pulled")
        rs_pushed = s["counters"].get("rowstore.rows_pushed")
        if rs_pulled or rs_pushed:
            # sharded row store (cluster/rowstore.py): how sparse the
            # row traffic actually was — rows pulled vs the dense
            # row-pull baseline (every leaf whole, every pull), sparse
            # wire bytes vs what dense snapshots would have shipped,
            # the rpc retries the framed row wire absorbed, and the
            # worst per-row staleness any merge gated on
            c = s["counters"]
            g = s["gauges"]
            dense_rows = c.get("rowstore.pull_rows_dense", 0)
            frac = ((rs_pulled or 0) / dense_rows) if dense_rows \
                else 0.0
            wire = (c.get("rowstore.wire_push_bytes", 0)
                    + c.get("rowstore.wire_pull_bytes", 0))
            lines.append(
                f"rowstore: {rs_pulled or 0} row(s) pulled of "
                f"{dense_rows} dense ({frac:.0%} sparse-pull "
                f"fraction), {rs_pushed or 0} row(s) pushed, "
                f"{wire / 1e6:.2f} MB sparse wire vs "
                f"{c.get('rowstore.wire_dense_bytes', 0) / 1e6:.2f}"
                f" MB dense, "
                f"{c.get('rowstore.rpc_retries', 0)} rpc retr(ies), "
                f"max row staleness "
                f"{g.get('rowstore.max_row_staleness', 0)}")
        resh = s["counters"].get("reshard.syncs")
        if resh:
            # device-side resharding (parallel/partition.py): layout
            # changes lowered to on-device collective programs; the
            # avoided figure is what the old host gather+re-put would
            # have moved over PCIe for the same transitions
            c = s["counters"]
            lines.append(
                f"reshard: {resh} layout change(s), "
                f"{c.get('reshard.leaves', 0)} leaf move(s), "
                f"{c.get('reshard.bytes_wire', 0) / 1e6:.1f} MB wire "
                f"(host round-trip avoided: "
                f"{c.get('reshard.bytes_host_avoided', 0) / 1e6:.1f}"
                f" MB)")
        n_res = s["counters"].get("tune.knobs_resolved", 0)
        n_exp = s["counters"].get("tune.knobs_explicit", 0)
        n_def = s["counters"].get("tune.knobs_defaulted", 0)
        if n_res or n_exp or n_def:
            # platform-aware autotuner (tpu_distalg/tune/): which rig
            # profile shaped this run's geometry, how many knobs came
            # from the cost model vs explicit flags vs the default
            # tables, and — when the run measured itself — the
            # predicted-vs-measured step delta (the cost model's
            # honesty check; per-knob WHYs live in the tune_knob
            # events)
            g = s["gauges"]
            line = (f"tune: profile {g.get('tune.profile', '?')}, "
                    f"{n_res} knob(s) resolved / {n_exp} explicit / "
                    f"{n_def} defaulted")
            pred = g.get("tune.predicted_step_ms")
            meas = g.get("tune.measured_step_ms")
            if pred is not None:
                line += f", predicted sync {pred:.3f} ms"
            if meas is not None:
                line += f", measured step {meas:.3f} ms"
            if pred is not None and meas is not None and meas:
                line += f" ({pred / meas:.2f}x predicted/measured)"
            lines.append(line)
    if s["gauges"]:
        lines.append("gauges: " + ", ".join(
            f"{k}={v}" for k, v in sorted(s["gauges"].items())))
    if s["metrics"]:
        lines.append("metrics:")
        for name, m in s["metrics"].items():
            vs = (f"  ({m['vs_baseline']}x baseline)"
                  if m.get("vs_baseline") is not None else "")
            lines.append(f"  {name}: {m['value']} {m['unit']}{vs}")
    if s["torn_lines"]:
        lines.append(f"torn lines skipped: {s['torn_lines']}")
    return "\n".join(lines)


# counters the merged multi-directory rendering breaks out into
# per-worker columns (the cluster runtime's per-process telemetry
# dirs: DIR/coordinator + DIR/worker-N)
PER_WORKER_PREFIXES = ("ssp.", "cluster.")

# The TDA102 waiver table: every counter/gauge emitted anywhere in the
# library must either appear in a renderer above, match a per-worker
# family, or be listed HERE — an explicit statement that the generic
# "counters:"/"gauges:" lines are its whole story (no derived summary
# line owed). A `family.*` entry waives a prefix, including f-string
# names like the per-code `lint.TDAxxx` counters. Adding a counter
# without deciding its rendering is exactly the drift TDA102 exists
# to stop — extend a renderer or extend this table, on purpose.
SUMMARY_ONLY_COUNTERS = (
    "checkpoints_saved",        # rendered via the checkpoint_saved
    #                             event count, not the counter
    "restarts",                 # ditto: the restart event line
    "quarantines",
    "preemptions",
    "closure.capacity_regrows",
    "closure.form",             # the closure line says which and why
    "closure.pairs",            # (closure:fit's fields, the
    "closure.rounds",           #  closure_form event, these two)
    "data.*",                   # gather/h2d byte+batch bookkeeping
    "faults.*",                 # the fault table reads the events
    "graph.ingest_edges",
    "graph.edges_streamed",
    "lint.*",                   # per-code counts + files/cached/
    #                             graph_seconds; the span carries time
    "protocol.frame_kinds",     # contract size; the span carries time
    "serve.artifact_reread",
    "serve.failed_batches",
    "serve.merge_bytes_wire",
    "spmv_plan_rejections",
    "spmv_slots_padded",        # pagerank:prepare's padding_share says it
    "pagerank_shard_*",         # pagerank:dedup's shard_edges and
    #                             pagerank:exchange's overflow say them
    "reshard.bytes_logical",    # the reshard line renders wire/host;
    #                             logical is accounting input only
)


def _natural_key(path: str):
    """Numeric-aware sort key: ``worker-10`` sorts after ``worker-9``,
    not between ``worker-1`` and ``worker-2``."""
    import re

    return [int(p) if p.isdigit() else p
            for p in re.split(r"(\d+)", os.path.basename(
                os.path.normpath(path)))]


def expand_dirs(paths: list[str]) -> list[str]:
    """Resolve the report inputs: each path is an event file, an event
    directory, or a PARENT of per-worker event directories (the
    ``tda cluster --telemetry-dir`` layout) — parents expand to their
    event-bearing children, sorted by name so worker columns render in
    slot order."""
    out: list[str] = []
    for path in paths:
        if os.path.isfile(path):
            out.append(path)
            continue
        has_own = bool(glob.glob(os.path.join(path,
                                              "events-*.jsonl")))
        children = sorted(
            (d for d in glob.glob(os.path.join(path, "*"))
             if os.path.isdir(d)
             and glob.glob(os.path.join(d, "events-*.jsonl"))),
            key=_natural_key)
        if children:
            # a parent of per-worker dirs; its own stray events (if
            # any) still count as one more column
            out.extend(([path] if has_own else []) + children)
            continue
        # no event-bearing children: the dir itself (load_events
        # raises its remedy-carrying FileNotFoundError when it holds
        # nothing either)
        out.append(path)
    return out


def summarize_multi(paths: list[str]) -> dict:
    """Per-directory summaries + one MERGED view: counters summed,
    events/metrics/faults pooled — ``{"merged": ..., "workers":
    {label: summary}}`` where labels are the directory basenames."""
    workers: dict[str, dict] = {}
    all_events: list[dict] = []
    for p in paths:
        evts = load_events(p)
        label = os.path.basename(os.path.normpath(p)) or p
        base, n = label, 2
        while label in workers:
            label = f"{base}#{n}"
            n += 1
        workers[label] = summarize(evts)
        all_events.extend(evts)
    return {"merged": summarize(all_events), "workers": workers}


def render_multi(multi: dict) -> str:
    """The merged rendering: the usual report over the pooled events,
    then a per-worker column table for the ``ssp.*`` / ``cluster.*``
    counters — how a cluster run's straggle/gate/push behavior reads
    side by side across processes."""
    lines = [f"merged over {len(multi['workers'])} telemetry dir(s): "
             + ", ".join(multi["workers"]),
             render(multi["merged"])]
    names = sorted({
        name
        for s in multi["workers"].values()
        for name in s["counters"]
        if name.startswith(PER_WORKER_PREFIXES)})
    if names:
        labels = list(multi["workers"])
        widths = [max(len(lb), 8) for lb in labels]
        name_w = max(len(n) for n in names)
        header = " ".join([" " * name_w] + [
            lb.rjust(w) for lb, w in zip(labels, widths)])
        lines.append("per-worker counters (ssp.*/cluster.*):")
        lines.append("  " + header)
        for name in names:
            row = [name.ljust(name_w)]
            for lb, w in zip(labels, widths):
                v = multi["workers"][lb]["counters"].get(name, "-")
                row.append(str(v).rjust(w))
            lines.append("  " + " ".join(row))
    return "\n".join(lines)


def report_main(path, as_json: bool = False, out=print) -> int:
    """The ``tda report <dir>...`` entry point: one directory renders
    the classic single-run report; several (or a parent of per-worker
    dirs) render the merged report with per-worker counter columns."""
    paths = expand_dirs([path] if isinstance(path, str) else
                        list(path))
    if len(paths) == 1:
        summary = summarize(load_events(paths[0]))
        out(json.dumps(summary, indent=2) if as_json
            else render(summary))
        return 0
    multi = summarize_multi(paths)
    out(json.dumps(multi, indent=2) if as_json
        else render_multi(multi))
    return 0
