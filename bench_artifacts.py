"""Shared loader for recorded bench artifacts (``BENCH_r*.json``).

Both consumers of "the newest parsed bench artifact" — bench.py's
perf-regression tripwire and ``scripts/check_readme_claims.py``'s
README reconciliation — MUST resolve it identically, or a drift in one
silently desynchronizes the two checks; this module is the single
resolution. Stdlib only (the claims checker runs without jax).
"""

from __future__ import annotations

import glob
import json
import os
import re
import socket


def load_newest_metrics(search_dir: str, path: str | None = None,
                        rig: str | None = None):
    """``(artifact_name, {metric: value})`` from ``path`` or from the
    newest ``BENCH_r*.json`` under ``search_dir`` whose ``parsed``
    field carries metrics. Artifacts are tried newest-round first; one
    whose ``parsed`` is null (a run that died before any metric line)
    falls through to the previous round. Pre-summary artifacts carry a
    single metric line instead of the ``all_metrics`` map; both shapes
    load. ``(None, {})`` when nothing parses.

    ``rig`` is the CLAIMING rig (default: this hostname): an artifact
    whose summary carries a DIFFERENT rig tag is skipped — numbers
    measured on another machine are not a reference this machine's
    claims or tripwire should reconcile against. Artifacts predating the rig tag (no ``rig`` field) still
    load. An explicit ``path`` always loads verbatim."""
    if rig is None:
        rig = socket.gethostname()
    if path is not None:
        paths = [path]
    else:
        arts = []
        for p in glob.glob(os.path.join(search_dir, "BENCH_r*.json")):
            m = re.search(r"BENCH_r(\d+)\.json$", p)
            if m:
                arts.append((int(m.group(1)), p))
        paths = [p for _, p in sorted(arts, reverse=True)]
    for p in paths:
        try:
            with open(p) as f:
                parsed = json.load(f).get("parsed")
        except (OSError, ValueError):
            continue
        if not isinstance(parsed, dict):
            continue
        art_rig = parsed.get("rig")
        if path is None and art_rig is not None and art_rig != rig:
            # a round measured on a DIFFERENT rig (the summary's rig
            # tag) cannot anchor this rig's claims — tuned geometry
            # especially is per-rig
            continue
        metrics = parsed.get("all_metrics")
        if not isinstance(metrics, dict):
            if isinstance(parsed.get("value"), (int, float)) \
                    and parsed.get("metric"):
                metrics = {parsed["metric"]: parsed["value"]}
            else:
                continue
        return os.path.basename(p), metrics
    return None, {}
