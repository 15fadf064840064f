"""Telemetry contract — TDA102 (and the bench-metric collector the
tests share).

Two drift directions, both review-caught historically, both
cross-module:

* a counter/gauge is emitted somewhere in the library but
  ``telemetry/report.py`` never renders it and never waives it — the
  signal exists in JSONL and nowhere a human looks. Every emitted name
  must appear in report.py (a literal in a renderer), match a
  ``PER_WORKER_PREFIXES`` family (rendered as per-worker columns), or
  be listed in ``SUMMARY_ONLY_COUNTERS`` (the explicit "generic
  counters: line is enough" waiver; ``name.*`` entries waive a
  family). F-string names (``f"lint.{code}"``) are checked by their
  static prefix against the family entries.

* a bench metric line's name drifts from ``ALL_METRIC_NAMES`` — an
  emission the canonical set does not know (rogue), or a canonical
  name nothing emits any more (canonical-but-unemitted), so the
  tripwire and the claims checker reconcile against a set that is
  not the one the bench prints. This was an AST tripwire
  duplicated across three test files; the collector here
  (:func:`metric_contract` / :func:`contract_problems` /
  :func:`assert_registered`) is now the ONE implementation — the
  engine runs it as TDA102 and the tests call it directly.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re

from tpu_distalg.analysis.project import ProjectRule, _joined_pattern

#: the tuple name that declares the canonical bench metric set
CANONICAL_TUPLE = "ALL_METRIC_NAMES"

#: the report-side waiver table (lives in telemetry/report.py)
WAIVER_TUPLE = "SUMMARY_ONLY_COUNTERS"


# ---------------------------------------------------------------------
# the bench-metric collector (shared with tests/)


@dataclasses.dataclass
class MetricContract:
    """One module's metric emission surface vs its canonical set."""

    path: str
    canonical: tuple
    canonical_line: int
    literals: dict          # name -> first emission line
    patterns: list          # (compiled regex, line) for f-string names


def metric_contract_from_source(source: str,
                                path: str = "bench.py"
                                ) -> MetricContract | None:
    """Parse a module's ``{"metric": ...}`` emission dicts and its
    ``ALL_METRIC_NAMES`` tuple. None when the module declares no
    canonical set."""
    tree = ast.parse(source)
    canonical, can_line = None, 0
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == CANONICAL_TUPLE \
                and isinstance(stmt.value, (ast.Tuple, ast.List)):
            canonical = tuple(
                e.value for e in stmt.value.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, str))
            can_line = stmt.lineno
    if canonical is None:
        return None
    literals: dict = {}
    patterns: list = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Dict):
            continue
        for k, v in zip(node.keys, node.values):
            if not (isinstance(k, ast.Constant)
                    and k.value == "metric"):
                continue
            if isinstance(v, ast.Constant) and isinstance(v.value,
                                                          str):
                literals.setdefault(v.value, node.lineno)
            elif isinstance(v, ast.JoinedStr):
                patterns.append((re.compile(_joined_pattern(v)),
                                 node.lineno))
    return MetricContract(path=path, canonical=canonical,
                          canonical_line=can_line,
                          literals=literals, patterns=patterns)


def bench_contract(repo_root: str | None = None) -> MetricContract:
    """The repo's bench.py contract (the tests' entry point)."""
    root = repo_root or os.getcwd()
    path = os.path.join(root, "bench.py")
    with open(path, encoding="utf-8") as f:
        contract = metric_contract_from_source(f.read(), path)
    if contract is None:
        raise ValueError(f"{path} declares no {CANONICAL_TUPLE}")
    return contract


def contract_problems(contract: MetricContract):
    """Both drift directions: ``(unemitted, rogue)`` where
    ``unemitted`` is canonical names with no emission site and
    ``rogue`` maps non-canonical literal emissions to their line."""
    unemitted = [
        n for n in contract.canonical
        if n not in contract.literals
        and not any(p.match(n) for p, _ in contract.patterns)]
    rogue = {n: line for n, line in sorted(contract.literals.items())
             if n not in contract.canonical}
    return unemitted, rogue


def assert_registered(names, repo_root: str | None = None) -> None:
    """Test helper: each name is canonical AND has a live emission
    site — the one spelling of the membership checks that used to be
    re-implemented per test file."""
    contract = bench_contract(repo_root)
    missing = [n for n in names if n not in contract.canonical]
    assert not missing, (
        f"not in {CANONICAL_TUPLE}: {missing}")
    unemitted, _ = contract_problems(contract)
    dead = [n for n in names if n in unemitted]
    assert not dead, (
        f"registered in {CANONICAL_TUPLE} but no emission site in "
        f"bench.py (renamed phase metric?): {dead}")


# ---------------------------------------------------------------------
# the rule


def _star_covered(name: str, entries) -> bool:
    for w in entries:
        if w == name:
            return True
        if w.endswith("*") and name.startswith(w[:-1]):
            return True
    return False


def _prefix_covered(prefix: str, families) -> bool:
    return any(prefix.startswith(p) or p.startswith(prefix)
               for p in families if p)


class TelemetryContract(ProjectRule):
    code = "TDA102"
    name = "telemetry emission outside the rendered/waived contract"
    invariant = ("every emitted counter/gauge is rendered or "
                 "explicitly waived in telemetry/report.py, and every "
                 "bench metric line is canonical in ALL_METRIC_NAMES "
                 "(and vice versa)")

    def check_project(self, project):
        yield from self._check_counters(project)
        yield from self._check_metrics(project)

    def _check_counters(self, project):
        reports = [s for s in project if s.get("report_like")]
        if not reports:
            return   # no report module on this lint surface
        rendered: set = set()
        waivers: list = []
        families: list = []
        for r in reports:
            rendered.update(r["report_strings"])
            waivers.extend(r["str_tuples"].get(
                WAIVER_TUPLE, {}).get("values", []))
            families.extend(r["str_tuples"].get(
                "PER_WORKER_PREFIXES", {}).get("values", []))
        families += [w[:-1] for w in waivers if w.endswith("*")]
        report_paths = {r["path"] for r in reports}
        seen: set = set()
        emitted_names: set = set()
        emitted_prefixes: set = set()
        for s in project.library():
            if s["path"] in report_paths:
                continue
            for emit in s["counter_emits"]:
                name, prefix = emit["name"], emit["prefix"]
                if name is not None:
                    emitted_names.add(name)
                elif prefix:
                    emitted_prefixes.add(prefix)
                key = (s["path"], name or prefix, emit["line"])
                if key in seen:
                    continue
                seen.add(key)
                if name is not None:
                    ok = name in rendered \
                        or _prefix_covered(name, families) \
                        or _star_covered(name, waivers)
                else:
                    ok = _prefix_covered(prefix, families)
                if ok:
                    continue
                what = f"'{name}'" if name is not None \
                    else f"f-string family '{prefix}…'"
                yield self.project_violation(
                    project, s["path"], emit["line"],
                    f"{emit['kind']} {what} is emitted but "
                    f"telemetry/report.py neither renders nor waives "
                    f"it — a signal nobody can see; add a report "
                    f"line, or list it in {WAIVER_TUPLE} "
                    f"('name' or 'family.*') to state that the "
                    f"generic counters rendering is enough")
        # the reverse direction — waiver rot. An entry matching zero
        # emissions is a retired counter's ghost: it reads as "this
        # signal is accounted for" while waiving nothing, exactly the
        # drift the unused-suppression detector stops for inline pins.
        # Only decidable when the surface actually emits (a lone
        # report-module lint sees no emissions and must stay silent).
        if not emitted_names and not emitted_prefixes:
            return
        for r in reports:
            decl = r["str_tuples"].get(WAIVER_TUPLE)
            if decl is None:
                continue
            for entry in decl["values"]:
                if entry.endswith("*"):
                    fam = entry[:-1]
                    used = any(n.startswith(fam)
                               for n in emitted_names) \
                        or any(fam.startswith(p) or p.startswith(fam)
                               for p in emitted_prefixes)
                else:
                    used = entry in emitted_names \
                        or any(entry.startswith(p)
                               for p in emitted_prefixes)
                if used:
                    continue
                yield self.project_violation(
                    project, r["path"], decl["line"],
                    f"waiver '{entry}' in {WAIVER_TUPLE} matches no "
                    f"emitted counter/gauge on this surface — a "
                    f"retired signal's ghost; remove the entry (or "
                    f"restore the emission it claims to waive)")

    def _check_metrics(self, project):
        # ONE implementation of the drift checks: rebuild the
        # collector's MetricContract from the summary fields and run
        # contract_problems — the rule and the tests cannot diverge
        for s in project.library():
            decl = s["str_tuples"].get(CANONICAL_TUPLE)
            if decl is None:
                continue
            literals = {}
            for d in s["metric_dicts"]:
                if d["name"] is not None:
                    literals.setdefault(d["name"], d["line"])
            contract = MetricContract(
                path=s["path"], canonical=tuple(decl["values"]),
                canonical_line=decl["line"], literals=literals,
                patterns=[(re.compile(d["pattern"]), d["line"])
                          for d in s["metric_dicts"]
                          if d["pattern"] is not None])
            unemitted, rogue = contract_problems(contract)
            for n in unemitted:
                yield self.project_violation(
                    project, s["path"], contract.canonical_line,
                    f"canonical metric '{n}' has no emission "
                    f"site in {s['path']} (renamed phase metric "
                    f"without updating {CANONICAL_TUPLE}?)")
            for n, line in sorted(rogue.items()):
                yield self.project_violation(
                    project, s["path"], line,
                    f"metric '{n}' is emitted but missing from "
                    f"{CANONICAL_TUPLE}; register it")


RULES = (TelemetryContract(),)
