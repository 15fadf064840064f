"""Telemetry contract — TDA102.

A counter/gauge is emitted somewhere in the library but
``telemetry/report.py`` never renders it and never waives it — the
signal exists in JSONL and nowhere a human looks. Every emitted name
must appear in report.py (a literal in a renderer), match a
``PER_WORKER_PREFIXES`` family (rendered as per-worker columns), or
be listed in ``SUMMARY_ONLY_COUNTERS`` (the explicit "generic
counters: line is enough" waiver; ``name.*`` entries waive a
family). F-string names (``f"lint.{code}"``) are checked by their
static prefix against the family entries. The reverse direction is
audited too: a waiver that matches no emission is a retired
counter's ghost.
"""

from __future__ import annotations

from tpu_distalg.analysis.project import ProjectRule

#: the report-side waiver table (lives in telemetry/report.py)
WAIVER_TUPLE = "SUMMARY_ONLY_COUNTERS"


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
                 "waiver there matches an emission")

    def check_project(self, project):
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


RULES = (TelemetryContract(),)
