"""The inherited-seed-failure ledger — closed, and it stays closed.

The seed tree carried 15 tier-1 failures into this container. PR 5
fixed the cheap ones and skip-marked nine with a
``seed-failure[category]`` reason, adjudicated against another jaxlib;
on jax 0.9.0 (the only build since PR 21) all nine pass and PR 28 took
the marks off. A new failure is fixed, not skipped under this name.
"""

from __future__ import annotations

import pathlib

TESTS_DIR = pathlib.Path(__file__).resolve().parent


def test_no_seed_failure_skip_exists():
    marked = [path.name for path in sorted(TESTS_DIR.glob("test_*.py"))
              if path.name != "test_seed_triage.py"
              and "seed-failure[" in path.read_text()]
    assert not marked, (
        f"seed-failure skip(s) in {marked}: the grandfather ledger is "
        f"closed; fix the test")
