#!/usr/bin/env python
"""Cross-reference registered metric names against docs/TELEMETRY.md.

The metrics catalog only stays useful while it is COMPLETE and not
stale; with ~10 new metrics per observability PR that property rots in
one merge unless it is enforced. This script extracts:

  * every metric name registered with a string literal in the package
    (``.counter("name"``, ``.gauge(...)``, ``.histogram(...)`` — names
    built from f-strings are not literal and are skipped), and
  * every metric name documented as a catalog table row in
    docs/TELEMETRY.md (``| `name...` | ...``; a ``{label=...}`` suffix
    is part of the row, not the name),

and fails on either direction of drift: registered-but-undocumented
(write the row) or documented-but-unregistered (stale row — delete it
or fix the rename). tests/unit/telemetry/test_telemetry_docs.py runs
this as a tier-1 test; it is also runnable standalone::

    python scripts/check_telemetry_docs.py
"""

import pathlib
import re
import sys
from typing import Set, Tuple

REPO = pathlib.Path(__file__).resolve().parent.parent

_REGISTER_RE = re.compile(
    r"\.(?:counter|gauge|histogram)\(\s*[\"']"
    r"([a-zA-Z_][a-zA-Z0-9_]*)[\"']")
_DOC_ROW_RE = re.compile(
    r"^\|\s*`([a-zA-Z_][a-zA-Z0-9_]*)(?:\{[^`]*\})?`\s*\|", re.M)


def registered_metrics(root: pathlib.Path = REPO) -> Set[str]:
    """Metric names registered with literal strings anywhere in the
    package."""
    names: Set[str] = set()
    for p in (root / "deepspeed_tpu").rglob("*.py"):
        names.update(_REGISTER_RE.findall(p.read_text()))
    return names


def documented_metrics(root: pathlib.Path = REPO) -> Set[str]:
    doc = root / "docs" / "TELEMETRY.md"
    return set(_DOC_ROW_RE.findall(doc.read_text()))


def check(root: pathlib.Path = REPO) -> Tuple[Set[str], Set[str]]:
    """Returns (undocumented, stale) — both empty when the catalog is
    honest."""
    code = registered_metrics(root)
    docs = documented_metrics(root)
    return code - docs, docs - code


def main() -> int:
    undocumented, stale = check()
    rc = 0
    for name in sorted(undocumented):
        print(f"check_telemetry_docs: UNDOCUMENTED metric {name!r} — "
              f"add a catalog row to docs/TELEMETRY.md", file=sys.stderr)
        rc = 1
    for name in sorted(stale):
        print(f"check_telemetry_docs: STALE catalog row {name!r} — no "
              f"such metric is registered in the package", file=sys.stderr)
        rc = 1
    if rc == 0:
        n = len(registered_metrics())
        print(f"check_telemetry_docs: OK ({n} metrics, catalog in sync)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
