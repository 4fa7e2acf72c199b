#!/usr/bin/env python
"""Metric-name drift check: every metric the code registers must be
documented in docs/observability.md.

The observability doc's "what is instrumented" tables are the contract
operators build dashboards against; a metric added in code but not in
the doc is invisible drift. This script:

1. scans ``paddle_tpu/`` for string-literal metric
   names passed to the registration/observation calls
   (``inc/observe/set_gauge/counter/gauge/histogram/timed`` and the
   latency helper) — f-string templated names are skipped (they are
   families; the doc covers them with ``<placeholder>`` patterns);
2. parses the backtick-quoted names out of ``docs/observability.md``,
   expanding two shorthands the tables use:
   - pipe alternation in a segment: ``a.b.hit|miss`` -> a.b.hit, a.b.miss
   - ``<placeholder>`` segments match any single segment:
     ``op.<name>.calls`` matches ``op.matmul.calls``;
3. fails (exit 1) listing any registered name no doc pattern covers.

Run standalone (``python scripts/check_metrics_docs.py``) or from
tier-1 via tests/test_trace.py.
"""
from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "observability.md")

# registration/observation entry points whose FIRST string argument is
# a metric name; the optional leading underscore catches the lazy-import
# aliases modules bind (``from . import inc as _inc``)
_CALL_RE = re.compile(
    r"""\b_?(?:inc|observe|set_gauge|counter|gauge|histogram|timed|
             observe_latency)\s*\(\s*
        (f?)["']([a-zA-Z0-9_.{}<>|-]+)["']""",
    re.VERBOSE)

# a plausible metric name: dotted lowercase segments (filters out call
# sites whose first string arg is prose, a format string, or a kind
# tag like get_or_create("counter", ...))
_NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")

# doc tokens worth treating as metric patterns
_DOC_TOKEN_RE = re.compile(r"`([a-zA-Z0-9_.<>|{}-]+)`")


def registered_names(root: str = None) -> set:
    """Literal metric names registered under paddle_tpu/."""
    root = root or REPO
    names = set()
    files = []
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        files.extend(os.path.join(dirpath, f) for f in filenames
                     if f.endswith(".py"))
    for path in files:
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        for m in _CALL_RE.finditer(src):
            is_fstring, name = m.group(1), m.group(2)
            if is_fstring or "{" in name:
                continue            # templated family: doc uses <...>
            if _NAME_RE.match(name):
                names.add(name)
    return names


def doc_patterns(doc_path: str = DOC) -> list:
    """Compiled regex patterns for every metric-shaped doc token."""
    with open(doc_path, encoding="utf-8") as f:
        text = f.read()
    patterns = []
    for token in _DOC_TOKEN_RE.findall(text):
        if "." not in token:
            continue
        for expanded in _expand_pipes(token):
            patterns.append(_to_regex(expanded))
    return patterns


def _expand_pipes(token: str) -> list:
    """``a.b.hit|miss`` -> [a.b.hit, a.b.miss] (per segment, cross
    product across segments)."""
    outs = [""]
    for i, seg in enumerate(token.split(".")):
        alts = seg.split("|")
        outs = [(o + "." if i else "") + a for o in outs for a in alts]
    return outs


def _to_regex(pattern: str):
    """``op.<name>.calls`` -> regex with one-segment wildcards."""
    parts = []
    for seg in pattern.split("."):
        if seg.startswith("<") and seg.endswith(">"):
            parts.append(r"[a-z0-9_]+")
        else:
            parts.append(re.escape(seg))
    return re.compile(r"^" + r"\.".join(parts) + r"$")


def undocumented(root: str = None, doc_path: str = DOC) -> list:
    pats = doc_patterns(doc_path)
    missing = []
    for name in sorted(registered_names(root)):
        if not any(p.match(name) for p in pats):
            missing.append(name)
    return missing


def main() -> int:
    names = registered_names()
    if not names:
        print("check_metrics_docs: found NO registered metric names — "
              "the scanner regex is broken", file=sys.stderr)
        return 2
    missing = undocumented()
    if missing:
        print("metrics registered in code but missing from "
              "docs/observability.md tables:", file=sys.stderr)
        for name in missing:
            print(f"  - {name}", file=sys.stderr)
        print(f"({len(missing)} undocumented of {len(names)} scanned; "
              "add them to the tables in docs/observability.md)",
              file=sys.stderr)
        return 1
    print(f"check_metrics_docs: OK ({len(names)} literal metric names, "
          "all documented)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
