"""Shared helpers for the repo-root ``bench_*`` scripts.

Deliberately free of jax/numpy imports: the bench scripts set platform
env vars BEFORE importing jax, so anything they import first must not
touch a backend.
"""

from __future__ import annotations

import json


def archive_rows(rows, path, legacy_keys=()):
    """Merge ``rows`` into the JSON archive at ``path``, keyed by each
    row's ``metric`` name: a rerun replaces its own metrics' rows and
    leaves every other archived row untouched.  ``legacy_keys`` are
    pre-archive-era whole-file keys to drop — they were overwritten per
    run (never merged), so anything left is one stale snapshot that
    would sit beside the authoritative rows forever."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    for legacy in legacy_keys:
        doc.pop(legacy, None)
    new_metrics = {r["metric"] for r in rows}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("metric") not in new_metrics] + rows
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"archived {len(rows)} rows -> {path}", flush=True)


def emit_row(row, platform):
    """Print one result row as a JSON line, stamped with the platform it
    was measured on (``jax.devices()[0].platform``, passed in to keep
    this module jax-free) — a host timing must never read as a chip's.
    Stamps in place so the archived copy carries it too."""
    row.setdefault("platform", platform)
    print(json.dumps(row), flush=True)
