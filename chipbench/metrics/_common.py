"""Helpers the metric readers share."""
from __future__ import annotations


def idle_share_pct(record: dict, kind: str) -> float | None:
    tr = record.get("trace")
    if record["kind"] != kind or not tr:
        return None
    return 100.0 * tr["idle_share"]
