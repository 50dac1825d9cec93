"""device_idle_share.backlog: 1 - (union of device-op intervals) / window,
over the traced last seconds of a backlog window."""
from chipbench.metrics._common import idle_share_pct


def read(record: dict):
    return idle_share_pct(record, "serve_closed")
