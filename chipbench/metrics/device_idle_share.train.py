"""device_idle_share.train: 1 - (union of device-op intervals) / window,
over the traced last seconds of a training window."""
from chipbench.metrics._common import idle_share_pct


def read(record: dict):
    return idle_share_pct(record, "train")
