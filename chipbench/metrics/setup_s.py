"""setup_s: process start to the first timed instant, compilation (or
the persistent cache's loads), weights and warm-up included."""


def read(record: dict):
    return record["setup_s"]
