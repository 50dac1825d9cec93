"""train_tokens_per_s: all tokens of the train steps that completed in the
window (a step completes when its loss is on the host), over the window
from the first step's start to the last one's end."""


def read(record: dict):
    if record["kind"] != "train":
        return None
    return record["train_tokens"] / record["window_s"]
