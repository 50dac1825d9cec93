"""serve_tokens_per_s: generated tokens handed to the host inside the
window of a saturated (closed backlog) cell, over the window: whole decode
steps, from the first one's start to the start of the last one that began
inside the window."""


def read(record: dict):
    if record["kind"] != "serve_closed":
        return None
    return record["serve_tokens"] / record["window_s"]
