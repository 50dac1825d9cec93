"""train_mfu: model FLOPs per token (``chipbench.work``, recomputation not
counted) times the window's tokens per second, over the chip's peak bf16
FLOP/s."""
from chipbench import work


def read(record: dict):
    if record["kind"] != "train":
        return None
    rate = record["train_tokens"] / record["window_s"]
    flops = work.train_flops_per_token(record["sizes"], record["seq"])
    return 100.0 * flops * rate / record["peaks"].bf16_flops
