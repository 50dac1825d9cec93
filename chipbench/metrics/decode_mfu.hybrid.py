"""decode_mfu.hybrid: the hybrid configurations' decode steps' share of
the chip's roofline, whole steps: the least time each step of the window
could take (the larger of its FLOPs over peak FLOP/s and its required
bytes over peak bandwidth, from ``chipbench.work_hybrid``, which leaves
out the key/value cache reads and so gives a lower bound), summed, over
their synced time."""
from chipbench import work, work_hybrid


def read(record: dict):
    if record["kind"] != "serve_closed" or not record.get("decode") or record["sizes"]["family"] != "hybrid":
        return None
    pk = record["peaks"]
    least = sum(
        work.least_time(*work_hybrid.decode_step_work(record["sizes"], rows), pk.bf16_flops, pk.hbm_bw)
        for rows, _t, _s in record["decode"]
    )
    return 100.0 * least / sum(s for _r, _t, s in record["decode"])
