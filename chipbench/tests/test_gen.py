"""The traffic generator: every seed gets the same work in another order."""
import json
from collections import Counter

import numpy as np

from chipbench import gen
from chipbench.harness import HERE

BACKLOG = json.loads((HERE / "traffic" / "serve.backlog.json").read_text())
BIG = 2**31 + 977  # the driver's seeds exceed 32 signed bits


def _shape(reqs):
    return Counter((len(r.prompt), r.max_new_tokens) for r in reqs)


def test_backlog_seeds_share_their_lengths():
    a = gen.backlog_call(BACKLOG, 50277, BIG, 0)
    b = gen.backlog_call(BACKLOG, 50277, BIG, 1)
    c = gen.backlog_call(BACKLOG, 50277, 5, 0)
    assert len(a) == BACKLOG["requests_per_call"]
    assert _shape(a) == _shape(b) == _shape(c)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in c]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert len({r.index for r in a + b}) == 2 * len(a)
    assert {len(r.prompt) for r in a} <= set(BACKLOG["prompt_len"]["buckets"])
    out = BACKLOG["output_len"]
    assert all(out["min"] <= r.max_new_tokens <= out["max"] for r in a)
    assert max(len(r.prompt) + r.max_new_tokens for r in a) <= BACKLOG["max_len"]
    again = gen.backlog_call(BACKLOG, 50277, BIG, 0)
    assert [r.prompt for r in again] == [r.prompt for r in a]


def test_train_batches_differ_by_step_and_row():
    mix = {"batch": 4, "seq": 16}
    b0, b1 = gen.train_batch(mix, 1000, BIG, 0), gen.train_batch(mix, 1000, BIG, 1)
    assert b0["tokens"].shape == (4, 16) and b0["tokens"].dtype == np.int32
    np.testing.assert_array_equal(b0["tokens"][:, 1:], b0["labels"][:, :-1])
    assert not np.array_equal(b0["tokens"], b1["tokens"])
    assert len({r.tobytes() for r in b0["tokens"]}) == 4


def test_zipf_rows_are_documents_of_their_own():
    """Each row follows one Zipf law under its own order of the ids: its
    commonest id takes about the law's first share, and rows differ in
    which id that is."""
    V, a = 1000, 1.1
    mix = {"batch": 6, "seq": 4000, "zipf": a}
    b = gen.train_batch(mix, V, BIG, 2)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    assert b["tokens"].min() >= 0 and b["tokens"].max() < V
    first = 1.0 / (np.arange(1, V + 1) ** -a).sum()
    tops = []
    for row in b["tokens"]:
        counts = np.bincount(row, minlength=V)
        tops.append(int(counts.argmax()))
        assert abs(counts.max() / len(row) - first) < 0.03
    assert len(set(tops)) == len(tops)
    again = gen.train_batch(mix, V, BIG, 2)
    np.testing.assert_array_equal(again["tokens"], b["tokens"])
