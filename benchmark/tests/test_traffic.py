"""The same seed gives the same inputs; another seed the same set of
sizes in another order."""

import numpy as np

from benchmark import run as brun
from benchmark import traffic

CELL = brun.load_json("workloads", "mistral-7b.serve-chat.json")


def test_same_seed_same_schedule():
    a = traffic.open_loop_schedule(CELL["traffic"], 32000, 2**31 + 5, 20.0, 3.0)
    b = traffic.open_loop_schedule(CELL["traffic"], 32000, 2**31 + 5, 20.0, 3.0)
    assert a == b and len(a) == 60
    assert all(0.0 <= r["due_s"] < 20.0 for r in a)
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)


def test_other_seed_same_sizes_other_order():
    a = traffic.open_loop_schedule(CELL["traffic"], 32000, 1, 20.0, 3.0)
    b = traffic.open_loop_schedule(CELL["traffic"], 32000, 2, 20.0, 3.0)
    pa = [(len(r["prompt"]), r["max_tokens"]) for r in a]
    pb = [(len(r["prompt"]), r["max_tokens"]) for r in b]
    assert sorted(pa) == sorted(pb) and pa != pb
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert [r["prompt"][:4] for r in a] != [r["prompt"][:4] for r in b]
    p = CELL["traffic"]["prompt_tokens"]
    assert all(p["min"] <= n <= p["max"] for n, _ in pa)


def test_token_batches():
    f = traffic.TokenBatches({"batch": 4, "seq": 64}, 1000, 2**31 + 9)
    g = traffic.TokenBatches({"batch": 4, "seq": 64}, 1000, 2**31 + 9)
    b1, b2 = f.next(), f.next()
    assert np.array_equal(b1["input_ids"], g.next()["input_ids"])
    assert not np.array_equal(b1["input_ids"], b2["input_ids"])
    assert len({row.tobytes() for row in b1["input_ids"]}) == 4
    assert np.array_equal(b1["labels"][:, :-1], b1["input_ids"][:, 1:])


def test_shuffle_block_keeps_sizes_near_their_place():
    t = CELL["traffic"]
    a = traffic.open_loop_schedule(t, 32000, 1, 20.0, 3.0)
    b = traffic.open_loop_schedule(t, 32000, 2, 20.0, 3.0)
    la = [len(r["prompt"]) for r in a]
    lb = [len(r["prompt"]) for r in b]
    assert la != lb
    for i in range(0, 60, 4):
        assert sorted(la[i:i + 4]) == sorted(lb[i:i + 4])
