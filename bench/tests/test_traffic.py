"""Every seed gets the same sizes in another order; a seed past 2**31 is
fine; the same seed gives the same inputs."""
import numpy as np

from bench.traffic import Requests, TrainTokens, load_traffic, request_sizes


def test_same_sizes_other_order():
    t = load_traffic("chat_closed32")
    sizes = sorted(request_sizes(t))
    a = Requests(t, 2**31 + 12345, 151936)
    b = Requests(t, 3, 151936)
    la = [a.next() for _ in range(t["pool"])]
    lb = [b.next() for _ in range(t["pool"])]
    assert sorted((len(p), o) for p, o in la) == sizes
    assert sorted((len(p), o) for p, o in lb) == sizes
    assert [len(p) for p, _ in la] != [len(p) for p, _ in lb]
    assert all(p.min() >= 1 for p, _ in la)
    p0, _ = Requests(t, 3, 151936).next()
    assert np.array_equal(p0, lb[0][0])


def test_lengths_in_bounds():
    t = load_traffic("chat_closed32")
    s = np.array(request_sizes(t))
    assert s[:, 0].min() >= 64 and s[:, 0].max() <= 2048
    assert s[:, 1].min() >= 16 and s[:, 1].max() <= 256
    assert 900 < np.median(s[:, 0]) < 1150 and 110 < np.median(s[:, 1]) < 145


def test_train_tokens_deterministic():
    t = load_traffic("train_4x1024")
    a = TrainTokens(t, 2**33, 151936).global_batch(5)
    b = TrainTokens(t, 2**33, 151936).global_batch(5)
    c = TrainTokens(t, 2**33, 151936).global_batch(6)
    assert a["tokens"].shape == (4, 1024)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert len({r.tobytes() for r in a["tokens"]}) == 4
