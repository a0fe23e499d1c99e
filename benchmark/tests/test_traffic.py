"""The request generator and the corpus writer: one seed, one output."""

import filecmp
import itertools
import json
import os

from benchmark import harness
from benchmark.traffic import corpus, requests


def _traffic(name):
    with open(os.path.join(harness.BENCH_DIR, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _take(traffic, seed, n=70):
    return list(itertools.islice(requests.make_requests(traffic, 50304, seed), n))


def test_requests_same_seed_same_output_other_seed_other_tokens():
    t = _traffic("chat_c32")
    a, b, c = _take(t, 2**31 + 5), _take(t, 2**31 + 5), _take(t, 6)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    # the sizes, and their order, do not change with the seed; they come round again
    sizes = lambda rs: [(len(r["prompt"]), r["max_new_tokens"]) for r in rs]  # noqa: E731
    assert sizes(a) == sizes(c)
    assert sizes(a)[:32] == sizes(a)[32:64] == [tuple(s) for s in requests.size_table(t).tolist()]


def test_request_sizes_follow_the_mix():
    t = _traffic("chat_c32")
    table = requests.size_table(t)
    assert len(table) == t["n_sizes"] == t["clients"]
    p, n = table[:, 0], table[:, 1]
    assert p.min() >= 32 and p.max() <= 1024 and n.min() >= 16 and n.max() <= 256
    assert 230 <= sorted(p)[len(p) // 2] <= 280 and 85 <= sorted(n)[len(n) // 2] <= 110
    assert all(r["temperature"] == 0.0 for r in _take(t, 1, 5))  # greedy: the check needs it


def test_corpus_same_seed_same_files_other_seed_other_files(tmp_path):
    t = dict(_traffic("relora_r128"), corpus_tokens=40000)
    a = corpus.write_corpus(t, 50304, 2**31 + 7, str(tmp_path / "a"))
    b = corpus.write_corpus(t, 50304, 2**31 + 7, str(tmp_path / "b"))
    c = corpus.write_corpus(t, 50304, 8, str(tmp_path / "c"))
    files = sorted(f for f in os.listdir(tmp_path / "a") if f.startswith("corpus"))
    assert files
    for f in files:
        assert filecmp.cmp(tmp_path / "a" / f, tmp_path / "b" / f, shallow=False)
    assert not all(filecmp.cmp(tmp_path / "a" / f, tmp_path / "c" / f, shallow=False) for f in files)
    assert open(a).read().replace(str(tmp_path / "a"), "") == open(b).read().replace(str(tmp_path / "b"), "")
    assert "seq_length: 2048" in open(c).read()
    docs = list(corpus.documents(t, 50304, 3))
    assert all(256 <= len(d) < 3000 for d in docs) and sum(map(len, docs)) >= 40000
