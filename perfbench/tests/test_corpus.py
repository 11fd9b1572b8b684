"""The folder generator: the layout is the cell's, the bytes the seed's."""

import json
import os

from perfbench import corpus, spec

PART = {"name": "p", "files": 300, "dup_rate": 0.1, "max_file_bytes": 196608,
        "layout_seed": 5, "size_classes": [[0.5, 256, 102400],
                                           [0.4, 102401, 1048576],
                                           [0.1, 1048576, 8388608]]}


def test_layout_is_fixed_and_bytes_follow_the_seed():
    d1, f1 = corpus.plan_location([PART])
    d2, f2 = corpus.plan_location([PART])
    assert (d1, f1) == (d2, f2)
    assert max(f.size for f in f1) <= 196608
    a = corpus.content(1, f1[0])
    assert a == corpus.content(1, f1[0]) and a != corpus.content(2, f1[0])
    assert len(a) == f1[0].size


def test_planted_duplicates_share_bytes():
    _d, files = corpus.plan_location([PART])
    groups = corpus.planted_groups(files)
    assert groups
    by_rel = {f.rel: f for f in files}
    for g in groups:
        assert len({corpus.content(9, by_rel[r]) for r in g}) == 1


def test_write_location(tmp_path):
    part = {**PART, "files": 40, "max_file_bytes": 4096}
    files = corpus.write_location(str(tmp_path), [part], seed=2**33 + 1,
                                  processes=2)
    for f in files:
        with open(str(tmp_path) + f.rel, "rb") as fh:
            assert fh.read() == corpus.content(2**33 + 1, f)


def test_reduced_names_keys_the_harness_reads():
    """Every cut a configuration lists is a key of its location's parts,
    the keys corpus.py reads, and BENCHMARK.json lists the same cuts."""
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        with open(os.path.join(spec.ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert set(entry["reduced"]) == set(cfg["reduced"])
        for key in entry["reduced"]:
            head, leaf = key.rsplit(".", 1)
            assert head == "location.parts"
            assert all(leaf in part for part in cfg["location"]["parts"])


def test_extension_names_the_files():
    _d, files = corpus.plan_location([{**PART, "extension": "jpg"}])
    assert all(f.rel.endswith(".jpg") for f in files)
