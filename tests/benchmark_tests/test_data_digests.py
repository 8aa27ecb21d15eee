"""Same seed, same data, across PR 45's generator seam: the bytes of
``data.corpus`` and ``data.request_queries`` at every configuration's
rehearsal sizes, two seeds each, as the PARENT of PR 45 (commit
``b6c1e9c``) produced them. ``data_digests.json`` was written by that
commit's ``benchmark/data.py``; a configuration added later is not in it
and is not held (its own PR may bring a file of its own)."""

import hashlib
import json
import os

import pytest

from benchmark import data, spec

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data_digests.json")) as f:
    PINNED = json.load(f)


def toy(name):
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == name)
    doc = spec._load(os.path.join(spec.ROOT, entry["file"]))
    return spec.merge(doc, doc["rehearse"])


def digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("key", sorted(PINNED["digests"]))
def test_corpus_bytes_are_the_parents(key):
    name, seed = key.rsplit(":", 1)
    labels, rows = data.corpus(toy(name), int(seed))
    h = hashlib.sha256()
    h.update(str((labels.dtype, labels.shape, rows.dtype,
                  rows.shape)).encode())
    h.update(labels.tobytes())
    h.update(rows.tobytes())
    assert h.hexdigest() == PINNED["digests"][key]["corpus"]


@pytest.mark.parametrize("key", sorted(PINNED["digests"]))
def test_request_query_bytes_are_the_parents(key):
    name, seed = key.rsplit(":", 1)
    cfg = toy(name)
    got = digest(data.request_queries(cfg, int(seed), index, nq)
                 for index, nq in PINNED["requests"])
    assert got == PINNED["digests"][key]["requests"]


def test_every_configuration_of_the_parent_is_pinned_on_two_seeds():
    assert len(PINNED["seeds"]) == 2 and max(PINNED["seeds"]) > 2 ** 31
    names = {k.rsplit(":", 1)[0] for k in PINNED["digests"]}
    assert names == {"bigann-4m", "bigann-mesh4", "gist-1m",
                     "bigann-gt1000", "bigann-10m", "msturing-10m"}
    assert names <= {c["name"] for c in spec.benchmark()["configs"]}
    assert len(PINNED["digests"]) == 2 * len(names)
