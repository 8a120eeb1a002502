"""Traffic of the on-chip benchmark (benchmarks/chip/chipbench/data.py):
the same seed gives the same traffic, another seed other traffic, and
every step its own rows."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmarks", "chip"))

from chipbench import data  # noqa: E402


def tokens(seed, step=0):
    return data.TokenSource(seed, 8000, 16).batch(step, 4)


def images(seed, step=0):
    return data.ImageSource(seed, 64, 8, 3, 10).batch(step, 16)


@pytest.mark.parametrize("source", [tokens, images])
def test_same_seed_same_batch_other_seed_other_batch(source):
    a, b, c = source(2**33 + 5), source(2**33 + 5), source(2**33 + 6)
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert any(not np.array_equal(a[k], c[k]) for k in a)


@pytest.mark.parametrize("source", [tokens, images])
def test_each_step_has_its_own_rows(source):
    a, b = source(1, 0), source(1, 1)
    assert any(not np.array_equal(a[k], b[k]) for k in a)


def test_tokens_lie_in_the_vocabulary_and_labels_are_the_next_token():
    b = data.TokenSource(3, 50, 32).batch(7, 2)
    assert b["tokens"].shape == b["labels"].shape == (2, 32)
    assert b["tokens"].dtype == np.int32
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 50
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_images_visit_every_image_once_per_pass():
    src = data.ImageSource(9, 64, 8, 3, 10)
    seen = np.concatenate([src.rows(s, 16) for s in range(4)])
    assert sorted(seen) == list(range(64))
    assert not np.array_equal(src.rows(0, 16), src.rows(4, 16))
    b = src.batch(0, 16)
    assert b["images"].shape == (16, 8, 8, 3)
    assert b["images"].dtype == np.float32
    assert b["labels"].min() >= 0 and b["labels"].max() < 10
