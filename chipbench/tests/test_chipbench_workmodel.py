"""The work model behind intersect_roofline."""
import numpy as np
import pytest

from chipbench.harness import reference, workmodel

# a=0 b=1 c=2, each in 4 of the 5 rows, so the F-list is a, b, c (ties by id)
TINY = np.array([[0, 1, 2], [0, 1, 2], [0, 1, -1], [0, 2, -1], [1, 2, -1]], np.int32)


def test_nlist_lengths_by_hand():
    # PPC tree: root-a-b-c (rows 1, 2), a-b (row 3), a-c (row 4), root-b-c
    # (row 5): a has one node, b two (a.b, b), c three (a.b.c, a.c, b.c)
    assert workmodel.nlist_lengths(TINY, 3, 2) == {0: 1, 1: 2, 2: 3}


def test_least_bytes_by_hand():
    answer = reference.frequent_itemsets(TINY, 3, 2, None)
    assert answer[(0, 1, 2)] == 2
    # the one 3-itemset reads a, b and c once (6 codes of 12 bytes) and
    # writes one 4-byte support
    assert workmodel.least_bytes(TINY, 3, 2, answer) == 6 * 12 + 4


def _padded_launch_bytes(rows, n_items, count, answer):
    """The intersect kernel's launches as the program made them when this
    benchmark was written: a wave per level from 2 up, candidate slots a
    power-of-two multiple of 256, every N-list padded to one power-of-two
    width W, and per slot W int32 codes of A (pre, post, count), of Y (pre,
    post), of the carried state and of the merged output. Every frequent
    itemset of a level was one of that level's candidates, so this is at
    most what the kernel moved."""
    lengths = workmodel.nlist_lengths(rows, n_items, count)
    W = 1 << max(3, (max(lengths.values()) - 1).bit_length())
    total = 0
    for k in range(2, max(len(s) for s in answer) + 1):
        n = sum(1 for s in answer if len(s) == k)
        slots = 256 * (1 << max(0, (-(-n // 256) - 1).bit_length()))
        total += slots * W * 4 * 7
    return total


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_least_bytes_never_exceed_the_padded_launch(seed):
    rng = np.random.default_rng(seed)
    rows = np.where(rng.random((400, 12)) < 0.5, np.arange(12), -1).astype(np.int32)
    count = 40
    answer = reference.frequent_itemsets(rows, 12, count, 6)
    least = workmodel.least_bytes(rows, 12, count, answer)
    assert 0 < least <= _padded_launch_bytes(rows, 12, count, answer)
