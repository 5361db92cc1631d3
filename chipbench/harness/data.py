"""The benchmark's own copy of the FIMI surrogate generators, and the
seeded transformations that turn one base database into a run's inputs.

The base databases are seeded surrogates of the paper's Table-3 datasets
(the real files are not in the repository; each configuration's file
gives the published figures, the generator's assumptions and what it
realizes). The sparse generator is ``repro.data.synth``'s, vectorised (the
same random stream and per-row dedup); the dense one spreads all
``n_items`` ids over the attribute slots. They are fixed, as the real
``.dat`` files are: a run's ``--seed`` changes how the data is presented
(row order, item labels), never the size of the work.
"""
from __future__ import annotations

import zlib

import numpy as np

PAD = -1


def dataset_rng(cfg: dict) -> np.random.Generator:
    """The fixed random stream of a configuration's base database, keyed by
    its ``name`` and data seed: configurations that deploy one dataset on
    different meshes keep its name, and mine the same rows."""
    seed = int(cfg["assumed"]["data_seed"])
    return np.random.default_rng(seed + zlib.crc32(cfg["name"].encode()) % 2**16)


def slot_values(cfg: dict) -> np.ndarray:
    """Values per attribute slot: ``n_items`` spread over ``avg_len`` slots,
    the first ``n_items % avg_len`` slots taking one value more, so every
    item id occurs."""
    n_slots, n_items = int(cfg["avg_len"]), int(cfg["n_items"])
    return n_items // n_slots + (np.arange(n_slots) < n_items % n_slots)


def dense_templates(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """(n_templates, n_slots) per-slot value indices: the first draw of the
    dense generator."""
    vals = slot_values(cfg)
    return rng.integers(0, vals, size=(int(cfg["assumed"]["n_templates"]), len(vals)))


def dense_rows(cfg: dict, templates: np.ndarray, rng: np.random.Generator,
               n_rows: int) -> np.ndarray:
    """Noisy copies of the templates: each slot keeps its template value or,
    with probability ``mutate``, takes a uniform value of its alphabet.
    Fixed length, no padding."""
    vals = slot_values(cfg)
    n_slots = len(vals)
    which = rng.integers(0, len(templates), size=n_rows)
    rows = templates[which]
    flip = rng.random((n_rows, n_slots)) < float(cfg["assumed"]["mutate"])
    rows = np.where(flip, rng.integers(0, vals, size=(n_rows, n_slots)), rows)
    base = np.concatenate(([0], np.cumsum(vals)[:-1]))[None, :]
    return (base + rows).astype(np.int32)


def sparse_rows(cfg: dict, rng: np.random.Generator, n_rows: int) -> np.ndarray:
    """Click-stream rows: geometric lengths clipped at ``max_len``, Zipf item
    popularity clipped to the universe, duplicates removed within a row,
    items ascending, padded with ``PAD``."""
    a = cfg["assumed"]
    n_items, max_len = int(cfg["n_items"]), int(cfg["max_len"])
    lens = np.minimum(rng.geometric(1.0 / int(cfg["avg_len"]), size=n_rows), max_len)
    total = int(lens.sum())
    items = rng.zipf(float(a["zipf_a"]), size=total * 2)
    items = items[items <= n_items][:total].astype(np.int32) - 1
    while len(items) < total:
        extra = rng.zipf(float(a["zipf_a"]), size=total)
        extra = extra[extra <= n_items]
        items = np.concatenate([items, extra.astype(np.int32) - 1])[:total]
    big = np.int32(n_items)
    inside = np.arange(max_len)[None, :] < lens[:, None]
    mat = np.full((n_rows, max_len), big, np.int32)
    mat[inside] = items  # row r holds the next lens[r] draws, in order
    mat.sort(axis=1)
    dup = np.zeros_like(inside)
    dup[:, 1:] = mat[:, 1:] == mat[:, :-1]
    mat[dup] = big
    mat.sort(axis=1)
    return np.where(mat == big, PAD, mat).astype(np.int32)


def base_database(cfg: dict) -> np.ndarray:
    """The configuration's fixed base database, (rows, max_len) int32."""
    rng = dataset_rng(cfg)
    if cfg["kind"] == "dense":
        return dense_rows(cfg, dense_templates(cfg, rng), rng, int(cfg["n_rows"]))
    return sparse_rows(cfg, rng, int(cfg["n_rows"]))


def relabelled(rows: np.ndarray, n_items: int, rng: np.random.Generator):
    """The same database with its rows shuffled and its item ids permuted:
    new content (a new fingerprint for every cache keyed on content), the
    same mining work. -> (rows', item_map) with ``item_map[old] = new``."""
    item_map = rng.permutation(n_items).astype(np.int32)
    return relabel(rows[rng.permutation(len(rows))], item_map), item_map


def relabel(rows: np.ndarray, item_map: np.ndarray) -> np.ndarray:
    """The rows with every item id ``i`` replaced by ``item_map[i]``."""
    return np.where(rows >= 0, item_map[np.maximum(rows, 0)], PAD).astype(np.int32)


def relabel_answer(answer: dict, item_map: np.ndarray) -> dict:
    """The exact answer of a relabelled database, from the base answer."""
    return {tuple(sorted(int(item_map[i]) for i in s)): v for s, v in answer.items()}


def stream_batch(cfg: dict, key: tuple[int, ...], n_rows: int) -> np.ndarray:
    """One stream batch: rows of the configuration's shape (a dense
    configuration's own templates and mutation rate, a sparse one's length
    and popularity laws) drawn from ``key``, so any batch can be made
    again from its key alone."""
    rng = np.random.default_rng(list(key))
    if cfg["kind"] == "dense":
        return dense_rows(cfg, dense_templates(cfg, dataset_rng(cfg)), rng, n_rows)
    return sparse_rows(cfg, rng, n_rows)
