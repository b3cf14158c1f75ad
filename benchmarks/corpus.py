"""The benchmark's own inputs, all made from a seed.

* `write_synth_jsonl` — a copy of the program's bulk synthetic-corpus writer
  (`dnn_page_vectors_tpu/data/synth.py`, with the syllable table of
  `data/toy.py`): jsonl records {"query", "page"}, per-topic vocabularies
  over syllable words plus two page-unique key words shared with the gold
  query. Kept here so that the traffic cannot be moved by a later PR.
* `read_records` — a plain reader of that file for the reference.
* `IdCorpus` / `HashTokenizer` — the mt5 cell's feed: a "text" is the page
  id, and its token ids are a splitmix64 hash of (seed, side, page id,
  position) over the true vocabulary, never 0 (pad). A pure function of its
  arguments, so tokenizer worker threads, epochs and the reference all see
  the same ids.
"""
from __future__ import annotations

import json
import os

import numpy as np

_SYLLABLES = [c + v for c in "bdfgklmnprstvz" for v in "aeiou"]


def _make_word(rng: np.random.Generator, n_syll: int) -> str:
    idx = rng.integers(0, len(_SYLLABLES), size=n_syll)
    return "".join(_SYLLABLES[i] for i in idx)


def write_synth_jsonl(path: str, num_pages: int, seed: int = 0,
                      num_topics: int = 64, page_len: int = 48,
                      query_len: int = 8, block: int = 16_384) -> str:
    """Write pages [0, num_pages) as jsonl records; returns `path`.
    Deterministic in every argument; each block re-seeds from its first
    page id."""
    master = np.random.default_rng(seed)
    common = np.array(sorted({_make_word(master, 2) for _ in range(300)}),
                      dtype=object)
    topics = [np.array(sorted({_make_word(master, 3) for _ in range(48)}),
                       dtype=object) for _ in range(num_topics)]
    syll = np.array(_SYLLABLES, dtype=object)
    tmp = path + ".tmp"
    with open(tmp, "w", buffering=1 << 22) as f:
        for lo in range(0, num_pages, block):
            hi = min(lo + block, num_pages)
            b = hi - lo
            rng = np.random.default_rng((seed * 1_000_003 + lo) & 0x7FFFFFFF)
            ids = np.arange(lo, hi)
            topic_of = ids % num_topics
            body = np.empty((b, page_len), dtype=object)
            use_topic = rng.random((b, page_len)) < 0.75
            ci = rng.integers(0, len(common), size=(b, page_len))
            ti = rng.integers(0, 1 << 30, size=(b, page_len))
            body[~use_topic] = common[ci[~use_topic]]
            for t in range(num_topics):
                rows = np.nonzero(topic_of == t)[0]
                if rows.size == 0:
                    continue
                m = use_topic[rows]
                sub = body[rows]
                sub[m] = topics[t][ti[rows][m] % len(topics[t])]
                body[rows] = sub
            ks = rng.integers(0, len(syll), size=(b, 2, 4))
            key0 = syll[ks[:, 0, 0]] + syll[ks[:, 0, 1]] + \
                syll[ks[:, 0, 2]] + syll[ks[:, 0, 3]] + \
                np.array([str(i % 10) for i in ids], dtype=object)
            key1 = syll[ks[:, 1, 0]] + syll[ks[:, 1, 1]] + \
                syll[ks[:, 1, 2]] + syll[ks[:, 1, 3]]
            keys = np.stack([key0, key1], axis=1)
            for j in range(6):
                body[np.arange(b), (7 * (j + 1) + ids) % page_len] = \
                    keys[:, j % 2]
            qbody = np.empty((b, query_len), dtype=object)
            qti = rng.integers(0, 1 << 30, size=(b, query_len))
            for t in range(num_topics):
                rows = np.nonzero(topic_of == t)[0]
                if rows.size:
                    qbody[rows] = topics[t][qti[rows] % len(topics[t])]
            qpos = rng.integers(0, query_len - 1, size=b)
            qbody[np.arange(b), qpos] = keys[:, 0]
            qbody[np.arange(b), qpos + 1] = keys[:, 1]
            for r in range(b):
                f.write(json.dumps(
                    {"query": " ".join(qbody[r]), "page": " ".join(body[r])},
                    separators=(",", ":")))
                f.write("\n")
    os.replace(tmp, path)
    return path


def read_records(path: str, ids) -> dict:
    """{id: record} for the wanted line numbers, by one plain pass."""
    want = {int(i) for i in ids}
    out = {}
    with open(path, "rb") as f:
        for n, line in enumerate(f):
            if n in want:
                out[n] = json.loads(line)
                if len(out) == len(want):
                    break
    return out


# -- the synthetic-id feed --------------------------------------------------

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)) & _M64
        x = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _M64
        x = ((x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _M64
        return x ^ (x >> np.uint64(31))


def hash_ids(seed: int, side: int, page_ids, length: int,
             vocab_size: int) -> np.ndarray:
    """[n, length] int32 token ids in [1, vocab_size): uniform over the true
    vocabulary, never pad."""
    pid = np.asarray(page_ids, np.uint64)[:, None]
    pos = np.arange(length, dtype=np.uint64)[None, :]
    with np.errstate(over="ignore"):
        base = _splitmix64(np.uint64(int(seed) & 0xFFFFFFFF)
                           * np.uint64(2) + np.uint64(side))
        x = _splitmix64(base ^ (pid * np.uint64(1_000_003) + pos))
    return (1 + (x % np.uint64(vocab_size - 1))).astype(np.int32)


class IdCorpus:
    """A corpus whose texts are the page ids themselves."""

    def __init__(self, num_pages: int):
        self.num_pages = int(num_pages)

    def page_texts(self, ids) -> list:
        return [str(int(i)) for i in ids]

    query_texts = page_texts

    def page_text(self, i: int) -> str:
        return str(int(i))

    query_text = page_text

    def fingerprint(self) -> str:
        return f"ids:{self.num_pages}"


class HashTokenizer:
    """`encode_batch(texts)` of id-strings -> hashed token ids."""

    def __init__(self, vocab_size: int, max_tokens: int, seed: int,
                 side: int):
        self.vocab_size = int(vocab_size)
        self.max_tokens = int(max_tokens)
        self.seed, self.side = int(seed), int(side)

    def encode_batch(self, texts) -> np.ndarray:
        return hash_ids(self.seed, self.side, [int(t) for t in texts],
                        self.max_tokens, self.vocab_size)
