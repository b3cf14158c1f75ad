"""The bert_mini configuration's vocabulary and its plain encoder.

The published `vocab.txt` is not on this machine, so the benchmark builds a
vocabulary of exactly the published size once per checkout: the characters
of a fixed-seed synthetic sample, then its most frequent substrings (count
descending, then the string), until 30,520 pieces stand beside the two
reserved ids (0 pad, 1 unknown). It depends on the configuration alone, not
on `--seed`. The program's tokenizer is handed this table as it would be
handed a published one; the reference encodes with `encode` below.
"""
from __future__ import annotations

import collections
import json
import os

import numpy as np

from .corpus import write_synth_jsonl

PAD_ID, UNK_ID, RESERVED = 0, 1, 2
MAX_PIECE = 9


def build_vocab(sample_path: str, vocab_size: int) -> dict:
    words: collections.Counter = collections.Counter()
    with open(sample_path) as f:
        for line in f:
            rec = json.loads(line)
            words.update(rec["page"].split())
            words.update(rec["query"].split())
    alphabet = sorted({ch for w in words for ch in w})
    subs: collections.Counter = collections.Counter()
    for w, c in words.items():
        n = len(w)
        for i in range(n):
            for j in range(i + 2, min(n, i + MAX_PIECE) + 1):
                subs[w[i:j]] += c
    want = vocab_size - RESERVED - len(alphabet)
    ranked = sorted(subs.items(), key=lambda kv: (-kv[1], kv[0]))[:want]
    if len(ranked) < want:
        raise ValueError(f"sample gives only {len(ranked)} substrings, "
                         f"{want} needed")
    pieces = alphabet + [s for s, _ in ranked]
    return {p: i + RESERVED for i, p in enumerate(pieces)}


def load_or_build(cache_dir: str, config: dict) -> dict:
    """The vocabulary of `config`, from `<cache_dir>/vocab_<name>.json`, or
    built there first (a temporary sample file is written and removed)."""
    size = config["published"]["vocab_size"]
    path = os.path.join(cache_dir, f"vocab_{config['name']}.json")
    if os.path.exists(path):
        with open(path) as f:
            vocab = json.load(f)
        if len(vocab) + RESERVED == size:
            return vocab
    s = config["assumed"]["vocab_sample"]
    os.makedirs(cache_dir, exist_ok=True)
    sample = os.path.join(cache_dir, f"vocab_sample_{config['name']}.jsonl")
    write_synth_jsonl(sample, s["pages"], seed=s["seed"],
                      page_len=s["page_len"], query_len=s["query_len"])
    try:
        vocab = build_vocab(sample, size)
    finally:
        os.remove(sample)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(vocab, f)
    os.replace(tmp, path)
    return vocab


def encode(vocab: dict, texts, max_tokens: int) -> np.ndarray:
    """Greedy longest-match wordpiece: split on whitespace, take the longest
    prefix of the rest of the word that is a piece (the same table inside a
    word as at its start), one unknown id for a character no piece covers;
    cut at `max_tokens`, pad with 0."""
    out = np.zeros((len(texts), max_tokens), np.int32)
    for r, text in enumerate(texts):
        pos = 0
        for word in text.split():
            i, n = 0, len(word)
            while i < n and pos < max_tokens:
                j = min(n, i + MAX_PIECE)
                while j > i and word[i:j] not in vocab:
                    j -= 1
                if j == i:
                    out[r, pos] = UNK_ID
                    j = i + 1
                else:
                    out[r, pos] = vocab[word[i:j]]
                pos += 1
                i = j
            if pos >= max_tokens:
                break
    return out
