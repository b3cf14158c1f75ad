"""One benchmark, one home for numbers: the chip benchmark is `benchmarks/`
(BENCHMARK.json), measured numbers live in PERF.md, and no document quotes
the retired `bench.py`, its `BENCH_*` flags or its record files."""
import glob
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RETIRED = ("bench.py", "BENCH_", "MULTICHIP_r")
DOCS = ["README.md", "PARITY.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_no_retired_benchmark(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    hits = [f"{doc}:{n}: {line.strip()}"
            for n, line in enumerate(text.splitlines(), 1)
            for name in RETIRED if name in line]
    assert not hits, "\n".join(hits)


def test_root_holds_one_benchmark():
    stale = [p for pat in ("bench.py", "BENCH_r*.json", "MULTICHIP_r*.json")
             for p in glob.glob(os.path.join(ROOT, pat))]
    assert not stale, stale
    assert os.path.exists(os.path.join(ROOT, "BENCHMARK.json"))
