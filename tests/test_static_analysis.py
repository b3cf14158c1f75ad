"""graftcheck static-analysis tests (docs/ANALYSIS.md): the nine rule
families' true-positive/true-negative fixture matrix (determinism, lock
discipline, lock-order/deadlock, thread & resource lifecycle, asyncio
hygiene, jit purity + host-sync, manifest I/O, wire-protocol
conformance, doc drift), pragma-suppression semantics (line vs file
scope, missing-reason rejected), baseline add/expire behavior, the
`cli lint` JSON report + exit codes + `--changed` fast mode, and the
repo-is-clean tier-1 gate.

Everything here is AST-only: no jax, no devices, no stores — the cli
subprocess tests even strip JAX_PLATFORMS so the lint path is exercised
exactly as it runs on a jax-less box.
"""
import json
import os
import subprocess
import sys

import pytest

from dnn_page_vectors_tpu.tools.analyze import (
    BASELINE_NAME, RULES, analyze, analyze_source, write_baseline)

pytestmark = pytest.mark.lint

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings, name=None):
    return [f for f in findings if name is None or f.rule == name]


# ---------------------------------------------------------------------------
# family 1: determinism
# ---------------------------------------------------------------------------

_DET_POS = """
import random
import time
import numpy as np
import jax
from datetime import datetime

def bad():
    a = np.random.rand(3)                 # module-state sampler
    b = random.random()                   # stdlib module state
    c = np.random.default_rng()           # seedless constructor
    t = time.time()                       # wall clock
    d = datetime.now()                    # wall clock
    key = jax.random.PRNGKey(int(time.time()))   # clock-fed key
    return a, b, c, t, d, key
"""

_DET_NEG = """
import random
import time
import numpy as np
import jax

def good(seed: int):
    rng = np.random.default_rng(seed)
    r2 = random.Random(seed)
    t = time.perf_counter()               # duration, not wall clock
    key = jax.random.PRNGKey(seed)
    return rng.random(), r2.random(), t, key
"""


def test_determinism_true_positives():
    fs = _rules(analyze_source(
        _DET_POS, "dnn_page_vectors_tpu/infer/fixture.py"), "determinism")
    msgs = "\n".join(f.msg for f in fs)
    # 7 findings on 6 lines: the clock-fed PRNGKey line is both a
    # wall-clock read and a clock-seeded key
    assert len(fs) == 7, msgs
    assert "module-state RNG" in msgs
    assert "stdlib module-state RNG" in msgs
    assert "seedless RNG constructor" in msgs
    assert "wall-clock read" in msgs
    assert "seeded from the wall clock" in msgs


def test_determinism_true_negatives():
    assert not _rules(analyze_source(
        _DET_NEG, "dnn_page_vectors_tpu/infer/fixture.py"), "determinism")


def test_determinism_scope_is_byte_pinned_paths_only():
    # the same sins OUTSIDE the pinned paths (e.g. train/) are not this
    # rule's business
    assert not _rules(analyze_source(
        _DET_POS, "dnn_page_vectors_tpu/train/fixture.py"), "determinism")


# ---------------------------------------------------------------------------
# family 2: lock discipline
# ---------------------------------------------------------------------------

_LOCK_SRC = """
import threading

class Svc:
    def __init__(self):
        self._cache = {}                  # guarded-by: _cache_lock
        self._cache_lock = threading.Lock()
        self._view = None                 # swapped, never mutated
        self.sizes = []
        self._t = threading.Thread(target=self._run)

    def ok_locked(self, k, v):
        with self._cache_lock:
            self._cache[k] = v

    def ok_swap(self):
        self._cache = {}                  # whole-reference assignment

    def ok_snapshot(self):
        cache = self._cache               # snapshot read of the reference
        return cache

    def _evict(self):  # holds-lock: _cache_lock
        self._cache.clear()

    def bad_unlocked(self, k):
        return self._cache[k]             # read outside the lock

    def _run(self):
        self.sizes.append(1)              # thread mutates un-annotated attr
"""


def test_locks_rule_matrix():
    fs = _rules(analyze_source(
        _LOCK_SRC, "dnn_page_vectors_tpu/infer/serve.py"), "locks")
    lines = {f.line for f in fs}
    assert len(fs) == 2, [f.human() for f in fs]
    bad_read = next(f for f in fs if "read holds no lock" in f.msg)
    assert "self._cache" in bad_read.msg and "_cache_lock" in bad_read.msg
    thread_f = next(f for f in fs if "thread-reachable" in f.msg)
    assert "sizes" in thread_f.msg
    # the ok_* accesses, the holds-lock helper, and __init__ are all clean
    assert all("ok_" not in (f.snippet or "") for f in fs), lines


def test_locks_scope_is_the_three_threaded_files():
    assert not _rules(analyze_source(
        _LOCK_SRC, "dnn_page_vectors_tpu/infer/bulk_embed.py"), "locks")


# ---------------------------------------------------------------------------
# family: lock-order / deadlock analysis (project rule on a mini tree)
# ---------------------------------------------------------------------------

_CYCLE_SRC = """
import threading


class Svc:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            self._grab_b()

    def _grab_b(self):
        with self._b:
            pass

    def two(self):
        with self._b:
            with self._a:
                pass
"""


def _lock_project(tmp_path, src):
    pkg = os.path.join(str(tmp_path), "dnn_page_vectors_tpu", "infer")
    os.makedirs(pkg, exist_ok=True)
    with open(os.path.join(pkg, "conc.py"), "w") as f:
        f.write(src)
    return str(tmp_path)


def test_lock_order_cycle_reports_both_acquisition_paths(tmp_path):
    r = analyze(root=_lock_project(tmp_path, _CYCLE_SRC))
    fs = _rules(r.findings, "lock-order")
    assert len(fs) == 1, [f.human() for f in r.findings]
    msg = fs[0].msg
    assert "potential deadlock" in msg
    assert "`Svc._a` -> `Svc._b`" in msg or "`Svc._b` -> `Svc._a`" in msg
    # BOTH acquisition paths ride the finding: the call-closure edge
    # through _grab_b and the direct nested-with edge in two()
    assert msg.count("held") >= 2, msg
    assert "_grab_b" in msg
    assert msg.count("conc.py:") >= 2, msg


def test_lock_order_no_cycle_is_clean(tmp_path):
    src = _CYCLE_SRC.replace(
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n", "")
    r = analyze(root=_lock_project(tmp_path, src))
    assert not _rules(r.findings, "lock-order"), [
        f.human() for f in r.findings]


def test_lock_order_declaration_violation_and_unknown_name(tmp_path):
    src = """
import threading


class Svc:
    def __init__(self):
        # lock-order: Svc._b < Svc._a
        # lock-order: Svc._ghost < Svc._a
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass
"""
    r = analyze(root=_lock_project(tmp_path, src))
    msgs = "\n".join(f.msg for f in _rules(r.findings, "lock-order"))
    assert "violates the declared hierarchy" in msgs       # a->b vs b<a
    assert "Svc._ghost" in msgs and "no such lock" in msgs  # stale decl


def test_lock_order_declared_hierarchy_is_clean(tmp_path):
    src = """
import threading


class Svc:
    def __init__(self):
        # lock-order: Svc._a < Svc._b
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass
"""
    r = analyze(root=_lock_project(tmp_path, src))
    assert not _rules(r.findings, "lock-order"), [
        f.human() for f in r.findings]


def test_lock_order_rlock_reentry_is_not_a_self_deadlock(tmp_path):
    src = """
import threading


class Svc:
    def __init__(self):
        self._m = threading.RLock()

    def outer(self):
        with self._m:
            self.inner()

    def inner(self):
        with self._m:
            pass
"""
    r = analyze(root=_lock_project(tmp_path, src))
    assert not _rules(r.findings, "lock-order")
    plain = src.replace("RLock", "Lock")
    r2 = analyze(root=_lock_project(tmp_path, plain))
    msgs = "\n".join(f.msg for f in _rules(r2.findings, "lock-order"))
    assert "self-deadlock" in msgs


# ---------------------------------------------------------------------------
# family: thread & resource lifecycle
# ---------------------------------------------------------------------------

_LIFE_POS = """
import socket
import threading


def leaked_thread():
    t = threading.Thread(target=print)
    t.start()                             # never joined, not daemon


def happy_path_close(addr):
    s = socket.create_connection(addr)
    s.sendall(b"x")
    s.close()                             # skipped when sendall raises


def never_closed(addr):
    s = socket.create_connection(addr)
    s.sendall(b"x")


def gap_before_try(addr):
    s = socket.create_connection(addr)
    s.setsockopt(1, 2, 3)                 # raises -> finally never runs
    try:
        s.sendall(b"x")
    finally:
        s.close()
"""

_LIFE_NEG = """
import socket
import threading


def daemonized():
    t = threading.Thread(target=print, daemon=True)
    t.start()


def joined():
    t = threading.Thread(target=print)
    t.start()
    t.join()


def managed(addr):
    with socket.create_connection(addr) as s:
        s.sendall(b"x")


def closed_in_finally(addr):
    s = socket.create_connection(addr)
    try:
        s.sendall(b"x")
    finally:
        s.close()


def transferred(addr):
    s = socket.create_connection(addr)
    return s                              # the caller owns it now


class Owner:
    def __init__(self, addr):
        self._sock = socket.create_connection(addr)

    def close(self):
        self._sock.close()
"""


def test_lifecycle_true_positives():
    fs = _rules(analyze_source(
        _LIFE_POS, "dnn_page_vectors_tpu/infer/fixture.py"), "lifecycle")
    msgs = "\n".join(f.msg for f in fs)
    assert len(fs) == 4, [f.human() for f in fs]
    assert "neither daemonized nor joined" in msgs
    assert "happy path" in msgs
    assert "never closed" in msgs
    assert "between" in msgs and "try/finally" in msgs


def test_lifecycle_true_negatives():
    assert not _rules(analyze_source(
        _LIFE_NEG, "dnn_page_vectors_tpu/infer/fixture.py"), "lifecycle")


def test_lifecycle_unowned_self_attr_is_a_finding():
    src = ("import socket\n"
           "class Leaky:\n"
           "    def __init__(self, addr):\n"
           "        self._sock = socket.create_connection(addr)\n")
    fs = _rules(analyze_source(
        src, "dnn_page_vectors_tpu/infer/fixture.py"), "lifecycle")
    assert len(fs) == 1 and "leaked on shutdown" in fs[0].msg


def test_lifecycle_scope_excludes_models():
    assert not _rules(analyze_source(
        _LIFE_POS, "dnn_page_vectors_tpu/models/fixture.py"), "lifecycle")


# ---------------------------------------------------------------------------
# family: asyncio hygiene
# ---------------------------------------------------------------------------

_ASYNC_POS = """
import asyncio
import time


async def bad():
    time.sleep(0.1)                        # blocks the loop
    open("/tmp/x")                         # file I/O on the loop
    asyncio.create_task(asyncio.sleep(0))  # discarded task
    try:
        await asyncio.sleep(0)
    except:                                # swallows CancelledError
        pass
"""

_ASYNC_NEG = """
import asyncio
import time


async def good():
    await asyncio.sleep(0.1)
    t = asyncio.create_task(asyncio.sleep(0))
    await t
    try:
        await asyncio.sleep(0)
    except asyncio.CancelledError:
        raise
    except Exception:
        pass
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(None, lambda: time.sleep(0.1))


def sync_helper():
    time.sleep(0.1)                        # executor payload: fine
"""


def test_async_hygiene_true_positives():
    fs = _rules(analyze_source(
        _ASYNC_POS, "dnn_page_vectors_tpu/infer/fixture.py"),
        "async-hygiene")
    msgs = "\n".join(f.msg for f in fs)
    assert len(fs) == 4, [f.human() for f in fs]
    assert "time.sleep" in msgs
    assert "file I/O" in msgs
    assert "create_task" in msgs and "discarded" in msgs
    assert "CancelledError" in msgs


def test_async_hygiene_true_negatives():
    assert not _rules(analyze_source(
        _ASYNC_NEG, "dnn_page_vectors_tpu/infer/fixture.py"),
        "async-hygiene")


# ---------------------------------------------------------------------------
# family: wire-protocol conformance (project rule on a mini tree)
# ---------------------------------------------------------------------------

_MINI_TRANSPORT = '''
import struct

T_PING = 1
T_PONG = 2

_TYPES = {T_PING, T_PONG}

_HEAD = struct.Struct("!Q")


def decode_ping(payload):
    if len(payload) != _HEAD.size:
        raise ValueError("bad ping")
    return _HEAD.unpack(payload)[0]
'''

_MINI_SERVING_CLEAN = """# Serving

| type | payload | notes |
|---|---|---|
| `PING` | req u64 | ping |
| `PONG` | empty | pong |
"""

_MINI_SERVING_DIRTY = """# Serving

| type | payload | notes |
|---|---|---|
| `PING` | req u64 | ping |
| `GONE` | empty | removed long ago |
"""


def _proto_project(tmp_path, doc):
    root = str(tmp_path)
    pkg = os.path.join(root, "dnn_page_vectors_tpu", "infer")
    os.makedirs(pkg, exist_ok=True)
    os.makedirs(os.path.join(root, "docs"), exist_ok=True)
    with open(os.path.join(pkg, "transport.py"), "w") as f:
        f.write(_MINI_TRANSPORT)
    with open(os.path.join(root, "docs", "SERVING.md"), "w") as f:
        f.write(doc)
    return root


def test_proto_drift_catches_missing_and_stale_rows(tmp_path):
    r = analyze(root=_proto_project(tmp_path, _MINI_SERVING_DIRTY))
    msgs = "\n".join(f.msg for f in _rules(r.findings, "proto-drift"))
    assert "T_PONG" in msgs and "no row" in msgs        # constant undocumented
    assert "GONE" in msgs and "stale" in msgs           # row without constant
    # PONG's payload is unknown (no row), so the missing decode branch
    # flags too
    assert "no bounded-length decode branch" in msgs


def test_proto_drift_clean_table_passes(tmp_path):
    r = analyze(root=_proto_project(tmp_path, _MINI_SERVING_CLEAN))
    assert not _rules(r.findings, "proto-drift"), [
        f.human() for f in r.findings]


def test_proto_drift_unregistered_type_and_unguarded_decoder(tmp_path):
    src = _MINI_TRANSPORT.replace(
        "_TYPES = {T_PING, T_PONG}", "_TYPES = {T_PING}").replace(
        '    if len(payload) != _HEAD.size:\n'
        '        raise ValueError("bad ping")\n', "").replace(
        "    return _HEAD.unpack(payload)[0]",
        "    return _HEAD.unpack_from(payload)[0]")
    root = _proto_project(tmp_path, _MINI_SERVING_CLEAN)
    with open(os.path.join(root, "dnn_page_vectors_tpu", "infer",
                           "transport.py"), "w") as f:
        f.write(src)
    msgs = "\n".join(f.msg for f in _rules(
        analyze(root=root).findings, "proto-drift"))
    assert "not registered in `_TYPES`" in msgs
    assert "no length guard" in msgs


# ---------------------------------------------------------------------------
# family 3: jit purity + host-sync
# ---------------------------------------------------------------------------

_JIT_SRC = """
from functools import partial
import jax

TRACE_LOG = []

@jax.jit
def bad(x):
    print("tracing", x)                  # trace-time-only side effect
    TRACE_LOG.append(x)                  # captured-state mutation
    return x * 2

@partial(jax.jit, static_argnames=("k",))
def also_jitted(x, k):
    acc = []
    acc.append(k)                        # local list: fine
    return x[:k]

def host_fn(x):
    print("host side is allowed", x)
    return x
"""

_HOT_SRC = """
import numpy as np

# graftcheck: hot
def dispatch(dev_results):
    out = [r.item() for r in dev_results]     # per-element sync
    arr = np.asarray(dev_results)             # device pull
    return out, arr

def cold(dev_results):
    return [r.item() for r in dev_results]    # not marked hot: fine
"""


def test_jit_purity_matrix():
    fs = _rules(analyze_source(
        _JIT_SRC, "dnn_page_vectors_tpu/ops/fixture.py"), "jit-purity")
    msgs = "\n".join(f.msg for f in fs)
    assert len(fs) == 2, msgs
    assert "print()" in msgs and "mutates captured state" in msgs
    # models/ and index/ are in scope too; train/ is not a compiled-op home
    assert not _rules(analyze_source(
        _JIT_SRC, "dnn_page_vectors_tpu/train/fixture.py"), "jit-purity")


def test_host_sync_fires_only_on_hot_functions():
    fs = _rules(analyze_source(
        _HOT_SRC, "dnn_page_vectors_tpu/infer/fixture.py"), "host-sync")
    assert len(fs) == 2, [f.human() for f in fs]
    assert any(".item()" in f.msg for f in fs)
    assert any("numpy.asarray" in f.msg for f in fs)
    assert all(f.line < 10 for f in fs)       # nothing from cold()


# ---------------------------------------------------------------------------
# family 4: manifest I/O
# ---------------------------------------------------------------------------

_IO_SRC = """
import json
import os
import numpy as np

from dnn_page_vectors_tpu.infer.vector_store import crc_file

def bad_write(path, obj):
    with open(path, "w") as f:            # unmanifested write
        json.dump(obj, f)

def bad_save(path, arr):
    np.save(path, arr)                    # unmanifested array

def _atomic_dump(obj, path):
    with open(path + ".tmp", "w") as f:   # the sanctioned writer itself
        json.dump(obj, f)
    os.replace(path + ".tmp", path)

def crc_recorded_write(path, arr):
    np.save(path, arr)                    # CRC recorded below: sanctioned
    return os.path.getsize(path), crc_file(path)

def reader(path):
    with open(path) as f:                 # reads are nobody's business
        return f.read()
"""


def test_manifest_io_matrix():
    fs = _rules(analyze_source(
        _IO_SRC, "dnn_page_vectors_tpu/index/fixture.py"), "manifest-io")
    assert len(fs) == 2, [f.human() for f in fs]
    assert any("open" in f.msg for f in fs)
    assert any("numpy.save" in f.msg for f in fs)
    # infer/ (vector_store's own home) is not in this rule's scope
    assert not _rules(analyze_source(
        _IO_SRC, "dnn_page_vectors_tpu/infer/fixture.py"), "manifest-io")


# ---------------------------------------------------------------------------
# family 5: drift (project rules on a mini tree)
# ---------------------------------------------------------------------------

_MINI_CONFIG = '''
import dataclasses

@dataclasses.dataclass(frozen=True)
class ServeConfig:
    nprobe: int = 8
    mystery_knob: int = 3

@dataclasses.dataclass(frozen=True)
class Config:
    name: str
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
'''

_MINI_OBS_DOC = """# Observability

Knobs: `serve.nprobe` steers probing. See also `serve.ghost_knob`.

| event | meaning |
|---|---|
| `view_swap` | serving view hot-swapped |
| `dead_event` | documented but never emitted |
"""

_MINI_EVENTS_PY = '''
def fire(registry):
    registry.event("view_swap")
    registry.event("secret_event")
'''

_MINI_PYTEST_INI = """[pytest]
markers =
    slow: long tests
    ghost: declared but never used
"""

_MINI_TEST_PY = """
import pytest

@pytest.mark.slow
def test_a():
    pass

@pytest.mark.rogue
def test_b():
    pass
"""


def _mini_project(root, clean=False):
    pkg = os.path.join(root, "dnn_page_vectors_tpu")
    os.makedirs(pkg, exist_ok=True)
    os.makedirs(os.path.join(root, "docs"), exist_ok=True)
    os.makedirs(os.path.join(root, "tests"), exist_ok=True)
    cfg = _MINI_CONFIG
    obs = _MINI_OBS_DOC
    events = _MINI_EVENTS_PY
    ini = _MINI_PYTEST_INI
    test_py = _MINI_TEST_PY
    if clean:
        cfg = cfg.replace("    mystery_knob: int = 3\n", "")
        obs = (obs.replace("See also `serve.ghost_knob`.", "")
                  .replace("| `dead_event` | documented but never emitted |\n",
                           ""))
        events = events.replace('    registry.event("secret_event")\n', "")
        ini = ini.replace("    ghost: declared but never used\n", "")
        test_py = test_py.replace(
            "@pytest.mark.rogue\ndef test_b():\n    pass\n", "")
    with open(os.path.join(pkg, "config.py"), "w") as f:
        f.write(cfg)
    with open(os.path.join(pkg, "telem.py"), "w") as f:
        f.write(events)
    with open(os.path.join(root, "docs", "OBSERVABILITY.md"), "w") as f:
        f.write(obs)
    with open(os.path.join(root, "pytest.ini"), "w") as f:
        f.write(ini)
    with open(os.path.join(root, "tests", "test_mini.py"), "w") as f:
        f.write(test_py)
    return root


def test_drift_rules_mini_project(tmp_path):
    root = _mini_project(str(tmp_path))
    r = analyze(root=root)
    by_rule = {}
    for f in r.findings:
        by_rule.setdefault(f.rule, []).append(f)
    knob_msgs = "\n".join(f.msg for f in by_rule.get("drift-knobs", []))
    assert "serve.mystery_knob" in knob_msgs          # undocumented knob
    assert "serve.ghost_knob" in knob_msgs            # stale doc reference
    ev_msgs = "\n".join(f.msg for f in by_rule.get("drift-events", []))
    assert "secret_event" in ev_msgs                  # emitted, undocumented
    assert "dead_event" in ev_msgs                    # documented, dead
    mk_msgs = "\n".join(f.msg for f in by_rule.get("drift-markers", []))
    assert "rogue" in mk_msgs                         # used, undeclared
    assert "ghost" in mk_msgs                         # declared, unused
    # and the `view_swap`/`slow`/`nprobe` matches stayed silent
    for quiet in ("view_swap", "`slow`", "serve.nprobe"):
        assert quiet not in knob_msgs + ev_msgs + mk_msgs


def test_drift_knobs_exempts_profiler_stage_events(tmp_path):
    """`serve.topk` in a doc is the profiler's trace event (the prefix a
    PipelineProfiler is built with + a stage name), not a knob; a
    `serve.<word>` that is neither knob, instrument nor stage still is."""
    root = _mini_project(str(tmp_path), clean=True)
    with open(os.path.join(root, "dnn_page_vectors_tpu", "svc.py"), "w") as f:
        f.write('prof = PipelineProfiler(prefix="serve.")\n'
                'with prof.stage("topk"):\n    pass\n'
                'with self._stage("merge", shards=3):\n    pass\n')
    doc = os.path.join(root, "docs", "OBSERVABILITY.md")
    with open(doc, "a") as f:
        f.write("Events: `serve.topk`, `serve.merge`; not `serve.nostage`.\n")
    msgs = [f.msg for f in analyze(root=root).findings]
    assert len(msgs) == 1 and "serve.nostage" in msgs[0], msgs


def test_drift_rules_clean_mini_project(tmp_path):
    root = _mini_project(str(tmp_path), clean=True)
    r = analyze(root=root)
    assert not r.findings, [f.human() for f in r.findings]


# ---------------------------------------------------------------------------
# pragma semantics
# ---------------------------------------------------------------------------

def test_pragma_inline_with_reason_suppresses():
    src = ("import numpy as np\n"
           "x = np.random.rand(3)  "
           "# graftcheck: off=determinism -- fixture wants raw entropy\n")
    fs = analyze_source(src, "dnn_page_vectors_tpu/infer/fixture.py")
    assert not _rules(fs, "determinism")
    assert not _rules(fs, "pragma")


def test_pragma_without_reason_is_rejected_and_reported():
    src = ("import numpy as np\n"
           "x = np.random.rand(3)  # graftcheck: off=determinism\n")
    fs = analyze_source(src, "dnn_page_vectors_tpu/infer/fixture.py")
    assert _rules(fs, "determinism")       # NOT suppressed
    assert _rules(fs, "pragma")            # and the naked pragma is flagged


def test_pragma_wrong_rule_does_not_suppress():
    src = ("import numpy as np\n"
           "x = np.random.rand(3)  # graftcheck: off=locks -- wrong family\n")
    fs = analyze_source(src, "dnn_page_vectors_tpu/infer/fixture.py")
    assert _rules(fs, "determinism")


def test_pragma_file_scope_at_top_of_file():
    src = ("# graftcheck: off=determinism -- synthetic chaos fixture\n"
           "import numpy as np\n"
           "x = np.random.rand(3)\n"
           "y = np.random.rand(4)\n")
    fs = analyze_source(src, "dnn_page_vectors_tpu/infer/fixture.py")
    assert not _rules(fs, "determinism")


def test_pragma_standalone_mid_file_covers_next_code_line_only():
    src = ("import numpy as np\n"
           "# graftcheck: off=determinism -- seeded upstream of this call\n"
           "x = np.random.rand(3)\n"
           "y = np.random.rand(4)\n")
    fs = _rules(analyze_source(
        src, "dnn_page_vectors_tpu/infer/fixture.py"), "determinism")
    assert len(fs) == 1 and fs[0].line == 4


# ---------------------------------------------------------------------------
# baseline add / expire
# ---------------------------------------------------------------------------

def test_baseline_add_and_expire(tmp_path):
    root = _mini_project(str(tmp_path))
    baseline = os.path.join(root, BASELINE_NAME)
    first = analyze(root=root)
    assert first.findings and first.exit_code == 1
    write_baseline(baseline, first.findings)

    second = analyze(root=root)             # same tree, accepted findings
    assert not second.findings and second.exit_code == 0
    assert len(second.baselined) == len(first.findings)
    assert not second.stale_baseline

    _mini_project(str(tmp_path), clean=True)  # everything fixed
    third = analyze(root=root)
    assert not third.findings and third.exit_code == 0
    assert not third.baselined
    assert third.stale_baseline              # entries now expired, listed


# ---------------------------------------------------------------------------
# cli lint: JSON report shape + exit codes (subprocess, no jax import)
# ---------------------------------------------------------------------------

def _run_lint(root):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "dnn_page_vectors_tpu.cli", "lint",
         "--root", root],
        capture_output=True, text=True, env=env, timeout=120)


def test_cli_lint_exits_nonzero_on_seeded_violation(tmp_path):
    proc = _run_lint(_mini_project(str(tmp_path)))
    assert proc.returncode == 1, proc.stderr
    report = json.loads(proc.stdout)
    assert report["exit_code"] == 1
    assert report["counts"]["findings"] == len(report["findings"])
    assert report["findings"], report
    f = report["findings"][0]
    assert set(f) >= {"rule", "path", "line", "col", "msg", "snippet"}
    # human diagnostics ride stderr as file:line:col
    assert ":" in proc.stderr.splitlines()[0]


def test_cli_lint_exits_zero_on_clean_tree_and_after_write_baseline(tmp_path):
    clean_root = _mini_project(str(tmp_path / "clean"), clean=True)
    os.makedirs(clean_root, exist_ok=True)
    proc = _run_lint(clean_root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["counts"]["findings"] == 0
    assert sorted(report["rules"]) == sorted(RULES)

    dirty_root = _mini_project(str(tmp_path / "dirty"))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    wb = subprocess.run(
        [sys.executable, "-m", "dnn_page_vectors_tpu.cli", "lint",
         "--root", dirty_root, "--write-baseline"],
        capture_output=True, text=True, env=env, timeout=120)
    assert wb.returncode == 0, wb.stderr
    assert json.loads(wb.stdout)["entries"] > 0
    proc = _run_lint(dirty_root)             # baselined: now green
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout)["counts"]["baselined"] > 0


# ---------------------------------------------------------------------------
# cli lint --changed: the fast pre-commit mode (docs/ANALYSIS.md)
# ---------------------------------------------------------------------------

def test_analyze_paths_restricts_file_rules_only(tmp_path):
    root = str(tmp_path)
    pkg = os.path.join(root, "dnn_page_vectors_tpu", "infer")
    os.makedirs(pkg, exist_ok=True)
    bad = ("import numpy as np\n"
           "x = np.random.rand(3)\n")
    for name in ("one.py", "two.py"):
        with open(os.path.join(pkg, name), "w") as f:
            f.write(bad)
    full = analyze(root=root)
    assert len(_rules(full.findings, "determinism")) == 2
    part = analyze(root=root,
                   paths=["dnn_page_vectors_tpu/infer/one.py"])
    fs = _rules(part.findings, "determinism")
    assert len(fs) == 1 and fs[0].path.endswith("one.py")
    assert part.files_scanned == 1


def test_analyze_paths_suppresses_stale_baseline(tmp_path):
    root = _mini_project(str(tmp_path))
    baseline = os.path.join(root, BASELINE_NAME)
    write_baseline(baseline, analyze(root=root).findings)
    _mini_project(str(tmp_path), clean=True)     # everything fixed
    full = analyze(root=root)
    assert full.stale_baseline                   # full mode reports stale
    part = analyze(root=root, paths=[])
    assert not part.stale_baseline               # restricted mode cannot


def test_cli_lint_changed_runs_project_rules_on_the_real_repo():
    """`--changed HEAD` on this checkout: file rules over only the
    diffed files, project rules whole-repo, exit 0 (the repo is clean).
    Also pins the stderr mode banner and that the JSON shape is the
    plain report."""
    if not os.path.isdir(os.path.join(_REPO, ".git")):
        pytest.skip("not a git checkout")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "dnn_page_vectors_tpu.cli", "lint",
         "--root", _REPO, "--changed", "HEAD"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["counts"]["findings"] == 0
    # the project-level rules ran regardless of the diff restriction
    assert "proto-drift" in report["rules"]
    assert "lock-order" in report["rules"]
    assert "--changed" in proc.stderr or "changed" in proc.stderr


def test_cli_lint_changed_bad_ref_exits_2(tmp_path):
    if not os.path.isdir(os.path.join(_REPO, ".git")):
        pytest.skip("not a git checkout")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "dnn_page_vectors_tpu.cli", "lint",
         "--root", _REPO, "--changed", "no-such-ref-xyzzy"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 2
    assert "failed" in proc.stderr


# ---------------------------------------------------------------------------
# the repo itself is clean — the tier-1 gate behind `cli lint` exit 0
# ---------------------------------------------------------------------------

def test_repo_has_no_unsuppressed_findings():
    r = analyze(root=_REPO)
    assert not r.findings, "\n".join(f.human() for f in r.findings)
    assert not r.stale_baseline, r.stale_baseline
    # every suppression carries its reason (enforced by the pragma rule,
    # double-checked here so the report stays honest)
    assert all(s.get("reason") for s in r.suppressed)


def test_bulk_embed_sweep_is_host_sync_scoped():
    """Round 11 (MFU campaign): the bulk-embed sweep is `# graftcheck:
    hot`, so an accidental per-array `.item()`/`np.asarray` sync added
    inside the new packed-d2h pipeline fails `cli lint`. Pinned two ways:
    the annotation exists on embed_corpus (the repo's ONE packed
    device_get shows up as a reasoned host-sync suppression), and an
    accidental sync inserted into an identically-annotated loop is a
    finding."""
    r = analyze(root=_REPO)
    assert any(s["path"].endswith("infer/bulk_embed.py")
               and s["rule"] == "host-sync" and s.get("reason")
               for s in r.suppressed), (
        "embed_corpus lost its hot annotation (or its packed-d2h pragma)")
    findings = analyze_source(
        "import numpy as np\n"
        "# graftcheck: hot\n"
        "def embed_sweep(batches):\n"
        "    out = []\n"
        "    for b in batches:\n"
        "        out.append(np.asarray(b))\n"
        "    return out\n",
        "pkg/infer/sweep.py")
    assert _rules(findings, "host-sync"), \
        "np.asarray inside a hot embed loop must be a host-sync finding"


def test_analyzer_is_stdlib_only():
    """The lint path must run on a jax-less box: no jax/numpy imports
    anywhere under tools/analyze (the subprocess tests above strip
    JAX_PLATFORMS, this pins the import graph itself)."""
    import ast
    adir = os.path.join(_REPO, "dnn_page_vectors_tpu", "tools", "analyze")
    for name in os.listdir(adir):
        if not name.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(adir, name)).read())
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                mods = [node.module]
            for m in mods:
                root_mod = m.split(".")[0]
                assert root_mod not in ("jax", "numpy", "jaxlib"), (
                    f"{name} imports {m}")


def test_rule_registry_documented():
    """Every registered rule appears (backticked) in docs/ANALYSIS.md —
    the analyzer eats its own drift dog food."""
    doc = open(os.path.join(_REPO, "docs", "ANALYSIS.md")).read()
    for name in RULES:
        assert f"`{name}`" in doc, f"rule `{name}` missing from ANALYSIS.md"
    families = {r.family for r in RULES.values()}
    assert {"determinism", "locks", "jit", "io", "drift",
            "lock-order", "lifecycle", "async", "proto"} <= families
