"""Rule family 5 — doc/knob/marker drift (docs/ANALYSIS.md).

Generalizes the two hand-rolled drift checks that used to live only in
tests/test_telemetry.py into project-level rules, and adds a third:

  * drift-knobs   — every config dataclass field is documented as
                    `section.field` somewhere under docs/ or README.md, and
                    every `section.field` the docs mention really exists.
  * drift-events  — every `registry.event("name")` emitted in the package
                    appears in the docs/OBSERVABILITY.md event table, and
                    the table advertises no dead events.
  * drift-markers — every `@pytest.mark.<name>` used under tests/ is
                    declared in pytest.ini, and no declared marker is dead.

Everything is parsed with `ast`/regex — no imports of the package, so the
rules run on a jax-less box and on half-broken trees.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Set, Tuple

from dnn_page_vectors_tpu.tools.analyze.core import (
    Finding, ProjectContext, Rule, register, PKG_NAME)

_CONFIG_REL = f"{PKG_NAME}/config.py"
_OBS_DOC = "docs/OBSERVABILITY.md"
_EVENT_RE = re.compile(r"\.event\(\s*[\"']([a-z_]+)[\"']")
_EVENT_ROW_RE = re.compile(r"^\|\s*`([a-z_]+)`", re.M)
_BUILTIN_MARKERS = {"parametrize", "skip", "skipif", "xfail", "usefixtures",
                    "filterwarnings", "timeout"}
# doc tokens that look like `section.word` but are file/module suffixes
_NOT_KNOB_SUFFIX = {"py", "md", "json", "npy", "ini", "txt", "ivf"}


def _line_of(text: str, pos: int) -> int:
    return text.count("\n", 0, pos) + 1


def _config_schema(ctx: ProjectContext):
    """(sections, fields, linenos): section name -> dataclass fields, via
    AST only. sections maps e.g. "serve" -> "ServeConfig"."""
    src = ctx.read(_CONFIG_REL)
    if src is None:
        return {}, {}, {}
    tree = ast.parse(src)
    classes: Dict[str, ast.ClassDef] = {
        n.name: n for n in tree.body if isinstance(n, ast.ClassDef)}
    fields: Dict[str, List[Tuple[str, int]]] = {}
    for name, cls in classes.items():
        fields[name] = [
            (st.target.id, st.lineno) for st in cls.body
            if isinstance(st, ast.AnnAssign) and isinstance(st.target,
                                                            ast.Name)]
    sections: Dict[str, str] = {}
    root_cls = classes.get("Config")
    if root_cls is not None:
        for st in root_cls.body:
            if (isinstance(st, ast.AnnAssign)
                    and isinstance(st.target, ast.Name)
                    and isinstance(st.annotation, ast.Name)
                    and st.annotation.id in classes
                    and st.annotation.id.endswith("Config")):
                sections[st.target.id] = st.annotation.id
    return sections, fields, classes


def _doc_files(ctx: ProjectContext) -> List[str]:
    return ctx.glob("docs", ".md") + [
        p for p in ("README.md",) if ctx.read(p) is not None]


@register
class KnobDriftRule(Rule):
    name = "drift-knobs"
    family = "drift"
    doc = ("every config.py knob documented as `section.field` in docs/ or "
           "README; no doc names a knob that does not exist")
    project = True

    def check_project(self, ctx: ProjectContext) -> Iterator[Finding]:
        sections, fields, _ = _config_schema(ctx)
        if not sections:
            return
        docs = {p: ctx.read(p) or "" for p in _doc_files(ctx)}
        all_docs = "\n".join(docs.values())
        for section, cls_name in sections.items():
            for field, lineno in fields.get(cls_name, []):
                knob = f"{section}.{field}"
                if knob not in all_docs:
                    yield ctx.finding(
                        self.name, _CONFIG_REL, lineno,
                        f"config knob `{knob}` is not documented — add it "
                        "to a knob table under docs/ (docs/CONFIG.md holds "
                        "the train/data/model/eval tables)")
        known = {f"{s}.{f}" for s, cls in sections.items()
                 for f, _ in fields.get(cls, [])}
        # registry instrument names share the `section.` spelling
        # (`serve.recompiles`, `serve.queue_wait_ms`): a doc naming one is
        # documenting a metric, not a knob — collect and exempt them
        instruments = set()
        inst_re = re.compile(
            r"\.(?:counter|gauge|histogram)\(\s*[\"']([a-z_][a-z0-9_.]*)")
        # so do the profiler's trace events (`serve.topk`): the prefix a
        # PipelineProfiler is built with + a stage name (docs/
        # OBSERVABILITY.md "The combined trace") — exempt every such pair
        prefix_re = re.compile(
            r"PipelineProfiler\(\s*prefix=[\"']([a-z_]+\.)[\"']")
        # (a stage opened by name, or an event name the profiler makes
        # itself from its prefix: the collector's `<prefix>gc`)
        stage_re = re.compile(
            r"(?:\b_?stage\(|\b_prefix\s*\+)\s*[\"']([a-z_][a-z0-9_]*)[\"']")
        prefixes, stages = set(), set()
        for rel in ctx.glob(ctx.pkg, ".py"):
            text = ctx.read(rel) or ""
            instruments.update(inst_re.findall(text))
            prefixes.update(prefix_re.findall(text))
            stages.update(stage_re.findall(text))
        instruments.update(p + s for p in prefixes for s in stages)
        pat = re.compile(
            r"\b(" + "|".join(map(re.escape, sorted(sections))) +
            r")\.([a-z_][a-z0-9_]*)\b")
        for path, text in docs.items():
            for m in pat.finditer(text):
                knob, suffix = m.group(0), m.group(2)
                if suffix in _NOT_KNOB_SUFFIX or knob in known \
                        or knob in instruments:
                    continue
                if text[m.end():m.end() + 1] == "(":
                    continue   # `faults.counters()`-style API reference
                yield ctx.finding(
                    self.name, path, _line_of(text, m.start()),
                    f"doc names `{knob}` but no such field exists on "
                    f"{sections[m.group(1)]} — stale knob reference")


@register
class EventDriftRule(Rule):
    name = "drift-events"
    family = "drift"
    doc = ("every `registry.event(...)` name appears in the "
           "docs/OBSERVABILITY.md event table and vice versa")
    project = True

    @staticmethod
    def _event_rows(doc: str) -> Dict[str, int]:
        """Backticked names from tables whose header's FIRST cell is
        `event` — other tables in the doc (knobs, the shed-reason list
        the proto-drift rule owns) are not event rows."""
        out: Dict[str, int] = {}
        in_event_table = False
        for i, line in enumerate(doc.splitlines(), 1):
            stripped = line.strip()
            if not stripped.startswith("|"):
                in_event_table = False
                continue
            cells = [c.strip() for c in stripped.strip("|").split("|")]
            if cells and cells[0].lower() == "event":
                in_event_table = True
                continue
            if not in_event_table:
                continue
            m = _EVENT_ROW_RE.match(stripped)
            if m:
                out.setdefault(m.group(1), i)
        return out

    def check_project(self, ctx: ProjectContext) -> Iterator[Finding]:
        doc = ctx.read(_OBS_DOC)
        if doc is None:
            return
        documented = self._event_rows(doc)
        emitted: Dict[str, Tuple[str, int]] = {}
        tools_prefix = f"{ctx.pkg}/tools/"
        for rel in ctx.glob(ctx.pkg, ".py"):
            if rel.startswith(tools_prefix):
                continue   # the analyzer quotes the pattern it hunts
            text = ctx.read(rel) or ""
            for m in _EVENT_RE.finditer(text):
                emitted.setdefault(m.group(1),
                                   (rel, _line_of(text, m.start())))
        if not emitted and len(documented) >= 5:
            # the emit regex went stale (an API rename would zero the scan
            # silently while the doc still advertises a full table — the
            # old hand-rolled test pinned >= 10 emitted names)
            yield ctx.finding(
                self.name, _OBS_DOC, 1,
                "event scan found NOTHING while the doc documents "
                f"{len(documented)} events — `registry.event` spelling "
                "drift?")
        for name, (rel, line) in sorted(emitted.items()):
            if name not in documented:
                yield ctx.finding(
                    self.name, rel, line,
                    f"event `{name}` is emitted here but missing from the "
                    f"{_OBS_DOC} event table")
        for name, line in sorted(documented.items()):
            if name not in emitted:
                yield ctx.finding(
                    self.name, _OBS_DOC, line,
                    f"event `{name}` is documented but never emitted — "
                    "dead table row")


@register
class MarkerDriftRule(Rule):
    name = "drift-markers"
    family = "drift"
    doc = ("every pytest marker used under tests/ is declared in "
           "pytest.ini; no declared marker is unused")
    project = True

    def check_project(self, ctx: ProjectContext) -> Iterator[Finding]:
        ini = ctx.read("pytest.ini")
        if ini is None:
            return
        declared: Dict[str, int] = {}
        in_markers = False
        for i, line in enumerate(ini.splitlines(), 1):
            if re.match(r"\s*markers\s*=", line):
                in_markers = True
                rest = line.split("=", 1)[1].strip()
                if rest:
                    declared.setdefault(rest.split(":")[0].strip(), i)
                continue
            if in_markers:
                if line.strip() and line[:1].isspace():
                    declared.setdefault(line.strip().split(":")[0].strip(), i)
                elif line.strip():
                    in_markers = False
        used: Dict[str, Tuple[str, int]] = {}
        for rel in ctx.glob("tests", ".py"):
            text = ctx.read(rel) or ""
            try:
                tree = ast.parse(text)
            except SyntaxError:
                continue
            # AST, not regex: a fixture STRING quoting `pytest.mark.x`
            # (this analyzer's own tests do) is not a marker usage
            for node in ast.walk(tree):
                if (isinstance(node, ast.Attribute)
                        and isinstance(node.value, ast.Attribute)
                        and node.value.attr == "mark"
                        and isinstance(node.value.value, ast.Name)
                        and node.value.value.id == "pytest"
                        and node.attr not in _BUILTIN_MARKERS):
                    used.setdefault(node.attr, (rel, node.lineno))
        for name, (rel, line) in sorted(used.items()):
            if name not in declared:
                yield ctx.finding(
                    self.name, rel, line,
                    f"marker `@pytest.mark.{name}` is not declared in "
                    "pytest.ini — add it with a one-line description")
        for name, line in sorted(declared.items()):
            if name not in used:
                yield ctx.finding(
                    self.name, "pytest.ini", line,
                    f"marker `{name}` is declared but never used under "
                    "tests/")
