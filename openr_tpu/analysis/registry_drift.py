"""registry-drift: hand-maintained registries must match the code.

Three registries drift silently as the codebase grows; this rule pins each
to its source of truth (it is the generalization of the old
tests/test_counter_naming.py lint into the analysis layer — that test now
delegates here):

  1. Counter/histogram names. Every name emitted through
     CountersMixin/HistogramsMixin (`self._bump("...")`,
     `self._observe("...")`, `self._timer("...")`, literal subscripts on
     `counters`/`histograms`/`_ensure_counters()`/`_ensure_histograms()`)
     must follow `<module>.<name>` with a registered module prefix
     (docs/Monitoring.md); `_observe`/`_timer` names must carry a unit
     suffix (`*_ms`/`*_bytes`). On full-package scans the naming tables in
     docs/Monitoring.md are cross-checked: every documented name must
     exist in code (no ghost rows), and every emitted histogram must be
     documented (the histogram table is exhaustive by contract; the
     counter table is explicitly exemplary).
  2. Fault points. `fault_point("...")` names in code vs. the catalog
     table in docs/Robustness.md — both directions.
  3. Decision config knobs. Every `DecisionConfigSection` field must be
     mentioned in docs/ (bare, or as the `--decision_<name>` flag), and
     every `solver_*`-style knob the docs name must exist as a field.
  4. LogSample event names. Every event name stamped onto a LogSample —
     `sample.add_string("event", <literal or module constant>)` and
     `self._emit_sample("NAME", ...)` — must appear in the event-catalog
     table of docs/Monitoring.md, and every cataloged event must be
     emitted (CONVERGENCE_TRACE, FLOOD_TRACE, SOLVER_BREAKER_*,
     WARM_STATE_AUDIT_MISMATCH, ... — both directions).

Doc-name shorthand understood when parsing tables: `{a,b}` brace
alternation, `*` suffix wildcards, and `x_sent/recv` slash alternation on
the final `_`-separated token. Event-catalog rows are ALL_CAPS tokens and
support the same braces and `*` suffix wildcards.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

from openr_tpu.analysis.core import (
    RULES,
    AnalysisContext,
    Rule,
    SourceFile,
    register,
    walk_nodes,
)

MIXINS = {"CountersMixin", "HistogramsMixin"}

# module prefixes registered with the Monitor (openr.py) plus the
# cross-module end-to-end namespaces and process-level stats; "ctrl"
# covers the streaming control plane's fan-out + admission layers
# (ctrl.stream.* / ctrl.admission.*, docs/Streaming.md); "restart" is
# the whole-node warm-boot span (restart.e2e_ms, closed by Fib like
# convergence.e2e_ms — docs/Robustness.md "Graceful restart & warm boot");
# "fleet" is the fleet observer's own telemetry (openr_tpu/fleet — a
# Monitor-registrable module even though it usually runs out-of-daemon,
# docs/Monitoring.md "Fleet observer & SLO watchdog")
ALLOWED_PREFIXES = {
    "decision",
    "kvstore",
    "fib",
    "spark",
    "link_monitor",
    "prefix_manager",
    "convergence",
    "restart",
    "process",
    "monitor",
    "ctrl",
    "fleet",
    # the state journal (openr_tpu/journal — docs/Journal.md): recorder,
    # durable log and replay engine telemetry (docs/Monitoring.md
    # "State journal")
    "journal",
}

# <module>.<name>[.<name>...], lowercase snake segments
NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")
_DOC_TOKEN_RE = re.compile(r"`([a-z0-9_.{},*/]+)`")

# LogSample event names: SCREAMING_SNAKE (CONVERGENCE_TRACE, FLOOD_TRACE)
EVENT_NAME_RE = re.compile(r"^[A-Z][A-Z0-9_]*$")
_EVENT_DOC_TOKEN_RE = re.compile(r"`([A-Z0-9_{},*]+)`")

_EMIT_CALLS = {"_bump", "_observe", "_timer"}
_HIST_CALLS = {"_observe", "_timer"}
_DICT_ATTRS = {"counters", "histograms"}
_ENSURE_CALLS = {"_ensure_counters", "_ensure_histograms"}


# ---------------------------------------------------------------------------
# emission collection (the old test_counter_naming walk, context-based)
# ---------------------------------------------------------------------------


def _base_names(node: ast.ClassDef):
    for base in node.bases:
        if isinstance(base, ast.Name):
            yield base.id
        elif isinstance(base, ast.Attribute):
            yield base.attr


def _mixin_classes(ctx: AnalysisContext) -> Set[str]:
    """Names of classes inheriting a mixin, transitively by simple name."""
    bases: Dict[str, Set[str]] = {}
    for sf in ctx.files:
        for node in walk_nodes(sf.tree):
            if isinstance(node, ast.ClassDef):
                bases[node.name] = set(_base_names(node))
    users = set(MIXINS)
    changed = True
    while changed:
        changed = False
        for name, bs in bases.items():
            if name not in users and bs & users:
                users.add(name)
                changed = True
    return users - MIXINS


def _is_dict_ref(node) -> bool:
    """`self.counters` / `x.histograms` / `self._ensure_counters()` or a
    local alias of one (`counters = self._ensure_counters()`)."""
    if isinstance(node, ast.Attribute) and node.attr in _DICT_ATTRS:
        return True
    if isinstance(node, ast.Name) and node.id in _DICT_ATTRS:
        return True
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr in _ENSURE_CALLS
    )


def collect_emitted_names(
    ctx: AnalysisContext,
) -> List[Tuple[str, SourceFile, int]]:
    """(name, file, line) for every mixin-user emission site in scope."""
    mixin_users = _mixin_classes(ctx)
    found: List[Tuple[str, SourceFile, int]] = []
    for sf in ctx.files:
        for cls in walk_nodes(sf.tree):
            if not (
                isinstance(cls, ast.ClassDef) and cls.name in mixin_users
            ):
                continue
            for node in walk_nodes(cls):
                name = None
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _EMIT_CALLS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    name = node.args[0].value
                elif (
                    isinstance(node, ast.Subscript)
                    and _is_dict_ref(node.value)
                    and isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)
                ):
                    name = node.slice.value
                if name is not None:
                    found.append((name, sf, node.lineno))
    return found


def _stage_histogram(node) -> Optional[str]:
    """`stage("<name>", <histograms>, ...)` (monitor/spans.py) records into
    the histogram `<name>_ms`; a stage with no histograms records none."""
    if not (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id == "stage"
        and len(node.args) >= 2
        and isinstance(node.args[0], ast.Constant)
        and isinstance(node.args[0].value, str)
    ):
        return None
    return f"{node.args[0].value}_ms"


def collect_histogram_names(
    ctx: AnalysisContext,
) -> List[Tuple[str, SourceFile, int]]:
    """Literal first args of _observe/_timer anywhere in scope, and the
    histograms of the stages."""
    found = []
    for sf in ctx.files:
        for node in walk_nodes(sf.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _HIST_CALLS
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                found.append((node.args[0].value, sf, node.lineno))
            elif (name := _stage_histogram(node)) is not None:
                found.append((name, sf, node.lineno))
    return found


def _string_universe(ctx: AnalysisContext) -> Tuple[Set[str], Set[str]]:
    """(exact names, f-string prefixes) of dotted-name-shaped string
    constants anywhere in the scanned code — the existence oracle for the
    doc-direction checks (f-strings like
    f"decision.spf.solver_failures.{kind}" contribute their literal
    prefix)."""
    exact: Set[str] = set()
    prefixes: Set[str] = set()
    for sf in ctx.files:
        for node in walk_nodes(sf.tree):
            if isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ):
                if NAME_RE.match(node.value):
                    exact.add(node.value)
            elif (name := _stage_histogram(node)) is not None:
                exact.add(name)
            elif isinstance(node, ast.JoinedStr) and node.values:
                first = node.values[0]
                if isinstance(first, ast.Constant) and isinstance(
                    first.value, str
                ):
                    if "." in first.value:
                        prefixes.add(first.value)
    return exact, prefixes


# ---------------------------------------------------------------------------
# doc parsing
# ---------------------------------------------------------------------------


def _expand_doc_token(token: str) -> List[str]:
    """Expand one backticked doc token into candidate names/wildcards."""
    # {a,b} alternation (possibly with a suffix after the brace)
    m = re.match(r"^(.*)\{([^}]*)\}(.*)$", token)
    if m:
        out: List[str] = []
        for alt in m.group(2).split(","):
            out.extend(_expand_doc_token(m.group(1) + alt + m.group(3)))
        return out
    # x_sent/recv slash alternation on the final token
    if "/" in token:
        head, _, tail = token.rpartition("/")
        if "." in tail or "/" in head and "." in head.rsplit("/", 1)[1]:
            return []  # a path like fib/fib.py, not a name
        if not head or "." not in head:
            return []
        base = head
        cut = base.rfind("_")
        if cut < 0:
            return []
        second = base[: cut + 1] + tail.lstrip("_")
        return _expand_doc_token(head) + _expand_doc_token(second)
    if token.endswith("*"):
        stem = token.rstrip("*")
        return [stem + "*"] if "." in stem else []
    return [token] if NAME_RE.match(token) else []


def _table_names(text: str, header_hint: Optional[str] = None) -> Set[str]:
    """Backticked names from markdown table rows. With header_hint, only
    tables whose header row mentions it are read."""
    names: Set[str] = set()
    in_table = header_hint is None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped.startswith("|"):
            if header_hint is not None:
                in_table = False
            continue
        if header_hint is not None and header_hint in stripped.lower():
            in_table = True
            continue
        if not in_table:
            continue
        for token in _DOC_TOKEN_RE.findall(stripped):
            names.update(_expand_doc_token(token))
    return names


def _exists_in_code(
    name: str, exact: Set[str], prefixes: Set[str]
) -> bool:
    if name.endswith("*"):
        stem = name[:-1]
        return any(e.startswith(stem) for e in exact) or any(
            p.startswith(stem) or stem.startswith(p) for p in prefixes
        )
    return name in exact or any(name.startswith(p) for p in prefixes)


# ---------------------------------------------------------------------------
# LogSample event names
# ---------------------------------------------------------------------------


def collect_log_events(
    ctx: AnalysisContext,
) -> List[Tuple[str, SourceFile, int]]:
    """(event-name, file, line) for every LogSample event emission:
    `*.add_string("event", X)` where X is a string literal or a
    module-level string constant, and literal first args of
    `self._emit_sample("NAME", ...)` helpers."""
    found: List[Tuple[str, SourceFile, int]] = []
    for sf in ctx.files:
        consts: Dict[str, str] = {}
        for node in sf.tree.body:
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                consts[node.targets[0].id] = node.value.value
        for node in walk_nodes(sf.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
            ):
                continue
            name: Optional[str] = None
            if (
                node.func.attr == "add_string"
                and len(node.args) >= 2
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value == "event"
            ):
                arg = node.args[1]
                if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str
                ):
                    name = arg.value
                elif isinstance(arg, ast.Name):
                    name = consts.get(arg.id)
            elif (
                node.func.attr == "_emit_sample"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                name = node.args[0].value
            if name is not None and EVENT_NAME_RE.match(name):
                found.append((name, sf, node.lineno))
    return found


def _expand_event_token(token: str) -> List[str]:
    m = re.match(r"^(.*)\{([^}]*)\}(.*)$", token)
    if m:
        out: List[str] = []
        for alt in m.group(2).split(","):
            out.extend(_expand_event_token(m.group(1) + alt + m.group(3)))
        return out
    if token.endswith("*"):
        stem = token.rstrip("*")
        return [stem + "*"] if EVENT_NAME_RE.match(stem) else []
    return [token] if EVENT_NAME_RE.match(token) else []


def _event_table_names(text: str) -> Set[str]:
    """ALL_CAPS backticked tokens from the event-catalog table (rows of
    markdown tables whose header mentions 'event')."""
    names: Set[str] = set()
    in_table = False
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped.startswith("|"):
            in_table = False
            continue
        if "event" in stripped.lower() and "---" not in stripped:
            header_tokens = _EVENT_DOC_TOKEN_RE.findall(stripped)
            if not header_tokens:
                in_table = True
                continue
        if not in_table:
            continue
        for token in _EVENT_DOC_TOKEN_RE.findall(stripped):
            names.update(_expand_event_token(token))
    return names


def _event_documented(name: str, documented: Set[str]) -> bool:
    if name in documented:
        return True
    return any(
        name.startswith(d[:-1]) for d in documented if d.endswith("*")
    )


# ---------------------------------------------------------------------------
# fault points + config knobs
# ---------------------------------------------------------------------------


def collect_fault_points(
    ctx: AnalysisContext,
) -> List[Tuple[str, SourceFile, int]]:
    """Literal first args of fault_point(...) declarations in scope."""
    found = []
    for sf in ctx.files:
        if sf.rel.endswith("testing/faults.py"):
            continue  # the harness itself, not a declaration site
        for node in walk_nodes(sf.tree):
            if (
                isinstance(node, ast.Call)
                and (
                    (
                        isinstance(node.func, ast.Name)
                        and node.func.id == "fault_point"
                    )
                    or (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr == "fault_point"
                    )
                )
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                found.append((node.args[0].value, sf, node.lineno))
    return found


def _decision_config_fields(
    ctx: AnalysisContext,
) -> List[Tuple[str, SourceFile, int]]:
    fields = []
    for sf in ctx.files:
        for node in walk_nodes(sf.tree):
            if (
                isinstance(node, ast.ClassDef)
                and node.name == "DecisionConfigSection"
            ):
                for stmt in node.body:
                    if isinstance(stmt, ast.AnnAssign) and isinstance(
                        stmt.target, ast.Name
                    ):
                        fields.append((stmt.target.id, sf, stmt.lineno))
    return fields


@register
class RegistryDriftRule(Rule):
    name = "registry-drift"
    severity = "error"
    description = (
        "counter/histogram names, fault points, LogSample event names and "
        "DecisionConfigSection knobs must match their docs registries "
        "(Monitoring.md / Robustness.md)"
    )

    def run(self, ctx: AnalysisContext) -> Iterable:
        yield from self._check_naming(ctx)
        if ctx.docs_dir is None or not ctx.full_package:
            # doc cross-checks need the whole package in scope: a
            # single-file scan must not report the rest as ghosts
            return
        yield from self._check_monitoring_docs(ctx)
        yield from self._check_exporter_metrics(ctx)
        yield from self._check_event_catalog(ctx)
        yield from self._check_fault_catalog(ctx)
        yield from self._check_config_knobs(ctx)
        yield from self._check_rule_table(ctx)

    # -- naming convention (always on) ----------------------------------

    def _check_naming(self, ctx: AnalysisContext):
        for name, sf, line in collect_emitted_names(ctx):
            if (
                not NAME_RE.match(name)
                or name.split(".", 1)[0] not in ALLOWED_PREFIXES
            ):
                yield self.finding(
                    "counter-name",
                    sf,
                    line,
                    f"counter/histogram name '{name}' violates the "
                    f"<module>.<name> convention "
                    f"(allowed prefixes: docs/Monitoring.md)",
                )
        for name, sf, line in collect_histogram_names(ctx):
            if not name.endswith(("_ms", "_bytes")):
                yield self.finding(
                    "histogram-unit",
                    sf,
                    line,
                    f"histogram name '{name}' lacks a unit suffix "
                    f"(*_ms or *_bytes)",
                )

    # -- docs/Monitoring.md cross-check ---------------------------------

    def _check_monitoring_docs(self, ctx: AnalysisContext):
        doc = ctx.docs_dir / "Monitoring.md"
        if not doc.exists():
            return
        sf_doc = _doc_source(ctx, doc)
        text = doc.read_text()
        exact, prefixes = _string_universe(ctx)
        for name in sorted(_table_names(text)):
            if not _exists_in_code(name, exact, prefixes):
                yield self.finding(
                    "doc-ghost",
                    sf_doc,
                    _doc_line(text, name),
                    f"docs/Monitoring.md documents '{name}' but no code "
                    f"in the package emits it",
                )
        documented = _table_names(text)
        doc_exact = {n for n in documented if not n.endswith("*")}
        doc_stems = {n[:-1] for n in documented if n.endswith("*")}
        for name, sf, line in collect_histogram_names(ctx):
            if name in doc_exact or any(
                name.startswith(s) for s in doc_stems
            ):
                continue
            yield self.finding(
                "undocumented-histogram",
                sf,
                line,
                f"histogram '{name}' is emitted but missing from the "
                f"docs/Monitoring.md histogram table",
            )

    # -- docs/Monitoring.md exporter-metric table -----------------------

    def _check_exporter_metrics(self, ctx: AnalysisContext):
        """The exporter's own telemetry namespace (`monitor.*` — the
        scrape/push/rollup overhead metrics riding every exposition) is
        pinned to its docs/Monitoring.md table BOTH ways, exhaustively:
        an emitted `monitor.*` name missing a table row is an
        undocumented-metric, a row no code emits is a ghost-metric. The
        general counter table is exemplary by contract; this table is
        not — the exporter serves it to external scrapers, so drift here
        is operator-visible dashboard breakage."""
        doc = ctx.docs_dir / "Monitoring.md"
        if not doc.exists():
            return
        sf_doc = _doc_source(ctx, doc)
        text = doc.read_text()
        documented = _table_names(text, header_hint="exporter metric")
        doc_exact = {n for n in documented if not n.endswith("*")}
        doc_stems = {n[:-1] for n in documented if n.endswith("*")}
        emissions = {
            (name, sf.rel, line): (name, sf, line)
            for name, sf, line in (
                collect_emitted_names(ctx) + collect_histogram_names(ctx)
            )
            if name.startswith("monitor.")
        }
        emitted: Set[str] = set()
        for name, sf, line in emissions.values():
            emitted.add(name)
            if name in doc_exact or any(
                name.startswith(s) for s in doc_stems
            ):
                continue
            yield self.finding(
                "undocumented-metric",
                sf,
                line,
                f"exporter metric '{name}' is emitted but missing from "
                f"the docs/Monitoring.md exporter-metric table",
            )
        for name in sorted(documented):
            if name.endswith("*"):
                if any(e.startswith(name[:-1]) for e in emitted):
                    continue
            elif name in emitted:
                continue
            yield self.finding(
                "ghost-metric",
                sf_doc,
                _doc_line(text, name),
                f"docs/Monitoring.md exporter-metric table documents "
                f"'{name}' but no code emits it",
            )

    # -- docs/Monitoring.md LogSample event catalog ---------------------

    def _check_event_catalog(self, ctx: AnalysisContext):
        doc = ctx.docs_dir / "Monitoring.md"
        if not doc.exists():
            return
        sf_doc = _doc_source(ctx, doc)
        text = doc.read_text()
        documented = _event_table_names(text)
        code_events = collect_log_events(ctx)
        emitted = {name for name, _, _ in code_events}
        for name, sf, line in code_events:
            if not _event_documented(name, documented):
                yield self.finding(
                    "undocumented-event",
                    sf,
                    line,
                    f"LogSample event '{name}' is emitted but missing "
                    f"from the docs/Monitoring.md event catalog",
                )
        for name in sorted(documented):
            if name.endswith("*"):
                stem = name[:-1]
                if any(e.startswith(stem) for e in emitted):
                    continue
            elif name in emitted:
                continue
            yield self.finding(
                "ghost-event",
                sf_doc,
                _doc_line(text, name.rstrip("*")),
                f"docs/Monitoring.md catalogs LogSample event '{name}' "
                f"but no code emits it",
            )

    # -- docs/Robustness.md fault-point catalog -------------------------

    def _check_fault_catalog(self, ctx: AnalysisContext):
        doc = ctx.docs_dir / "Robustness.md"
        if not doc.exists():
            return
        sf_doc = _doc_source(ctx, doc)
        text = doc.read_text()
        doc_points = _table_names(text, header_hint="fault point")
        code_points = collect_fault_points(ctx)
        code_set = {name for name, _, _ in code_points}
        for name, sf, line in code_points:
            if name not in doc_points:
                yield self.finding(
                    "undocumented-fault-point",
                    sf,
                    line,
                    f"fault point '{name}' is declared in code but "
                    f"missing from the docs/Robustness.md catalog",
                )
        for name in sorted(doc_points - code_set):
            yield self.finding(
                "ghost-fault-point",
                sf_doc,
                _doc_line(text, name),
                f"docs/Robustness.md catalogs fault point '{name}' but "
                f"no fault_point(...) declares it",
            )

    # -- docs/Analysis.md rule catalog ----------------------------------

    def _check_rule_table(self, ctx: AnalysisContext):
        """The analysis suite's own registry: the docs/Analysis.md rule
        table and the RULES registry (= `--list-rules` output, which is
        generated from it) must match both ways — a rule family without a
        documented invariant is unreviewable, a documented family that no
        longer registers is a ghost."""
        doc = ctx.docs_dir / "Analysis.md"
        if not doc.exists():
            return
        sf_doc = _doc_source(ctx, doc)
        text = doc.read_text()
        documented: Set[str] = set()
        in_table = False
        for line in text.splitlines():
            s = line.strip()
            if not s.startswith("|"):
                in_table = False
                continue
            cells = [c.strip() for c in s.strip("|").split("|")]
            if not cells:
                continue
            low = cells[0].lower()
            if low == "rule":
                in_table = True
                continue
            if not in_table or set(cells[0]) <= {"-", " "}:
                continue
            m = re.match(r"^`([a-z][a-z0-9-]*)`$", cells[0])
            if m:
                documented.add(m.group(1))
        registered = set(RULES)
        for name in sorted(registered - documented):
            yield self.finding(
                "undocumented-rule",
                sf_doc,
                _doc_line(text, name),
                f"analysis rule '{name}' is registered but missing from "
                f"the docs/Analysis.md rule table",
            )
        for name in sorted(documented - registered):
            yield self.finding(
                "ghost-rule",
                sf_doc,
                _doc_line(text, name),
                f"docs/Analysis.md documents analysis rule '{name}' but "
                f"no such rule registers (see --list-rules)",
            )

    # -- DecisionConfigSection knobs ------------------------------------

    def _check_config_knobs(self, ctx: AnalysisContext):
        fields = _decision_config_fields(ctx)
        if not fields or ctx.docs_dir is None:
            return
        doc_text = "\n".join(
            p.read_text() for p in sorted(ctx.docs_dir.glob("*.md"))
        )
        for name, sf, line in fields:
            # documented bare, or via the --decision_<name> flag spelling
            pat = re.compile(
                r"(?<![A-Za-z0-9_])(?:decision_)?"
                + re.escape(name)
                + r"(?![A-Za-z0-9_])"
            )
            if not pat.search(doc_text):
                yield self.finding(
                    "undocumented-config-knob",
                    sf,
                    line,
                    f"DecisionConfigSection.{name} is not documented "
                    f"anywhere under docs/ (document the knob or the "
                    f"--decision_{name} flag)",
                )


def _doc_source(ctx: AnalysisContext, doc: Path) -> SourceFile:
    """A pseudo SourceFile for doc-anchored findings (suppression comments
    do not apply to docs; baseline entries do)."""
    try:
        rel = doc.relative_to(ctx.root).as_posix()
    except ValueError:
        rel = doc.as_posix()
    return SourceFile(
        path=doc, rel=rel, source="", tree=ast.parse(""), lines=[]
    )


def _doc_line(text: str, name: str) -> int:
    stem = name.rstrip("*")
    for i, line in enumerate(text.splitlines(), 1):
        if stem in line:
            return i
    return 1
