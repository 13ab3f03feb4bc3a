"""The four stable-name contracts as AST checkers.

Span names, metric names, alert-rule ids, and fault-site ids are
stable API: dashboards query them, runbooks link them, ``xsky``
subcommands filter on them. Each contract pairs construction sites
in code with a documentation table, checked both directions where
the doc side is a curated table. These started life as four grep
lints in the test suite (tests/test_trace.py,
tests/test_resilience.py); the AST rebuild sees multi-line calls and
aliased imports the regexes missed, and all four share ONE doc-table
parser (:mod:`~skypilot_tpu.analysis.docs_contract`) so format drift
breaks loudly in one place.

The collection helpers (``collect_span_names`` etc.) are public: the
migrated test classes keep their regex-rot meta-checks by asserting
the *checker* still sees the long-standing emission sites.
"""
import ast
import os
import re
from typing import Dict, Iterable, Optional, Tuple

from skypilot_tpu.analysis import core
from skypilot_tpu.analysis import docs_contract

OBS_DOC = 'observability.md'
RES_DOC = 'resilience.md'

# ``.phase`` names (loop phases on the profiler's clock) share the
# span-name contract; they are documented in their own sub-table,
# which is also checked in the reverse direction.
_SPAN_FUNCS_SUFFIX = ('.span', '.record_span', '.emit_span', '._span',
                      '.phase')
_SPAN_FUNCS_BARE = ('record_span', 'emit_span')
# ``jax_runtime.stage('engine.build')`` (context manager or
# decorator) emits the span ``startup.engine.build``.
_STAGE_FUNC_SUFFIX = 'jax_runtime.stage'
_STAGE_SPAN_PREFIX = 'startup.'
_SPAN_NAME_RE = re.compile(r'[a-z0-9_.]+\Z')
_METRIC_NAME_RE = re.compile(r'skytpu_[a-z0-9_]+\Z')
_METRIC_KINDS = ('counter', 'gauge', 'histogram')
_RULE_ID_RE = re.compile(r'[a-z0-9]+(?:-[a-z0-9]+)+\Z')
CC_METRIC_RE = re.compile(
    r'AppendMetric\(&out,\s*"(skytpu_[a-z0-9_]+)"')
_FAULT_SITE_RE = re.compile(r'[a-z]+\.[a-z_]+\Z')


# -- collection (shared with the migrated test meta-checks) -----------

def _span_literal(ctx: 'core.FileContext',
                  call: ast.Call) -> Optional[Tuple[str, ast.AST]]:
    qual = ctx.call_name(call) or ''
    if qual.endswith(_STAGE_FUNC_SUFFIX):
        val = ctx.string_value(call.args[0]) if call.args else None
        if val and _SPAN_NAME_RE.match(val):
            return _STAGE_SPAN_PREFIX + val, call.args[0]
        return None
    is_span_call = (any(qual.endswith(s) for s in _SPAN_FUNCS_SUFFIX)
                    or qual in _SPAN_FUNCS_BARE)
    if not is_span_call:
        return None
    if qual.endswith('.emit_span') or qual == 'emit_span':
        # emit_span(ctx, parent, 'name', ...): the name is the first
        # dotted-lowercase string literal among the positionals.
        for arg in call.args:
            val = ctx.string_value(arg)
            if val and _SPAN_NAME_RE.match(val) and '.' in val:
                return val, arg
        return None
    if call.args:
        val = ctx.string_value(call.args[0])
        if val and _SPAN_NAME_RE.match(val):
            return val, call.args[0]
    return None


def collect_span_names(repo: 'core.RepoContext'
                       ) -> Dict[str, Tuple[str, int]]:
    """{span name: (rel path, line)} for every LITERAL span name
    emitted in the scanned tree."""
    out: Dict[str, Tuple[str, int]] = {}
    for ctx in repo.files:
        for call in ctx.calls():
            hit = _span_literal(ctx, call)
            if hit:
                out.setdefault(hit[0], (ctx.rel, call.lineno))
    return out


def collect_metric_names(repo: 'core.RepoContext'
                         ) -> Dict[str, Tuple[str, int]]:
    """Metric-name construction sites: registry calls
    (``reg.counter('skytpu_x', ...)``), the py agent's hand-rendered
    sample tuples ``('skytpu_x', 'gauge', ...)``, and — regex
    fallback, ast can't parse C++ — ``AppendMetric(&out, "skytpu_x"``
    in the C++ host agent."""
    out: Dict[str, Tuple[str, int]] = {}
    for ctx in repo.files:
        for call in ctx.calls():
            # Any `<expr>.counter/gauge/histogram('skytpu_x', ...)`
            # — the receiver is often a chained call
            # (`registry().counter(...)`), which a dotted-name
            # resolution can't see, so match on the attribute alone
            # and let the skytpu_ name shape disambiguate.
            if isinstance(call.func, ast.Attribute) and \
                    call.func.attr in _METRIC_KINDS and call.args:
                val = ctx.string_value(call.args[0])
                if val and _METRIC_NAME_RE.match(val):
                    out.setdefault(val, (ctx.rel, call.lineno))
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Tuple) and len(node.elts) >= 2:
                name = ctx.string_value(node.elts[0])
                kind = ctx.string_value(node.elts[1])
                if name and kind in _METRIC_KINDS and \
                        _METRIC_NAME_RE.match(name):
                    out.setdefault(name, (ctx.rel, node.lineno))
    for rel, text in _cc_sources(repo):
        for m in CC_METRIC_RE.finditer(text):
            line = text[:m.start()].count('\n') + 1
            out.setdefault(m.group(1), (rel, line))
    return out


def _cc_sources(repo: 'core.RepoContext'
                ) -> Iterable[Tuple[str, str]]:
    root = repo.package_root
    if not root:
        return
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != '__pycache__']
        for fn in files:
            if fn.endswith('.cc'):
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, root).replace(os.sep, '/')
                with open(path, encoding='utf-8') as f:
                    yield rel, f.read()


def collect_alert_rule_ids(repo: 'core.RepoContext'
                           ) -> Dict[str, Tuple[str, int]]:
    """{rule id: (rel path, line)} for every ``AlertRule(id='...')``
    construction."""
    out: Dict[str, Tuple[str, int]] = {}
    for ctx in repo.files:
        for call in ctx.calls():
            qual = ctx.call_name(call) or ''
            if not qual.endswith('AlertRule'):
                continue
            for kw in call.keywords:
                if kw.arg == 'id':
                    val = ctx.string_value(kw.value)
                    if val and _RULE_ID_RE.match(val):
                        out.setdefault(val, (ctx.rel, call.lineno))
    return out


def collect_fault_sites(repo: 'core.RepoContext'
                        ) -> Dict[str, Tuple[str, int]]:
    """The ``SITES`` tuple in resilience/faults.py, read statically
    (the lint must not import the module under test)."""
    out: Dict[str, Tuple[str, int]] = {}
    for ctx in repo.files:
        if not ctx.rel.endswith('resilience/faults.py'):
            continue
        for stmt in ctx.tree.body:
            if not (isinstance(stmt, ast.Assign)
                    and any(isinstance(t, ast.Name)
                            and t.id == 'SITES'
                            for t in stmt.targets)):
                continue
            if isinstance(stmt.value, (ast.Tuple, ast.List)):
                for elt in stmt.value.elts:
                    val = ctx.string_value(elt)
                    if val:
                        out.setdefault(val, (ctx.rel, elt.lineno))
    return out


# -- checkers ---------------------------------------------------------

class SpanNameContractChecker(core.Checker):
    rule = 'span-name-contract'
    description = ('Every literal span name emitted in-tree is '
                   'backticked in docs/observability.md; the loop '
                   "phases' sub-table is checked both ways.")

    PHASE_SECTION = "### On the profiler's clock"

    def check_repo(self, repo: 'core.RepoContext'
                   ) -> Iterable['core.Finding']:
        emitted = collect_span_names(repo)
        if not emitted:
            # Nothing relevant in the scan (fixture dir, single
            # out-of-tree file): no contract to check.
            return
        doc = docs_contract.read_doc(repo, OBS_DOC)
        if doc is None:
            yield docs_contract.missing_doc_finding(self.rule,
                                                    OBS_DOC)
            return
        for name, (rel, line) in sorted(emitted.items()):
            if f'`{name}`' not in doc:
                yield core.Finding(
                    self.rule, rel, line, 1,
                    f'span name `{name}` is emitted here but missing '
                    'from the docs/observability.md span-name '
                    'contract table — span names are stable API '
                    'exactly like metric names')
        if repo.partial_package_scan:
            # Partial scan: skip the documented⇒emitted direction
            # (see MetricNameContractChecker).
            return
        sect = docs_contract.section(doc, self.PHASE_SECTION)
        if sect is None:
            # No such sub-table (a fixture, or a doc that documents
            # no loop phase): nothing to hold against the code.
            return
        documented = docs_contract.table_col0(
            sect, r'[a-z0-9_]+\.[a-z0-9_.]+')
        for name in sorted(documented - set(emitted)):
            yield core.Finding(
                self.rule, f'docs/{OBS_DOC}', 1, 1,
                f'loop phase `{name}` is documented in the '
                f'"{self.PHASE_SECTION}" table but emitted nowhere '
                'in skypilot_tpu/ — stale contract row')


class MetricNameContractChecker(core.Checker):
    rule = 'metric-name-contract'
    description = ('Two-way check between constructed skytpu_* '
                   'metric names and docs/observability.md.')

    def check_repo(self, repo: 'core.RepoContext'
                   ) -> Iterable['core.Finding']:
        constructed = collect_metric_names(repo)
        if not constructed:
            return  # nothing relevant in the scan
        doc = docs_contract.read_doc(repo, OBS_DOC)
        if doc is None:
            yield docs_contract.missing_doc_finding(self.rule,
                                                    OBS_DOC)
            return
        for name, (rel, line) in sorted(constructed.items()):
            if f'`{name}`' not in doc:
                yield core.Finding(
                    self.rule, rel, line, 1,
                    f'metric `{name}` is constructed here but missing '
                    'from the docs/observability.md contract tables')
        if repo.partial_package_scan:
            # Partial scan (a subdir of the package): the reverse
            # direction would call every doc row outside the slice
            # stale. Whole-tree runs check both directions.
            return
        documented = docs_contract.backticked(doc,
                                              r'skytpu_[a-z0-9_]+')
        for name in sorted(documented - set(constructed)):
            yield core.Finding(
                self.rule, f'docs/{OBS_DOC}', 1, 1,
                f'metric `{name}` is documented but constructed '
                'nowhere in skypilot_tpu/ — stale contract row')


class AlertRuleContractChecker(core.Checker):
    rule = 'alert-rule-contract'
    description = ('Two-way check between AlertRule(id=...) '
                   'constructions and the Built-in rules table.')

    SECTION = '### Built-in rules'

    def check_repo(self, repo: 'core.RepoContext'
                   ) -> Iterable['core.Finding']:
        constructed = collect_alert_rule_ids(repo)
        if not constructed:
            return  # nothing relevant in the scan
        doc = docs_contract.read_doc(repo, OBS_DOC)
        if doc is None:
            yield docs_contract.missing_doc_finding(self.rule,
                                                    OBS_DOC)
            return
        for rule_id, (rel, line) in sorted(constructed.items()):
            if f'`{rule_id}`' not in doc:
                yield core.Finding(
                    self.rule, rel, line, 1,
                    f'alert rule id `{rule_id}` is constructed here '
                    'but missing from docs/observability.md')
        if repo.partial_package_scan:
            # Partial scan: skip the documented⇒constructed
            # direction (see MetricNameContractChecker).
            return
        sect = docs_contract.section(doc, self.SECTION)
        if sect is None:
            yield core.Finding(
                self.rule, f'docs/{OBS_DOC}', 1, 1,
                f'docs/observability.md lost its "{self.SECTION}" '
                'section — the documented⇒constructed direction '
                'cannot be checked')
            return
        documented = docs_contract.backticked(
            sect, r'[a-z0-9]+(?:-[a-z0-9]+)+')
        for rule_id in sorted(documented - set(constructed)):
            yield core.Finding(
                self.rule, f'docs/{OBS_DOC}', 1, 1,
                f'alert rule id `{rule_id}` is documented in the '
                'Built-in rules table but constructed nowhere')


class FaultSiteContractChecker(core.Checker):
    rule = 'fault-site-contract'
    description = ('Two-way check between faults.SITES and the '
                   'docs/resilience.md fault-site table.')

    SECTION = '## Fault injection'

    def check_repo(self, repo: 'core.RepoContext'
                   ) -> Iterable['core.Finding']:
        registered = collect_fault_sites(repo)
        if not registered:
            # Scan did not include resilience/faults.py (e.g. a
            # fixture dir): nothing to check.
            return
        doc = docs_contract.read_doc(repo, RES_DOC)
        sect = docs_contract.section(doc, self.SECTION) \
            if doc is not None else None
        if sect is None:
            yield docs_contract.missing_doc_finding(self.rule,
                                                    RES_DOC)
            return
        documented = docs_contract.table_col0(
            sect, r'[a-z]+\.[a-z_]+')
        for site, (rel, line) in sorted(registered.items()):
            if site not in documented:
                yield core.Finding(
                    self.rule, rel, line, 1,
                    f'fault site `{site}` is registered in '
                    'faults.SITES but missing from the '
                    'docs/resilience.md fault-site table — an '
                    'undocumented site is undrillable')
        for site in sorted(documented - set(registered)):
            yield core.Finding(
                self.rule, f'docs/{RES_DOC}', 1, 1,
                f'fault site `{site}` is documented but not '
                'registered in faults.SITES — a chaos drill against '
                'it silently no-ops')
