"""env-contract: the SKYTPU_* env surface is a documented registry.

Every ``SKYTPU_*`` variable READ through ``os.environ`` /
``os.getenv`` is public configuration surface — operators set them,
codegen snippets export them, tests monkeypatch them. The registry is
``docs/env_contract.md``; the check runs both directions:

- **read ⇒ documented**: every name read in-tree (resolved through
  constants — ``environ.get(ENV_ACCELERATOR)`` — and import aliasing
  — ``from os import environ as e``) has a registry row. Families
  built from a constant prefix (``f'SKYTPU_FLASH_BLOCK_{n}'``) need a
  glob row (``SKYTPU_FLASH_BLOCK_*``).
- **documented ⇒ used**: every registry row's name occurs as a string
  constant somewhere in ``skypilot_tpu/`` (glob rows need at least
  one matching constant) — a row nobody reads is dead contract.
"""
import ast
import re
from typing import Dict, Iterable, List, Tuple

from skypilot_tpu.analysis import core
from skypilot_tpu.analysis import docs_contract

DOC_NAME = 'env_contract.md'
_NAME_RE = re.compile(r'SKYTPU_[A-Z0-9_]+\Z')
_GLOB_RE = re.compile(r'SKYTPU_[A-Z0-9_]+\*\Z')
# environ.pop counts: it CONSUMES the variable (the recovery-stamp
# pattern) — operator-facing either way.
_READ_FUNCS = ('os.environ.get', 'os.getenv',
               'os.environ.setdefault', 'os.environ.pop')


class EnvContractChecker(core.Checker):
    rule = 'env-contract'
    description = ('Two-way check between SKYTPU_* env reads and the '
                   'docs/env_contract.md registry.')

    def check_repo(self, repo: 'core.RepoContext'
                   ) -> Iterable['core.Finding']:
        reads = _collect_reads(repo)
        if not reads:
            # Nothing relevant in the scan (fixture dir, single
            # out-of-tree file): no contract to check.
            return
        doc = docs_contract.read_doc(repo, DOC_NAME)
        if doc is None:
            yield docs_contract.missing_doc_finding(self.rule,
                                                    DOC_NAME)
            return
        documented = docs_contract.backticked(
            doc, r'SKYTPU_[A-Z0-9_]+\*?')
        exact = {d for d in documented if not d.endswith('*')}
        globs = sorted(d[:-1] for d in documented if d.endswith('*'))
        for name, (ctx, node) in sorted(reads.items()):
            if name.endswith('*'):
                if (name[:-1] + '*') in documented:
                    continue
                yield core.Finding(
                    self.rule, ctx.rel, node.lineno,
                    node.col_offset + 1,
                    f'env family `{name}` is read here (dynamic '
                    'suffix) but docs/env_contract.md has no '
                    f'matching `{name}` glob row')
            elif name not in exact and \
                    not any(name.startswith(g) for g in globs):
                yield core.Finding(
                    self.rule, ctx.rel, node.lineno,
                    node.col_offset + 1,
                    f'`{name}` is read from the environment here but '
                    'has no row in docs/env_contract.md — every '
                    'SKYTPU_* read is operator-facing contract')

        if repo.partial_package_scan:
            # Partial scan (a subdir of the package): every row
            # outside the slice would look stale.
            return
        used = _all_skytpu_constants(repo)
        for name in sorted(exact):
            if name not in used:
                yield core.Finding(
                    self.rule, f'docs/{DOC_NAME}', 1, 1,
                    f'`{name}` is documented in the env registry but '
                    'appears nowhere in skypilot_tpu/ — stale row '
                    '(remove it, or the consumer was deleted '
                    'without its contract)')
        for g in globs:
            # The prefix itself counts: a dynamic family read keeps
            # only the constant head in-tree (`f'SKYTPU_X_{n}'`).
            if not any(u.startswith(g) for u in used):
                yield core.Finding(
                    self.rule, f'docs/{DOC_NAME}', 1, 1,
                    f'glob row `{g}*` matches no SKYTPU_* constant '
                    'in-tree — stale family')


def _collect_reads(repo: 'core.RepoContext'
                   ) -> Dict[str, Tuple['core.FileContext', ast.AST]]:
    """{name-or-family: first (ctx, node)}; families end with '*'."""
    reads: Dict[str, Tuple['core.FileContext', ast.AST]] = {}

    def note(name: str, ctx, node):
        reads.setdefault(name, (ctx, node))

    for ctx in repo.files:
        helpers = _env_reader_helpers(ctx)
        for name, lineno in _enum_env_reads(ctx):
            note(name, ctx, _FakeNode(lineno))
        for node in ast.walk(ctx.tree):
            arg = None
            if isinstance(node, ast.Call):
                qual = ctx.call_name(node) or ''
                if qual in _READ_FUNCS:
                    if not node.args:
                        continue
                    arg = node.args[0]
                else:
                    # Same-module helper whose parameter flows into
                    # an environ read (`_env_int('SKYTPU_X', 9)`).
                    idx = helpers.get(qual.rsplit('.', 1)[-1])
                    if idx is None or len(node.args) <= idx:
                        continue
                    arg = node.args[idx]
            elif isinstance(node, ast.Subscript):
                if ctx.qualname(node.value) != 'os.environ':
                    continue
                # Plain subscript READS only: `os.environ[k] = v`
                # is a write (stamping), not consumer surface.
                par = ctx.parent(node)
                if isinstance(par, ast.Assign) and \
                        node in par.targets:
                    continue
                if isinstance(par, (ast.Delete,)):
                    continue
                arg = node.slice
            else:
                continue
            value = repo.resolve_constant(ctx, arg)
            if value is not None:
                if _NAME_RE.match(value):
                    note(value, ctx, node)
                continue
            prefix = ctx.joined_prefix(arg)
            if prefix and prefix.startswith('SKYTPU_'):
                note(prefix + '*', ctx, node)
    return reads


class _FakeNode:
    """Location shim for reads found outside a single AST node
    (enum-class env reads attach to the member assignment line)."""

    def __init__(self, lineno: int):
        self.lineno = lineno
        self.col_offset = 0


def _env_reader_helpers(ctx: 'core.FileContext') -> Dict[str, int]:
    """{function name: param index} for same-module helpers whose
    parameter flows into an environ read — calls to them with a
    literal name are env reads at the call site (`_env_int`,
    `_env_override` style)."""
    out: Dict[str, int] = {}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            continue
        params = [a.arg for a in
                  node.args.posonlyargs + node.args.args]
        for call in ast.walk(node):
            if isinstance(call, ast.Call) and \
                    (ctx.call_name(call) or '') in _READ_FUNCS and \
                    call.args and \
                    isinstance(call.args[0], ast.Name) and \
                    call.args[0].id in params:
                idx = params.index(call.args[0].id)
                # Methods are CALLED without their self/cls slot, so
                # the call-site index shifts down one.
                if params and params[0] in ('self', 'cls'):
                    idx -= 1
                if idx >= 0:
                    out[node.name] = idx
    return out


def _enum_env_reads(ctx: 'core.FileContext'
                    ) -> List[Tuple[str, int]]:
    """The ``env_options.Options`` pattern: an enum class whose
    method reads ``os.environ[...self.value...]`` — every SKYTPU_*
    member value is an env read."""
    out: List[Tuple[str, int]] = []
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        reads_self_value = False
        for call in ast.walk(cls):
            if isinstance(call, ast.Call) and \
                    (ctx.call_name(call) or '') in _READ_FUNCS and \
                    call.args and \
                    ctx.qualname(call.args[0]) == 'self.value':
                reads_self_value = True
                break
        if not reads_self_value:
            continue
        for stmt in cls.body:
            if isinstance(stmt, ast.Assign) and \
                    isinstance(stmt.value, ast.Constant) and \
                    isinstance(stmt.value.value, str) and \
                    _NAME_RE.match(stmt.value.value):
                out.append((stmt.value.value, stmt.lineno))
    return out


def _all_skytpu_constants(repo: 'core.RepoContext') -> List[str]:
    """SKYTPU_* names appearing in STRING CONSTANTS (f-strings
    flattened, docstrings excluded) — not raw file text, so a name
    surviving only in a comment or docstring ('keep in sync with
    SKYTPU_FOO') cannot keep a stale registry row green."""
    out = set()
    rx = re.compile(r'SKYTPU_[A-Z0-9_]+')
    for ctx in repo.files:
        for _node, text in ctx.sql_strings():
            out.update(rx.findall(text))
    return sorted(out)
