"""serve-jit-prng: randomness in the serve plane's compiled steps
comes ONLY from ``ops/sampling/``.

The sampling subsystem's batch-invariance contract (docs/sampling.md)
holds because every draw is keyed by ``(request_seed, absolute
position)`` through ``ops/sampling/prng.row_key`` — a pure function
of the request, never of the batch. Any other PRNG construction
inside a jitted serve step reintroduces exactly the failure modes the
subsystem removed: a ``jax.random.PRNGKey``/``split`` chain advances
with the number of draws (so output depends on batch width and
dispatch history), and host RNG (``random``, ``numpy.random``,
``os.urandom``, ``secrets``) inside a trace runs ONCE at trace time —
every subsequent step silently reuses the first draw.

Scope: ``serve/``, and the paged engine's model steps in
``models/decode.py`` (``_STEP_ROOTS``: the scheduler jits them from
``serve/batching.py``, and this checker sees one file at a time, so
they are roots by name). ``ops/sampling/`` is the one package allowed
to build counter-based keys and is outside the scope. Like
blocking-in-jit, the checker finds jit roots (decorator,
``partial(jax.jit, ...)``, and ``jax.jit(fn)`` call forms) and walks
the same-module call graph to a fixpoint, so a jitted step that
reaches randomness through a local helper is still caught.
"""
import ast
from typing import Dict, Iterable, List, Set, Tuple

from skypilot_tpu.analysis import core

_SCOPE = 'serve/'
_STEP_FILE = 'models/decode.py'
_STEP_ROOTS = ('forward_paged', 'decode_steps_paged',
               'verify_step_paged')
_JIT_NAMES = ('jax.jit', 'jax.experimental.shard_map.shard_map')
_JIT_SUFFIXES = ('.shard_map',)

_RNG_EXACT = {'os.urandom'}
_RNG_PREFIXES = (
    'jax.random.', 'numpy.random.', 'np.random.', 'random.',
    'secrets.',
)


def _is_jit_ref(qual: str) -> bool:
    return qual in _JIT_NAMES or \
        any(qual.endswith(s) for s in _JIT_SUFFIXES) or \
        qual == 'shard_map'


def _is_rng(qual: str) -> bool:
    return qual in _RNG_EXACT or \
        any(qual.startswith(p) for p in _RNG_PREFIXES)


class ServeJitPrngChecker(core.Checker):
    rule = 'serve-jit-prng'
    description = ('PRNG construction (jax.random.*, host RNG) '
                   'reachable inside jitted serve-plane steps outside '
                   'ops/sampling/ — randomness there must flow '
                   'through the counter-based (seed, position) keys '
                   'or batch invariance breaks.')

    def check_file(self, ctx: 'core.FileContext'
                   ) -> Iterable['core.Finding']:
        steps = ctx.rel.endswith(_STEP_FILE)
        in_scope = ctx.rel.startswith(_SCOPE) or f'/{_SCOPE}' in ctx.rel
        if not in_scope and not steps:
            return
        funcs: Dict[str, ast.AST] = {
            node.name: node for node in ast.walk(ctx.tree)
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef))}
        if steps:
            roots = [(funcs[name], f'{name}, jitted by the engine')
                     for name in _STEP_ROOTS if name in funcs]
        else:
            roots = self._jit_roots(ctx, funcs)
        if not roots:
            return
        graph: Dict[str, Set[str]] = {}
        for name, node in funcs.items():
            graph[name] = {
                (ctx.call_name(c) or '')
                for c in ast.walk(node) if isinstance(c, ast.Call)
            } & set(funcs)
        for root_node, via in roots:
            yield from self._scan(ctx, root_node, via, funcs, graph)

    def _jit_roots(self, ctx, funcs
                   ) -> List[Tuple[ast.AST, str]]:
        """(function-or-lambda node, description of the jit site)."""
        roots: List[Tuple[ast.AST, str]] = []
        seen: Set[int] = set()

        def add(node, via):
            if node is not None and id(node) not in seen:
                seen.add(id(node))
                roots.append((node, via))

        for name, node in funcs.items():
            for dec in node.decorator_list:
                qual = ctx.qualname(dec)
                if qual and _is_jit_ref(qual):
                    add(node, f'@{qual} on {name}')
                if isinstance(dec, ast.Call):
                    dec_qual = ctx.call_name(dec) or ''
                    if _is_jit_ref(dec_qual):
                        add(node, f'@{dec_qual} on {name}')
                    elif dec_qual.endswith('partial') and dec.args:
                        inner = ctx.qualname(dec.args[0])
                        if inner and _is_jit_ref(inner):
                            add(node, f'@partial({inner}) on {name}')
        for call in ctx.calls():
            qual = ctx.call_name(call) or ''
            if not _is_jit_ref(qual):
                continue
            if not call.args:
                continue
            target = call.args[0]
            if isinstance(target, ast.Lambda):
                add(target, f'lambda passed to {qual} at line '
                            f'{call.lineno}')
            elif isinstance(target, ast.Name) and \
                    target.id in funcs:
                add(funcs[target.id],
                    f'{target.id} passed to {qual}')
            elif isinstance(target, ast.Call):
                # jax.jit(functools.partial(fn, ...)) — unwrap.
                inner_qual = ctx.call_name(target) or ''
                if inner_qual.endswith('partial') and target.args and \
                        isinstance(target.args[0], ast.Name) and \
                        target.args[0].id in funcs:
                    add(funcs[target.args[0].id],
                        f'partial({target.args[0].id}) passed to '
                        f'{qual}')
        return roots

    def _scan(self, ctx, root, via, funcs, graph
              ) -> Iterable['core.Finding']:
        frontier = [root]
        if isinstance(root, (ast.FunctionDef, ast.AsyncFunctionDef)):
            reachable = self._closure(root.name, graph)
            frontier += [funcs[n] for n in reachable
                         if n in funcs and funcs[n] is not root]
        for node in frontier:
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                qual = ctx.call_name(call) or ''
                if _is_rng(qual):
                    yield core.Finding(
                        self.rule, ctx.rel, call.lineno,
                        call.col_offset + 1,
                        f'{qual}() is reachable inside a jitted '
                        f'serve step ({via}) — serve-plane '
                        'randomness must come from ops/sampling/ '
                        'counter-based (seed, position) keys; a key '
                        'chain or host RNG here breaks batch '
                        'invariance')

    @staticmethod
    def _closure(name: str, graph: Dict[str, Set[str]]) -> Set[str]:
        seen: Set[str] = set()
        stack = [name]
        while stack:
            cur = stack.pop()
            for callee in graph.get(cur, ()):
                if callee not in seen:
                    seen.add(callee)
                    stack.append(callee)
        return seen
