"""skylint suite tests (skypilot_tpu/analysis/).

Four layers:

1. the tier-1 invariant — the full suite over ``skypilot_tpu/``
   reports ZERO unsuppressed findings (the acceptance gate);
2. seeded-violation fixtures — every registered rule demonstrably
   FIRES on a minimal violation (a rule that can't fire is worse
   than no rule: it certifies invariants it doesn't check);
3. framework behavior — suppression syntax (justification required,
   unknown ids rejected), JSON schema stability, import-alias /
   parent-link resolution on tricky shapes;
4. meta — every rule id has a fixture here AND a row in
   docs/static_analysis.md's rule table (the doc-contract two-way
   check applied to the linter itself).
"""
import json
import os
import textwrap

import pytest

import skypilot_tpu
from skypilot_tpu import analysis
from skypilot_tpu.analysis import core as a_core
from skypilot_tpu.analysis import docs_contract

PKG_DIR = os.path.dirname(skypilot_tpu.__file__)
REPO_ROOT = os.path.dirname(PKG_DIR)


def _write_fixture(tmp_path, files, docs=None):
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src), encoding='utf-8')
    docs_dir = tmp_path / 'docs'
    docs_dir.mkdir(exist_ok=True)
    for rel, src in (docs or {}).items():
        (docs_dir / rel).write_text(textwrap.dedent(src),
                                    encoding='utf-8')
    return str(tmp_path), str(docs_dir)


def run_fixture(tmp_path, rule, files, docs=None):
    root, docs_dir = _write_fixture(tmp_path, files, docs)
    return analysis.run([root], rules=[rule], docs_dir=docs_dir)


# ---------------------------------------------------------------------
# 1. The tree is clean.
# ---------------------------------------------------------------------


class TestTreeIsClean:

    def test_zero_unsuppressed_findings(self):
        findings = analysis.run([PKG_DIR])
        assert not findings, (
            'skylint found unsuppressed violations in-tree — fix '
            'them or add a justified `# skylint: disable=`:\n'
            + '\n'.join(f.render() for f in findings))

    def test_module_entry_exits_zero_on_clean_tree(self):
        from skypilot_tpu.analysis import __main__ as main_mod
        assert main_mod.main([PKG_DIR]) == 0

    def test_empty_scan_is_an_error_not_clean(self, tmp_path,
                                              capsys):
        """A gate that scanned nothing must not certify the tree: a
        typo'd path (or wrong cwd) errors instead of exiting 0."""
        from skypilot_tpu.analysis import __main__ as main_mod
        with pytest.raises(ValueError, match='no Python files'):
            analysis.run([str(tmp_path / 'nope')])
        assert main_mod.main([str(tmp_path / 'nope')]) == 2
        assert 'no Python files' in capsys.readouterr().err

    def test_partial_package_scan_skips_reverse_directions(self):
        """`xsky lint skypilot_tpu/analysis` must not call every doc
        row stale just because the slice constructs nothing — the
        documented⇒constructed directions are whole-repo statements
        and skip on partial scans."""
        findings = analysis.run(
            [os.path.join(PKG_DIR, 'analysis')])
        assert not findings, '\n'.join(f.render() for f in findings)

    def test_module_entry_exits_nonzero_on_findings(self, tmp_path,
                                                    capsys):
        from skypilot_tpu.analysis import __main__ as main_mod
        bad = tmp_path / 'bad.py'
        bad.write_text('import threading\n'
                       't = threading.Thread(target=print)\n')
        rc = main_mod.main([str(tmp_path), '--rule', 'naked-thread'])
        assert rc == 1
        assert 'naked-thread' in capsys.readouterr().out


# ---------------------------------------------------------------------
# 2. Seeded violations: every rule fires.
# ---------------------------------------------------------------------

# {rule: (files, docs)} — the minimal in-fixture violation for each
# registered rule. The meta-test below asserts this dict covers the
# whole registry, so adding a checker without a fixture fails CI.
FIXTURES = {
    'unfenced-state-write': ({
        'sneak.py': '''
            def sneak(conn, name):
                conn.execute(
                    "UPDATE services SET status=? WHERE name=?",
                    ('DOWN', name))
        ''',
    }, None),
    'raw-sqlite-outside-state-engine': ({
        'rogue_store.py': '''
            import sqlite3
            from skypilot_tpu.utils import db_utils

            def open_store(path):
                conn = sqlite3.connect(path, timeout=5)
                return db_utils.SQLiteConn(path, lambda c, k: None)
        ''',
    }, None),
    'non-atomic-write': ({
        'torn.py': '''
            import json, os
            def save(meta):
                base = os.path.expanduser(os.environ.get(
                    'SKYTPU_STATE_DIR', '~/.skypilot_tpu'))
                path = os.path.join(base, 'thing.json')
                with open(path, 'w', encoding='utf-8') as f:
                    json.dump(meta, f)
        ''',
    }, None),
    'sleep-in-retry': ({
        'loop.py': '''
            import time
            def fetch(url, do):
                for attempt in range(5):
                    try:
                        return do(url)
                    except OSError:
                        time.sleep(2 ** attempt)
        ''',
    }, None),
    'spawn-without-stamp': ({
        'spawn.py': '''
            import subprocess
            def spawn(cmd):
                env = {'PATH': '/usr/bin'}
                return subprocess.Popen(cmd, env=env)
        ''',
    }, None),
    'env-contract': ({
        'reader.py': '''
            import os
            def f():
                return os.environ.get('SKYTPU_TOTALLY_UNDOCUMENTED')
        ''',
    }, {'env_contract.md': '# empty registry\n'}),
    'blocking-in-jit': ({
        # Scope-gated: the violation must live under ops/ — and it
        # hides behind a local helper, which is the whole point of
        # the call-graph pass.
        'ops/kernel.py': '''
            import jax
            def _log(x):
                with open('/tmp/x', 'w') as f:
                    f.write(str(x))
            def step(x):
                _log(x)
                return x * 2
            step_fn = jax.jit(step)
        ''',
    }, None),
    'serve-jit-prng': ({
        # Scope-gated: serve/ — a jitted decode step that builds
        # its own key chain, hidden behind a local helper (the
        # call-graph pass catches it).
        'serve/rogue_engine.py': '''
            import jax
            def _draw(logits, step):
                key = jax.random.PRNGKey(step)
                return jax.random.categorical(key, logits)
            def step(logits, step_idx):
                return _draw(logits, step_idx)
            step_fn = jax.jit(step)
        ''',
    }, None),
    'naked-thread': ({
        'threads.py': '''
            import threading
            def start():
                t = threading.Thread(target=print)
                t.start()
        ''',
    }, None),
    'span-name-contract': ({
        'emit.py': '''
            from skypilot_tpu import trace as trace_lib
            def f():
                with trace_lib.span('secret.span'):
                    pass
        ''',
    }, {'observability.md': '# obs\nno spans documented\n'}),
    'metric-name-contract': ({
        'emit.py': '''
            def f(reg):
                reg.counter('skytpu_undocumented_total', 'x')
        ''',
    }, {'observability.md': '# obs\n`skytpu_ghost_metric` only\n'}),
    'alert-rule-contract': ({
        'emit.py': '''
            from skypilot_tpu.alerts.rules import AlertRule
            r = AlertRule(id='undocumented-rule')
        ''',
    }, {'observability.md':
        '# obs\n### Built-in rules\n| `ghost-rule` | x |\n\n## end\n'}),
    'fault-site-contract': ({
        'resilience/faults.py':
            "SITES = ('real.site', 'undocumented.site')\n",
    }, {'resilience.md':
        '# res\n## Fault injection\n| `real.site` | x |\n'
        '| `ghost.site` | x |\n\n## end\n'}),
    'urlopen-without-timeout': ({
        'client.py': '''
            import urllib.request
            def fetch(url):
                with urllib.request.urlopen(url) as resp:
                    return resp.read()
        ''',
    }, None),
    'suppression': ({
        'bare.py': '''
            import threading
            t = threading.Thread(target=print)  # skylint: disable=naked-thread
        ''',
    }, None),
}


class TestSeededViolations:

    @pytest.mark.parametrize('rule', sorted(FIXTURES))
    def test_rule_fires_on_seeded_violation(self, tmp_path, rule):
        files, docs = FIXTURES[rule]
        run_rule = 'naked-thread' if rule == 'suppression' else rule
        findings = run_fixture(tmp_path, run_rule, files, docs)
        assert any(f.rule == rule for f in findings), (
            f'{rule} did not fire on its seeded violation — the '
            f'rule is vacuous. Findings: '
            f'{[f.render() for f in findings]}')

    def test_two_way_contracts_fire_both_directions(self, tmp_path):
        """Each doc-backed contract reports BOTH code-not-documented
        and documented-not-in-code (the drift can't hide in either
        direction)."""
        for rule, ghost in (('metric-name-contract',
                             'skytpu_ghost_metric'),
                            ('alert-rule-contract', 'ghost-rule'),
                            ('fault-site-contract', 'ghost.site')):
            files, docs = FIXTURES[rule]
            findings = run_fixture(tmp_path / rule.replace('-', '_'),
                                   rule, files, docs)
            messages = ' | '.join(f.message for f in findings)
            assert ghost in messages, (rule, messages)
            assert len(findings) >= 2, (rule, messages)


    def test_serve_prng_reaches_the_steps_below_the_scheduler(
            self, tmp_path):
        """The paged engine's model steps live in models/decode.py
        and are jitted from serve/batching.py: the rule takes them
        as roots by name, and leaves that file's other jitted
        functions (the dense sampler) alone."""
        files = {'models/decode.py': '''
            import jax
            def _draw(logits, seed):
                return jax.random.categorical(
                    jax.random.PRNGKey(seed), logits)
            def decode_steps_paged(logits, seed):
                return _draw(logits, seed)
            def sample_tokens_scan(logits, key):
                return jax.random.split(key)
            scan_fn = jax.jit(sample_tokens_scan)
        '''}
        findings = run_fixture(tmp_path, 'serve-jit-prng', files, None)
        assert findings and all(
            'decode_steps_paged' in f.message for f in findings), [
                f.render() for f in findings]

    def test_loop_phase_names_are_held_both_ways(self, tmp_path):
        """``trace.phase`` names (loop phases on the profiler's
        clock) share the span-name contract: an emitted phase must be
        documented, and a row of the phases' own sub-table must be
        emitted somewhere."""
        files = {'loop.py': '''
            from skypilot_tpu import trace as trace_lib
            def f():
                with trace_lib.phase('engine.secret', rows=1):
                    with trace_lib.phase('engine.known'):
                        pass
        '''}
        docs = {'observability.md': (
            "# obs\n## Span-name contract\n| `launch` | x |\n"
            "### On the profiler's clock (`trace.phase`)\n"
            "| Span | Around |\n|---|---|\n"
            "| `engine.known` | x |\n| `engine.ghost` | x |\n"
            "\n# Bench gate\n| `bench.ghost` | not in the table |\n")}
        findings = run_fixture(tmp_path, 'span-name-contract', files,
                               docs)
        messages = ' | '.join(f.message for f in findings)
        assert len(findings) == 2, messages
        assert 'engine.secret' in messages
        assert 'engine.ghost' in messages
        assert 'engine.known' not in messages
        assert 'bench.ghost' not in messages


# ---------------------------------------------------------------------
# 3a. Suppression syntax.
# ---------------------------------------------------------------------


class TestSuppression:

    BAD_THREAD = ('import threading\n'
                  't = threading.Thread(target=print)')

    def _run(self, tmp_path, body):
        (tmp_path / 'f.py').write_text(body + '\n')
        return analysis.run([str(tmp_path)], rules=['naked-thread'])

    def test_justified_disable_suppresses(self, tmp_path):
        findings = self._run(
            tmp_path, self.BAD_THREAD +
            '  # skylint: disable=naked-thread — joined in caller')
        assert findings == []

    def test_disable_on_line_above_suppresses(self, tmp_path):
        findings = self._run(
            tmp_path,
            'import threading\n'
            '# skylint: disable=naked-thread — harness-only thread\n'
            't = threading.Thread(target=print)')
        assert findings == []

    def test_bare_disable_is_itself_a_finding(self, tmp_path):
        findings = self._run(
            tmp_path,
            self.BAD_THREAD + '  # skylint: disable=naked-thread')
        rules = sorted(f.rule for f in findings)
        # The original finding is NOT suppressed and the bad disable
        # is reported on top.
        assert rules == ['naked-thread', 'suppression']

    def test_unknown_rule_in_disable_is_a_finding(self, tmp_path):
        findings = self._run(
            tmp_path, self.BAD_THREAD +
            '  # skylint: disable=nakedd-thread — justified typo')
        rules = sorted(f.rule for f in findings)
        assert rules == ['naked-thread', 'suppression']
        assert 'unknown rule' in [
            f for f in findings if f.rule == 'suppression'
        ][0].message

    def test_disable_for_other_rule_does_not_suppress(self, tmp_path):
        findings = self._run(
            tmp_path, self.BAD_THREAD +
            '  # skylint: disable=sleep-in-retry — wrong rule')
        assert [f.rule for f in findings] == ['naked-thread']

    def test_directive_inside_string_literal_is_ignored(self,
                                                        tmp_path):
        """A `# skylint: disable=` shown inside a docstring or
        string (syntax documentation, generated snippets) is neither
        a directive nor a bad one — only real COMMENT tokens
        count."""
        (tmp_path / 'f.py').write_text(
            '"""Example:\n'
            '    # skylint: disable=naked-thread\n'
            '"""\n'
            "SNIPPET = '# skylint: disable=naked-thread'\n"
            'import threading\n'
            "t = threading.Thread(name='# skylint: "
            "disable=naked-thread — fake', target=print)\n")
        findings = analysis.run([str(tmp_path)],
                                rules=['naked-thread'])
        # No suppression findings from the strings, and the string
        # on the line above the violation does not suppress it.
        assert [f.rule for f in findings] == ['naked-thread']

    def test_same_basename_files_do_not_cross_suppress(self,
                                                       tmp_path):
        """Two scanned files sharing a basename must not share a
        suppression table: a justified disable in one cannot mask a
        violation at the same line of the other."""
        (tmp_path / 'a').mkdir()
        (tmp_path / 'b').mkdir()
        (tmp_path / 'a' / 'x.py').write_text(
            'import threading\n'
            't = threading.Thread(target=print)\n')
        (tmp_path / 'b' / 'x.py').write_text(
            'import threading\n'
            't = threading.Thread(target=print)  '
            '# skylint: disable=naked-thread — joined in caller\n')
        findings = analysis.run(
            [str(tmp_path / 'a' / 'x.py'),
             str(tmp_path / 'b' / 'x.py')],
            rules=['naked-thread'])
        assert len(findings) == 1, [f.render() for f in findings]
        assert findings[0].path.endswith('x.py')

    def test_multi_rule_disable(self, tmp_path):
        findings = self._run(
            tmp_path, self.BAD_THREAD +
            '  # skylint: disable=naked-thread,sleep-in-retry — two')
        assert findings == []


# ---------------------------------------------------------------------
# 3b. JSON output schema (stable API for tooling).
# ---------------------------------------------------------------------


class TestJsonSchema:

    EXPECTED_KEYS = {'rule', 'path', 'line', 'col', 'severity',
                     'message'}

    def test_finding_dict_keys_are_stable(self, tmp_path):
        files, docs = FIXTURES['naked-thread']
        findings = run_fixture(tmp_path, 'naked-thread', files, docs)
        assert findings
        for finding in findings:
            d = finding.to_dict()
            assert set(d) == self.EXPECTED_KEYS
            assert isinstance(d['line'], int)
            assert isinstance(d['col'], int)
            assert d['severity'] in a_core.SEVERITIES
            json.dumps(d)  # round-trips

    def test_findings_sorted_by_location(self, tmp_path):
        (tmp_path / 'a.py').write_text(
            'import threading\n'
            't1 = threading.Thread(target=print)\n'
            't2 = threading.Thread(target=print)\n')
        (tmp_path / 'b.py').write_text(
            'import threading\n'
            't3 = threading.Thread(target=print)\n')
        findings = analysis.run([str(tmp_path)],
                                rules=['naked-thread'])
        locs = [(f.path, f.line) for f in findings]
        assert locs == sorted(locs)

    def test_unknown_rule_filter_raises(self):
        with pytest.raises(ValueError, match='unknown rule'):
            analysis.run([PKG_DIR], rules=['no-such-rule'])


# ---------------------------------------------------------------------
# 3c. Scope/parent-link resolution on tricky shapes.
# ---------------------------------------------------------------------


class TestScopeResolution:

    def test_env_read_through_import_alias(self, tmp_path):
        findings = run_fixture(tmp_path, 'env-contract', {
            'aliased.py': '''
                from os import environ as e
                def f():
                    return e.get('SKYTPU_ALIASED_READ')
            ''',
        }, {'env_contract.md': '# empty\n'})
        assert any('SKYTPU_ALIASED_READ' in f.message
                   for f in findings)

    def test_env_read_through_module_constant(self, tmp_path):
        findings = run_fixture(tmp_path, 'env-contract', {
            'consts.py': "ENV_THING = 'SKYTPU_CONST_READ'\n",
            'reader.py': '''
                import os
                from consts import ENV_THING
                def f():
                    return os.environ.get(ENV_THING)
            ''',
        }, {'env_contract.md': '# empty\n'})
        assert any('SKYTPU_CONST_READ' in f.message
                   for f in findings)

    def test_sleep_through_aliased_import(self, tmp_path):
        findings = run_fixture(tmp_path, 'sleep-in-retry', {
            'aliased.py': '''
                from time import sleep as pause
                def fetch(do):
                    retries = 0
                    while retries < 3:
                        try:
                            return do()
                        except OSError:
                            retries += 1
                            pause(1)
            ''',
        })
        assert any(f.rule == 'sleep-in-retry' for f in findings)

    def test_sleep_through_local_helper(self, tmp_path):
        """Call-graph awareness: the grep lints could never see
        this one."""
        findings = run_fixture(tmp_path, 'sleep-in-retry', {
            'helper.py': '''
                import time
                def _nap():
                    time.sleep(1.0)
                def fetch(do):
                    for attempt in range(3):
                        try:
                            return do()
                        except OSError:
                            _nap()
            ''',
        })
        assert any('helper that sleeps' in f.message
                   for f in findings)

    def test_popen_through_aliased_module(self, tmp_path):
        findings = run_fixture(tmp_path, 'spawn-without-stamp', {
            'aliased.py': '''
                import subprocess as sp
                def go(cmd):
                    return sp.Popen(cmd, env={'PATH': '/bin'})
            ''',
        })
        assert any(f.rule == 'spawn-without-stamp' for f in findings)

    def test_environ_copy_env_is_sanctioned(self, tmp_path):
        findings = run_fixture(tmp_path, 'spawn-without-stamp', {
            'ok.py': '''
                import os, subprocess
                def go(cmd):
                    env = dict(os.environ)
                    env['EXTRA'] = '1'
                    return subprocess.Popen(cmd, env=env)
            ''',
        })
        assert findings == []

    def test_suppression_anchors_to_multiline_call_head(self,
                                                        tmp_path):
        """Parent links give findings the call's first line, so the
        disable comment on that line covers a call spanning many."""
        (tmp_path / 'multi.py').write_text(
            'import threading\n'
            't = threading.Thread(  # skylint: disable=naked-thread — joined below\n'
            '    target=print,\n'
            '    args=())\n')
        findings = analysis.run([str(tmp_path)],
                                rules=['naked-thread'])
        assert findings == []


# ---------------------------------------------------------------------
# 4. Meta: registry ⇄ fixtures ⇄ docs.
# ---------------------------------------------------------------------


class TestMeta:

    def test_every_rule_has_a_seeded_fixture(self):
        assert set(FIXTURES) == set(a_core.all_rule_ids()), (
            'every registered rule needs a seeded-violation fixture '
            'in FIXTURES (and every fixture a registered rule)')

    def test_every_rule_documented_in_static_analysis_doc(self):
        text = open(os.path.join(REPO_ROOT, 'docs',
                                 'static_analysis.md'),
                    encoding='utf-8').read()
        table = docs_contract.table_col0(text, r'[a-z0-9-]+')
        assert table == set(a_core.all_rule_ids()), (
            'docs/static_analysis.md rule table out of sync with '
            'the checker registry: '
            f'doc-only={sorted(table - set(a_core.all_rule_ids()))} '
            f'code-only={sorted(set(a_core.all_rule_ids()) - table)}')

    def test_rule_ids_are_kebab_case(self):
        for rule in a_core.all_rule_ids():
            assert rule == rule.lower() and ' ' not in rule

    def test_checkers_have_descriptions(self):
        for checker in a_core.all_checkers():
            assert checker.rule and checker.description
