"""chip_smoke.py and the compile-cache helper.

The chip itself is reached only through the chip tool; what is
checked here is everything around it: the script refuses to run
without a TPU, any single failed request or step is a failure, the
two child commands work end to end at ``--model tiny`` (rehearsal
mode, marked slow), and the compile cache lands where the
environment — or, failing that, the checkout — says.
"""
import http.server
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, 'chip_smoke.py')


def _load_script():
    spec = importlib.util.spec_from_file_location('chip_smoke_under_test',
                                                  SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCompileCacheHelper:
    """Run in-process on the session's jax, restoring the config:
    the helper only sets config values, nothing compiles here."""

    @pytest.fixture
    def jax_config(self):
        import jax
        names = ('jax_compilation_cache_dir',
                 'jax_persistent_cache_min_compile_time_secs',
                 'jax_persistent_cache_min_entry_size_bytes')
        saved = {n: getattr(jax.config, n) for n in names}
        yield jax.config
        for n, v in saved.items():
            jax.config.update(n, v)

    def test_env_var_wins_and_no_directory_is_set(self, jax_config,
                                                  monkeypatch,
                                                  tmp_path):
        from skypilot_tpu.utils import jax_runtime
        before = jax_config.jax_compilation_cache_dir
        monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR',
                           str(tmp_path / 'placed'))
        jax_runtime.configure_compile_cache()
        # JAX reads the variable itself (at import); the helper must
        # leave the directory setting exactly as it found it.
        assert jax_config.jax_compilation_cache_dir == before
        assert not (tmp_path / 'placed').exists()

    def test_default_is_checkout_regardless_of_cwd(self, jax_config,
                                                   monkeypatch,
                                                   tmp_path):
        from skypilot_tpu.utils import jax_runtime
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
        monkeypatch.chdir(tmp_path)  # a job's runtime dir
        got = jax_runtime.configure_compile_cache()
        assert got == os.path.join(REPO, '.jax_cache')
        assert jax_config.jax_compilation_cache_dir == got
        # The small executables a replica prewarms must be cached.
        assert jax_config.\
            jax_persistent_cache_min_compile_time_secs == 0.0
        assert jax_config.\
            jax_persistent_cache_min_entry_size_bytes == -1

    def test_path_has_no_moving_part(self):
        """The path is part of what makes the cache hit: nothing in
        it may change from one process or run to the next."""
        import inspect

        from skypilot_tpu.utils import jax_runtime
        src = inspect.getsource(jax_runtime)
        for moving in ('tempfile', 'getpid', 'import time',
                       'datetime', 'uuid', 'getcwd'):
            assert moving not in src, moving
        with open(os.path.join(REPO, '.gitignore'),
                  encoding='utf-8') as f:
            assert '.jax_cache/' in f.read().split()

    def test_entry_points_place_the_cache_first(self):
        """Both recipes and bench.py call the helper, and no code
        sets a cache directory of its own."""
        for rel in ('skypilot_tpu/recipes/finetune.py',
                    'skypilot_tpu/recipes/serve_model.py',
                    'bench.py'):
            with open(os.path.join(REPO, rel), encoding='utf-8') as f:
                assert 'configure_compile_cache()' in f.read(), rel
        setters = []
        for root, _, files in os.walk(os.path.join(REPO,
                                                   'skypilot_tpu')):
            for name in files:
                if not name.endswith('.py'):
                    continue
                path = os.path.join(root, name)
                with open(path, encoding='utf-8') as f:
                    if 'jax_compilation_cache_dir' in f.read():
                        setters.append(os.path.relpath(path, REPO))
        assert setters == ['skypilot_tpu/utils/jax_runtime.py']


class TestChipSmokeRefusesWithoutAChip:

    def test_no_rehearsal_argument_fails_fast_on_cpu(self, tmp_path):
        """No TPU here: the script must exit non-zero, quickly, say
        which platform it wanted and what the environment had, and
        print no result line."""
        env = dict(os.environ, JAX_PLATFORMS='cpu',
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
        t0 = time.monotonic()
        proc = subprocess.run([sys.executable, SCRIPT], env=env,
                              cwd=REPO, capture_output=True,
                              text=True, timeout=120, check=False)
        assert proc.returncode != 0
        assert time.monotonic() - t0 < 60
        assert '"ok"' not in proc.stdout
        assert 'FAILED' in proc.stdout
        assert 'JAX_PLATFORMS=tpu' in proc.stdout
        assert "this environment had 'cpu'" in proc.stdout
        assert "Unable to initialize backend 'tpu'" in proc.stdout

    def test_parent_stays_off_jax(self):
        """A chip belongs to one process: the parent that starts the
        chip-holding children must never import jax itself."""
        code = ('import sys, importlib.util as u; '
                f's = u.spec_from_file_location("cs", {SCRIPT!r}); '
                'm = u.module_from_spec(s); s.loader.exec_module(m); '
                'import skypilot_tpu.execution, skypilot_tpu.core; '
                'from skypilot_tpu.utils import jax_runtime; '
                'from skypilot_tpu.serve import prefix_hash; '
                'print("jax" in sys.modules)')
        out = subprocess.run([sys.executable, '-c', code], cwd=REPO,
                             capture_output=True, text=True,
                             timeout=60, check=True)
        assert out.stdout.strip() == 'False'


class _Replica(http.server.BaseHTTPRequestHandler):
    """A stand-in replica whose /generate reply the test dictates."""
    reply = (200, {'output_ids': [1, 2, 3]})

    def log_message(self, *args):
        pass

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers.get('Content-Length', '0')))
        code, obj = type(self).reply
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header('Content-Length', str(len(body)))
        self.end_headers()
        self.wfile.write(body)


class TestAnySingleFailureFails:

    @pytest.fixture
    def replica(self):
        server = http.server.ThreadingHTTPServer(('127.0.0.1', 0),
                                                 _Replica)
        thread = threading.Thread(target=server.serve_forever,
                                  daemon=True)
        thread.start()
        yield server.server_address[1]
        server.shutdown()
        server.server_close()
        thread.join(timeout=10)
        assert not thread.is_alive()

    @pytest.mark.parametrize('reply,why', [
        ((500, {'error': 'engine died'}), 'HTTP 500'),
        ((200, {'output_ids': [1, 2]}), 'asked 3 tokens, got 2'),
        ((200, {'output_ids': [1, 2, 512]}), 'outside the vocabulary'),
        ((200, {'output_ids': [1, 2, -1]}), 'outside the vocabulary'),
    ])
    def test_one_bad_reply_is_a_failure(self, replica, reply, why):
        smoke = _load_script()
        _Replica.reply = reply
        with pytest.raises(smoke.SmokeFailure, match=why):
            smoke.generate(replica, smoke.REHEARSAL, 'probe',
                           [5, 6, 7], 3)

    def test_good_reply_passes(self, replica):
        smoke = _load_script()
        _Replica.reply = (200, {'output_ids': [1, 2, 511]})
        got = smoke.generate(replica, smoke.REHEARSAL, 'probe',
                             [5, 6, 7], 3)
        assert got['ids'] == [1, 2, 511]

    @staticmethod
    def _job_log(platform='tpu', losses=None, kernels=None,
                 cache_dir='/c'):
        losses = losses or [11.9 - 0.01 * i for i in range(8)]
        kernels = kernels if kernels is not None else {
            'flash_fwd': 1, 'flash_bwd_dq': 1, 'flash_bwd_dkv': 1}
        device = {'platform': platform, 'device_kind': 'TPU v5 lite',
                  'device_count': 1, 'jax': '0.9.0'}
        lines = ['skytpu device ' + json.dumps(device),
                 'train_step ' + json.dumps({'kernels': kernels})]
        lines += [f'step {i} loss={v} grad_norm=0.2 '
                  f'tokens/s={1000 * (i + 1)} tokens/s/chip=1'
                  for i, v in enumerate(losses)]
        lines += ['runtime ' + json.dumps({
            'compiled': 2, 'cache_hits': 0, 'cache_dir': cache_dir,
            'memory': [{'device': 0, 'peak_bytes_in_use': 7}]}),
            'finetune done.']
        return '\n'.join(lines)

    def test_trainer_log_checks(self):
        smoke = _load_script()
        full = smoke.FULL
        ok = smoke.check_trainer_log(self._job_log(), full, '/c')
        assert ok['kernels']['flash_bwd_dkv'] == 1
        assert ok['peak_hbm_bytes'] == 7 and len(ok['losses']) == 8
        nan = [11.9] * 8
        nan[5] = float('nan')
        bad = {
            'wanted \'tpu\'': self._job_log(platform='cpu'),
            'non-finite loss': self._job_log(losses=nan),
            'wanted steps 0..7': self._job_log(losses=[11.9] * 7),
            'not near ln': self._job_log(losses=[3.0] * 8),
            'not in the lowered train step': self._job_log(
                kernels={'flash_fwd': 1, 'flash_bwd_dq': 0,
                         'flash_bwd_dkv': 1}),
            'did not survive the launcher': self._job_log(
                cache_dir='/elsewhere'),
        }
        for why, text in bad.items():
            with pytest.raises(smoke.SmokeFailure, match=why):
                smoke.check_trainer_log(text, full, '/c')

    def test_kernel_names_match_the_kernels(self):
        """The script cannot import the kernels' module (it would
        import jax), so its copy of their names is pinned here."""
        from skypilot_tpu.ops import attention
        assert _load_script().EXPECTED_KERNELS == \
            attention.KERNEL_NAMES

    def test_leftover_process_is_a_failure(self):
        """A process carrying the run's marker after a phase ended
        (it could hold the chip) fails the run — and is killed."""
        smoke = _load_script()
        marker = 'leftover-test-marker'
        env = dict(os.environ, **{smoke.MARKER_ENV: marker})
        proc = subprocess.Popen([sys.executable, '-c',
                                 'import time; time.sleep(120)'],
                                env=env, start_new_session=True)
        try:
            assert proc.pid in smoke.marked_processes(marker)
            with pytest.raises(smoke.SmokeFailure,
                               match='still alive'):
                smoke.wait_all_gone(marker, 'test phase', timeout=1.0)
            proc.wait(timeout=10)      # wait_all_gone killed it
            assert smoke.marked_processes(marker) == {}
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)


@pytest.mark.slow
class TestRehearsal:

    def test_both_child_commands_at_tiny_on_cpu(self, tmp_path):
        """The two child commands chip_smoke runs on the chip, end to
        end at --model tiny on the CPU: replica over HTTP, trainer
        through sky.launch on the local provider."""
        cache = tmp_path / 'cache'
        env = dict(os.environ, JAX_PLATFORMS='cpu',
                   JAX_COMPILATION_CACHE_DIR=str(cache))
        # One device, as on the one-chip machine (conftest exports
        # eight virtual ones for the mesh tests).
        env.pop('XLA_FLAGS', None)
        proc = subprocess.run([sys.executable, SCRIPT,
                               '--rehearse-cpu'], env=env, cwd=REPO,
                              capture_output=True, text=True,
                              timeout=600, check=False)
        assert proc.returncode == 0, proc.stdout[-3000:]
        last = json.loads(proc.stdout.strip().splitlines()[-1])
        assert last == {'ok': True, 'rehearsal': True,
                        'device': {'platform': 'cpu', 'kind': 'cpu',
                                   'count': 1}}
        assert 'server ok:' in proc.stdout
        assert 'trainer ok:' in proc.stdout
        # Both children — the job through the launcher included —
        # cached where the environment said.
        assert any(n.endswith('-cache') for n in os.listdir(cache))
