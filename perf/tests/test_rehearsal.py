"""The rehearsal walks every cell of BENCHMARK.json end to end on the
CPU (four virtual devices for the four-chip cell), names the device
as ``cpu`` and writes no metric."""
import json
import os
import subprocess
import sys

import pytest

from perf.lib import harness

_CELLS = [w['name'] for w in harness.load_json(
    harness.REPO_DIR, 'BENCHMARK.json')['workloads']]


@pytest.mark.parametrize('workload', _CELLS)
def test_rehearsal_walks_the_cell(workload):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=4')
    proc = subprocess.run(
        [sys.executable, '-m', 'perf.run', '--workload', workload,
         '--seed', str(2**31 + 11), '--seconds', '2', '--trace', '1',
         '--rehearse-cpu'], cwd=harness.REPO_DIR, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert 'platform: cpu' in lines[-2]
    result = json.loads(lines[-1])
    assert set(result) == {'correct', 'attempted', 'failed', 'metrics',
                           'device'}
    assert set(result['device']) == {'platform', 'kind', 'count'}
    assert result['correct'] is True, proc.stdout[-2000:]
    assert result['device']['platform'] == 'cpu'
    assert result['metrics'] == {}
    assert 'memory_peak_bytes' not in result['device']
    assert result['attempted'] > 0 and result['failed'] == 0
