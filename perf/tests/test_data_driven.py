"""A configuration, a traffic mix, a cell, a per-layer metric and a
kernel's cost function added as NEW files (and entries of BENCHMARK.json) in a temporary
copy are found by name and run, with no edit to a file that was
there."""
import json
import os
import shutil
import time

from perf.lib import harness

_ROOT = harness.REPO_DIR


def _copy_with_additions(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(_ROOT, 'BENCHMARK.json'), root)
    shutil.copytree(os.path.join(_ROOT, 'perf'),
                    os.path.join(root, 'perf'),
                    ignore=shutil.ignore_patterns(
                        '.traces', '__pycache__', 'data'))
    before = {}
    for d, _, files in os.walk(os.path.join(root, 'perf')):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, 'rb').read()
    perf = os.path.join(root, 'perf')
    cfg = json.load(open(os.path.join(
        perf, 'configs', 'mistral-7b-int8-serve.json')))
    cfg['build']['slots'] = 8
    cfg['deployment'] = 'a smaller replica, added by a later PR'
    json.dump(cfg, open(os.path.join(
        perf, 'configs', 'new-config.json'), 'w'))
    json.dump({'extends': 'chat-steady', 'rate_rps': 1.0,
               'shared_prompts': 2},
              open(os.path.join(perf, 'traffic', 'new-mix.json'), 'w'))
    json.dump({'reader': 'registry_counter_ratio',
               'params': {'numerator': ['skytpu_batch_requests_total'],
                          'denominator':
                              ['skytpu_batch_requests_total'],
                          'scale': 1.0}},
              open(os.path.join(perf, 'layer_metrics',
                                'new_metric.json'), 'w'))
    with open(os.path.join(perf, 'layer_metrics', 'own_reader.py'),
              'w') as f:
        f.write('from perf import costs\n'
                'def reduce(trace, records):\n'
                '    fn = costs.cost_function("new_kernel.work", '
                'records["perf_dir"])\n'
                '    return fn(records["model"]) if '
                'records.get("registry") else None\n')
    with open(os.path.join(perf, 'costs', 'new_kernel.py'), 'w') as f:
        f.write('def work(cfg):\n    return 42.0\n')
    bench = json.load(open(os.path.join(root, 'BENCHMARK.json')))
    bench['configs'].append({
        'name': 'new-config', 'source': cfg['source'],
        'file': 'perf/configs/new-config.json', 'reduced': [],
        'why': 'added by the test'})
    bench['workloads'].append({
        'name': 'new-cell', 'config': 'new-config',
        'traffic': 'new-mix', 'chips': 1, 'why': 'added by the test'})
    for m in bench['end_to_end']:
        if m['name'] == 'tpot_p50_ms':
            m['workloads'].append('new-cell')
    for m in bench['per_layer']:
        if m['name'] in ('decode_step_ms.steady',
                         'prefix_hit_pct.steady'):
            m['workloads'].append('new-cell')
    for name in ('new_metric', 'own_reader'):
        bench['per_layer'].append({
            'name': name, 'unit': 'requests', 'better': 'higher',
            'source': 'program_counter', 'layer': 'engine scheduler',
            'moves': 'tpot_p50_ms', 'workloads': ['new-cell']})
    json.dump(bench, open(os.path.join(root, 'BENCHMARK.json'), 'w'))
    return root, before


def test_new_files_are_found_and_run_without_editing_old_ones(
        tmp_path):
    from skypilot_tpu import metrics as metrics_lib
    from perf.lib.registry_delta import RegistryWindow
    root, before = _copy_with_additions(tmp_path)
    loaded = harness.load_cell('new-cell', rehearse=True, root=root)
    assert loaded['config']['build']['slots'] == 4  # rehearsal size
    assert loaded['traffic']['shared_prompts'] == 2
    assert {m['name'] for m in loaded['per_layer']} >= \
        {'new_metric', 'own_reader', 'decode_step_ms.steady'}
    driver = harness.driver_for(loaded['config'])
    out = driver.run(loaded, 5, 2.0, True, True, time.perf_counter())
    assert out['correct'], out['compared']
    records = {'registry': out['registry'], 'facts': out['facts'],
               'model': out['model'], 'e2e': out['e2e'], 'peaks': {},
               'perf_dir': loaded['perf_dir']}
    assert isinstance(out['registry'], RegistryWindow)
    metrics = harness.read_layer_metrics(loaded, None, records)
    assert metrics['new_metric'] == {'value': 1.0, 'unit': 'requests'}
    assert metrics['own_reader']['value'] == 42.0
    # A reader with nothing to read (no trace here) is left out.
    assert 'decode_step_ms.steady' not in metrics
    assert 'prefix_hit_pct.steady' in metrics
    for p, content in before.items():
        assert open(p, 'rb').read() == content, f'{p} was edited'
    del metrics_lib


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    import subprocess
    import sys
    root = str(tmp_path)
    shutil.copy(os.path.join(_ROOT, 'BENCHMARK.json'), root)
    shutil.copytree(os.path.join(_ROOT, 'perf'),
                    os.path.join(root, 'perf'),
                    ignore=shutil.ignore_patterns(
                        '.traces', '__pycache__', 'data'))
    env = {k: v for k, v in os.environ.items() if k != 'PYTHONPATH'}
    proc = subprocess.run(
        [sys.executable, '-m', 'perf.run', '--workload',
         'serve-chat-steady', '--seed', '1', '--seconds', '1',
         '--trace', '0', '--rehearse-cpu'], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_without_a_tpu_and_without_rehearse_the_run_fails():
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, '-m', 'perf.run', '--workload',
         'serve-chat-steady', '--seed', '1', '--seconds', '1',
         '--trace', '0'], cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS='cpu'),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert 'no accelerator' in proc.stderr


def test_per_layer_has_one_entry_a_family_and_judged_metric():
    """The rule that keeps the list short (PR 45): a new cell puts
    its NAME into the ``workloads`` lists of the families it reports
    and adds entries only for readings no family has. The builder's
    contract admits 128 entries and refuses a 129th before any run."""
    bench = harness.load_json(_ROOT, 'BENCHMARK.json')
    entries = bench['per_layer']
    assert len(entries) <= 128
    cells = {w['name'] for w in bench['workloads']}
    reports = {m['name']: set(m.get('workloads', cells))
               for m in bench['end_to_end']}
    seen = {}
    for m in entries:
        family = m['name'].rpartition('.')[0] or m['name']
        twin = seen.setdefault((family, m['moves']), m['name'])
        assert twin == m['name'], (
            f'{twin} and {m["name"]} are one family moving '
            f'{m["moves"]}: keep one entry and list both cells')
        assert m.get('workloads'), m['name']
        assert len(set(m['workloads'])) == len(m['workloads'])
        assert set(m['workloads']) <= cells, m['name']
        assert set(m['workloads']) <= reports[m['moves']], m['name']
        harness.reader_for(m['name'], harness.PERF_DIR)


def test_experts_held_is_the_configurations_own_count():
    """``moe_busiest_over_mean`` scales by ``experts_held``, which a
    configuration with experts states beside the model's own key for
    the experts a replica holds (``num_experts`` where it is a share
    of the published count, ``n_routed_experts`` where every expert
    is held), at the real size and at the rehearsal's; the reader
    takes it from the driver's facts and returns nothing without it."""
    from perf.layer_metrics import moe_busiest_over_mean as reader
    bench = harness.load_json(_ROOT, 'BENCHMARK.json')
    stated = 0
    for entry in bench['configs']:
        config = harness.load_json(_ROOT, entry['file'])
        if config.get('driver') == 'train_step':
            continue
        model, tiny = config['model'], config['rehearsal']
        for held, keys in (
                (config.get('experts_held'), model),
                (tiny.get('config', {}).get('experts_held'),
                 dict(model, **tiny['model']))):
            own = keys.get('num_experts', keys.get('n_routed_experts'))
            assert held == own, (entry['name'], held, own)
        stated += config.get('experts_held') is not None
    assert stated == 3
    counts = {'skytpu_batch_moe_busiest_expert_pairs_total': 30.0,
              'skytpu_batch_moe_held_pairs_total': 640.0}

    class Registry:
        def delta(self, name):
            return (counts[name], 0.0) if name in counts else None

    records = {'registry': Registry(), 'facts': {'experts_held': 64}}
    assert reader.reduce(None, records) == 64 * 30.0 / 640.0
    assert reader.reduce(None, dict(records, facts={})) is None
