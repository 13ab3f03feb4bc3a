#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two processes users run, through their normal entry
points, at the full width of one supported model each (random
weights from a fixed seed):

  (a) server  — ``python -m skypilot_tpu.recipes.serve_model
      --model mistral-7b --quant int8 --kv-int8 --slots 8``: the
      paged continuous-batching engine, as ``xsky serve up`` starts a
      replica. Over HTTP: readiness, concurrent / multi-chunk /
      repeated-prefix / sampled / streamed ``/generate`` requests.
  (b) trainer — ``sky.launch`` of ``python -m
      skypilot_tpu.recipes.finetune --model llama3.2-1b --seq 2048
      --batch 8 --steps 8 --lora-rank 16`` on the local provider;
      the job log is read back through ``sky.tail_logs``.

This process never imports jax: a chip belongs to one process, so
each phase is a child that owns it, and the next phase starts only
once every process of the previous one is gone. Children run with
``JAX_PLATFORMS=tpu``, so a missing chip is JAX's own hard error
rather than its silent CPU fallback; ``--rehearse-cpu`` is the only
way to run on the CPU, and it runs the tiny model (control flow
only — nothing it prints is a device number).

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
only if every request, step and phase passed and no process was
left behind. Needs no network. Per-phase figures printed on the way
(time to ready, executables compiled, peak HBM, compile-cache
entries) are set-up facts, not benchmark metrics.
"""
import argparse
import http.client
import io
import json
import math
import os
import random
import shlex
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import uuid

# The program under test; all jax-free at import. Without it beside
# this script there is nothing to check: ImportError, non-zero exit.
import skypilot_tpu as sky
from skypilot_tpu.runtime import agent_client
from skypilot_tpu.serve import prefix_hash
from skypilot_tpu.utils import jax_runtime

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 20260926
MARKER_ENV = 'CHIP_SMOKE_RUN'

# Sizes per mode. The script never lowers one by itself: a run that
# does not fit fails.
FULL = {
    'platform': 'tpu',
    'server_model': 'mistral-7b', 'vocab': 32000,
    'prompt_lens': (40, 300, 1500),      # chunk 512: 1, 1 and 3 chunks
    'trainer_model': 'llama3.2-1b', 'trainer_vocab': 128256,
    'seq': 2048, 'batch': 8, 'steps': 8, 'lora_rank': 16,
    'ready_timeout': 600, 'request_timeout': 420, 'job_timeout': 480,
}
REHEARSAL = {
    'platform': 'cpu',
    'server_model': 'tiny', 'vocab': 512,
    'prompt_lens': (40, 120, 400),
    'trainer_model': 'tiny', 'trainer_vocab': 512,
    'seq': 128, 'batch': 2, 'steps': 8, 'lora_rank': 4,
    'ready_timeout': 180, 'request_timeout': 120, 'job_timeout': 180,
}
# = skypilot_tpu.ops.attention.KERNEL_NAMES (importing it imports jax).
EXPECTED_KERNELS = ('flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv')


class SmokeFailure(Exception):
    """A phase did not pass. Never caught to exit 0."""


def log(msg: str) -> None:
    print(f'[chip_smoke] {msg}', flush=True)


def tail(path: str, lines: int = 40) -> str:
    try:
        with open(path, encoding='utf-8', errors='replace') as f:
            return ''.join(f.readlines()[-lines:])
    except OSError as e:
        return f'<no log: {e}>'


# ---------------------------------------------------------------------
# Process accounting: every process this run starts inherits
# MARKER_ENV (agents copy os.environ, jobs inherit the agent's), so
# "is anything of ours still alive" is a scan of /proc/*/environ —
# sessions and process groups do not help, the launcher's daemons and
# jobs each start their own.
# ---------------------------------------------------------------------


def marked_processes(marker: str) -> dict:
    needle = f'{MARKER_ENV}={marker}'.encode()
    found = {}
    for pid_s in os.listdir('/proc'):
        if not pid_s.isdigit() or int(pid_s) == os.getpid():
            continue
        try:
            with open(f'/proc/{pid_s}/environ', 'rb') as f:
                if needle not in f.read().split(b'\0'):
                    continue
            with open(f'/proc/{pid_s}/stat', encoding='utf-8') as f:
                # Zombies hold nothing; the field after "(comm)".
                if f.read().rsplit(')', 1)[1].split()[0] == 'Z':
                    continue
            with open(f'/proc/{pid_s}/cmdline', 'rb') as f:
                cmd = f.read().replace(b'\0', b' ').decode(
                    'utf-8', 'replace').strip()
        except OSError:
            continue  # raced an exit, or not ours to read
        found[int(pid_s)] = cmd
    return found


def wait_all_gone(marker: str, what: str, timeout: float = 60.0) -> None:
    """The gate between phases: nothing of ours may still run (and
    hold the chip) when the next phase starts or the script ends."""
    deadline = time.monotonic() + timeout
    left = marked_processes(marker)
    while left and time.monotonic() < deadline:
        time.sleep(0.5)
        left = marked_processes(marker)
    if left:
        kill_marked(marker)
        raise SmokeFailure(
            f'{what}: {len(left)} process(es) still alive after '
            f'{timeout:.0f}s: {left}')


def kill_marked(marker: str) -> None:
    for pid in marked_processes(marker):
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir)
                   if n.endswith('-cache'))
    except FileNotFoundError:
        return 0


def check_device(device: dict, mode: dict, who: str) -> None:
    if device.get('platform') != mode['platform']:
        raise SmokeFailure(
            f'{who} ran on platform {device.get("platform")!r} '
            f'({device}), wanted {mode["platform"]!r}')


def parse_device_line(text: str, who: str) -> dict:
    for line in text.splitlines():
        if line.startswith(jax_runtime.DEVICE_LINE_PREFIX):
            return json.loads(
                line[len(jax_runtime.DEVICE_LINE_PREFIX):])
    raise SmokeFailure(f'{who}: no "skytpu device" line in its log')


def peak_hbm(runtime: dict):
    mem = runtime.get('memory') or []
    return max((d.get('peak_bytes_in_use', 0) for d in mem),
               default=None)


# ---------------------------------------------------------------------
# Phase (a): the serving replica
# ---------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body=None,
              timeout: float = 60.0):
    conn = http.client.HTTPConnection('127.0.0.1', port,
                                      timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body)
        conn.request(method, path, body=payload)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def generate(port: int, mode: dict, name: str, prompt, max_new: int,
             **extra) -> dict:
    """One /generate request, checked: 200, exactly ``max_new`` ids,
    all inside the vocabulary. An engine that died answers 500 and
    keeps the port open, so the status is the check."""
    body = dict(prompt_ids=prompt, max_new_tokens=max_new, **extra)
    status, headers, raw = http_json(
        port, 'POST', '/generate', body,
        timeout=mode['request_timeout'])
    if status != 200:
        raise SmokeFailure(f'request {name}: HTTP {status}: '
                           f'{raw[:400]!r}')
    if extra.get('stream'):
        events = [line[len('data: '):]
                  for line in raw.decode().splitlines()
                  if line.startswith('data: ')]
        if not events or events[-1] != '[DONE]':
            raise SmokeFailure(f'request {name}: stream did not end '
                               f'in [DONE]: {raw[-200:]!r}')
        ids = [int(e) for e in events[:-1]]
    else:
        ids = json.loads(raw)['output_ids']
    if len(ids) != max_new:
        raise SmokeFailure(f'request {name}: asked {max_new} tokens, '
                           f'got {len(ids)}')
    if not all(isinstance(t, int) and 0 <= t < mode['vocab']
               for t in ids):
        raise SmokeFailure(f'request {name}: ids outside the '
                           f'vocabulary: {ids}')
    return {
        'ids': ids,
        'prefix_hits': int(headers.get(
            prefix_hash.PREFIX_HITS_HEADER, -1)),
    }


def server_requests(port: int, mode: dict) -> dict:
    rng = random.Random(SEED)
    short, mid, long_ = (
        [rng.randrange(mode['vocab']) for _ in range(n)]
        for n in mode['prompt_lens'])
    mid2 = [rng.randrange(mode['vocab'])
            for _ in range(mode['prompt_lens'][1])]
    sampled = dict(temperature=0.8, top_p=0.9, seed=SEED)

    # Wave 1: four requests in flight at once, so rows share decode
    # dispatches while the long prompt is still prefilling chunks.
    # All greedy: the engine switches every step to its sampled twin
    # while a sampled row is resident, so a sampled request in here
    # would make WHICH executables get compiled a matter of thread
    # timing, and two runs could not be compared by their compile
    # counts.
    wave = {
        'short': (short, 16, {}),
        'mid': (mid, 24, {}),
        'long': (long_, 32, {}),
        'mid2': (mid2, 16, {}),
    }
    results, errors = {}, []
    barrier = threading.Barrier(len(wave))

    def worker(name, prompt, max_new, extra):
        try:
            barrier.wait(timeout=30)
            results[name] = generate(port, mode, name, prompt,
                                     max_new, **extra)
        except BaseException as e:  # pylint: disable=broad-except
            errors.append((name, e))  # re-raised below, never dropped

    threads = [threading.Thread(target=worker, args=(n, *spec),
                                daemon=True)
               for n, spec in wave.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=mode['request_timeout'] + 60)
    if any(t.is_alive() for t in threads):
        raise SmokeFailure('concurrent wave: a request never returned')
    if errors:
        raise SmokeFailure(f'concurrent wave failed: {errors}')

    # Wave 2, one at a time. The long prompt again: its blocks are in
    # the prefix cache now and the reply's headers must say so.
    again = generate(port, mode, 'long-again', long_, 32)
    if again['prefix_hits'] <= 0:
        raise SmokeFailure('repeated prompt reported no prefix-cache '
                           f'hit: {again}')
    # Identical conditions twice (alone, cache warm) must give
    # identical tokens — the hardware is deterministic. Whether the
    # cold, batched first send agrees too is printed, not required:
    # different shapes may round differently on the MXU.
    third = generate(port, mode, 'long-third', long_, 32)
    if third['ids'] != again['ids']:
        raise SmokeFailure('same greedy request, same conditions, '
                           f'different tokens: {again["ids"]} vs '
                           f'{third["ids"]}')
    s1 = generate(port, mode, 'sampled', mid2, 16, **sampled)
    s2 = generate(port, mode, 'sampled-again', mid2, 16, **sampled)
    if s2['ids'] != s1['ids']:
        raise SmokeFailure('same seeded sampled request, same '
                           f'conditions, different tokens: '
                           f'{s1["ids"]} vs {s2["ids"]}')
    streamed = generate(port, mode, 'streamed', short, 16, stream=True)

    def first_diff(a, b):
        """Index of the first differing token, None if identical."""
        return next((i for i, (x, y) in enumerate(zip(a, b))
                     if x != y), None)

    return {
        'requests': len(wave) + 5,
        'prefix_hit_blocks': again['prefix_hits'],
        # Same request under different batch company / cache state:
        # where (if anywhere) the replies part. Observations.
        'greedy_batched_cold_vs_alone_cached_first_diff':
            first_diff(results['long']['ids'], again['ids']),
        'greedy_batched_vs_alone_streamed_first_diff':
            first_diff(results['short']['ids'], streamed['ids']),
    }


def server_phase(mode: dict, env: dict, log_dir: str, marker: str,
                 cache_dir: str) -> dict:
    port = free_port()
    log_path = os.path.join(log_dir, 'server.log')
    cmd = [sys.executable, '-m', 'skypilot_tpu.recipes.serve_model',
           '--model', mode['server_model'], '--quant', 'int8',
           '--kv-int8', '--slots', '8', '--port', str(port)]
    entries_before = cache_entries(cache_dir)
    log(f'server: {" ".join(cmd)}')
    t0 = time.monotonic()
    with open(log_path, 'wb') as logf:
        proc = subprocess.Popen(cmd, env=env, cwd=REPO, stdout=logf,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    try:
        while True:
            if proc.poll() is not None:
                raise SmokeFailure(
                    f'server exited with code {proc.returncode} '
                    f'before it was ready (it ran with JAX_PLATFORMS='
                    f'{env["JAX_PLATFORMS"]}; this environment had '
                    f'{os.environ.get("JAX_PLATFORMS")!r}); last log '
                    f'lines:\n{tail(log_path)}')
            if 'serve_model ready' in tail(log_path, 5):
                break
            if time.monotonic() - t0 > mode['ready_timeout']:
                raise SmokeFailure(
                    f'server not ready after {mode["ready_timeout"]}'
                    f's; last log lines:\n{tail(log_path)}')
            time.sleep(0.5)
        ready_s = time.monotonic() - t0
        with open(log_path, encoding='utf-8', errors='replace') as f:
            device = parse_device_line(f.read(), 'server')
        check_device(device, mode, 'server')

        status, _, raw = http_json(port, 'GET', '/')
        probe = json.loads(raw)
        if status != 200 or probe.get('status') != 'ok' or \
                probe.get('device') != device:
            raise SmokeFailure(f'GET / answered {status}: {probe}')
        at_ready = probe['runtime']

        t1 = time.monotonic()
        try:
            facts = server_requests(port, mode)
        except SmokeFailure as e:
            raise SmokeFailure(f'{e}\nserver log, last lines:\n'
                               f'{tail(log_path)}') from e
        work_s = time.monotonic() - t1
        _, _, raw = http_json(port, 'GET', '/')
        after = json.loads(raw)['runtime']
    finally:
        # SIGTERM, then wait for the process to be GONE before
        # anything else may touch the chip.
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
    wait_all_gone(marker, 'server phase')
    if after['cache_dir'] != cache_dir:
        raise SmokeFailure(f'server cached in {after["cache_dir"]}, '
                           f'not in {cache_dir}')
    return {
        'device': device,
        'ready_s': round(ready_s, 1),
        'compiled_to_ready': at_ready['compiled'],
        'cache_hits_to_ready': at_ready['cache_hits'],
        'work_s': round(work_s, 1),
        'compiled_in_work': after['compiled'] - at_ready['compiled'],
        'cache_hits_in_work':
            after['cache_hits'] - at_ready['cache_hits'],
        'peak_hbm_bytes': peak_hbm(after),
        'cache_entries': [entries_before, cache_entries(cache_dir)],
        **facts,
    }


# ---------------------------------------------------------------------
# Phase (b): the trainer, through the launcher
# ---------------------------------------------------------------------


def trainer_phase(mode: dict, env: dict, log_dir: str, marker: str,
                  cache_dir: str) -> dict:
    # The launcher's agents copy os.environ and the job inherits the
    # agent's environment — that is how JAX_PLATFORMS and the cache
    # directory reach the job, as they would from a user's shell.
    os.environ.update(env)
    binary = agent_client.resolve_agent_binary()
    if binary is not None:
        raise SmokeFailure(f'the launcher would use {binary}, a build '
                           'product a fresh checkout does not have')
    log('trainer: host agent = python -m skypilot_tpu.runtime.agent '
        '(forced; what a fresh git clone runs)')

    run = (f'{shlex.quote(sys.executable)} -m '
           f'skypilot_tpu.recipes.finetune '
           f'--model {mode["trainer_model"]} --seq {mode["seq"]} '
           f'--batch {mode["batch"]} --steps {mode["steps"]} '
           f'--lora-rank {mode["lora_rank"]} --log-every 1')
    task = sky.Task(name='chip-smoke-train', run=run)
    task.set_resources(sky.Resources(cloud='local'))   # one host
    cluster = 'chip-smoke'
    entries_before = cache_entries(cache_dir)
    log(f'trainer: sky.launch({run!r}) on the local provider')
    t0 = time.monotonic()
    job_log = ''
    first_step_s = None
    try:
        job_id, _ = sky.launch(task, cluster, detach_run=True,
                               quiet_optimizer=True)
        while True:
            status = sky.job_status(cluster, job_id)
            buf = io.StringIO()
            sky.tail_logs(cluster, job_id, out=buf, follow=False)
            job_log = buf.getvalue()
            if first_step_s is None and any(
                    line.startswith('step 0 loss=')
                    for line in job_log.splitlines()):
                first_step_s = time.monotonic() - t0
            if status is not None and status.is_terminal():
                break
            if time.monotonic() - t0 > mode['job_timeout']:
                raise SmokeFailure(
                    f'job not finished after {mode["job_timeout"]}s '
                    f'(status {status}); log so far:\n'
                    f'{job_log[-3000:]}')
            time.sleep(1.0)
    finally:
        with open(os.path.join(log_dir, 'trainer_job.log'), 'w',
                  encoding='utf-8') as f:
            f.write(job_log)
        sky.down(cluster, purge=True)
    wait_all_gone(marker, 'trainer phase (after sky.down)')

    if status != sky.JobStatus.SUCCEEDED:
        raise SmokeFailure(f'trainer: job ended {status}; job log, '
                           f'last lines:\n{job_log[-3000:]}')
    if first_step_s is None:
        raise SmokeFailure('trainer: never saw "step 0" in the log')
    facts = check_trainer_log(job_log, mode, cache_dir)
    return {
        'device': facts.pop('device'),
        'agent': 'python',
        'ready_s': round(first_step_s, 1),   # launch -> step 0 logged
        **facts,
        'cache_entries': [entries_before, cache_entries(cache_dir)],
        'batch': mode['batch'], 'seq': mode['seq'],
    }


def check_trainer_log(job_log: str, mode: dict, cache_dir: str) -> dict:
    """Everything the job's own log must show: the platform, every
    step with a finite loss that starts near ln(vocab), the Pallas
    kernels in the lowered step (on the chip), and the compile cache
    where the parent said. Raises SmokeFailure on the first miss."""

    def fail(why: str):
        raise SmokeFailure(f'trainer: {why}; job log, last lines:\n'
                           f'{job_log[-3000:]}')

    device = parse_device_line(job_log, 'trainer job')
    check_device(device, mode, 'trainer job')
    losses, rates = {}, {}
    kernels = runtime = None
    for line in job_log.splitlines():
        if line.startswith('step ') and ' loss=' in line:
            step = int(line.split()[1])
            losses[step] = float(line.split(' loss=')[1].split()[0])
            rates[step] = float(
                line.split(' tokens/s=')[1].split()[0])
        elif line.startswith('train_step '):
            kernels = json.loads(line[len('train_step '):])['kernels']
        elif line.startswith('runtime '):
            runtime = json.loads(line[len('runtime '):])
    if sorted(losses) != list(range(mode['steps'])):
        fail(f'wanted steps 0..{mode["steps"] - 1}, log has '
             f'{sorted(losses)}')
    bad = {s: v for s, v in losses.items() if not math.isfinite(v)}
    if bad:
        fail(f'non-finite loss at steps {bad}')
    # Random weights on random tokens: the loss starts near
    # ln(vocab). Far from it means the forward is wrong, not slow.
    ln_v = math.log(mode['trainer_vocab'])
    if abs(losses[0] - ln_v) > 2.0:
        fail(f'step-0 loss {losses[0]} is not near ln(vocab)='
             f'{ln_v:.2f}')
    if kernels is None or runtime is None:
        fail('no "train_step" / "runtime" line')
    if mode['platform'] == 'tpu':
        missing = [k for k in EXPECTED_KERNELS if not kernels.get(k)]
        if missing:
            fail(f'Pallas kernels {missing} are not in the lowered '
                 f'train step ({kernels})')
    if runtime['cache_dir'] != cache_dir:
        fail(f'job cached in {runtime["cache_dir"]}, not in '
             f'{cache_dir} — the cache directory did not survive '
             'the launcher')
    # The recipe logs cumulative tokens/s since its loop began, so
    # the job's own clock gives the steps after the compiling one.
    tokens = mode['batch'] * mode['seq']
    last = mode['steps'] - 1
    work_s = (last + 1) * tokens / rates[last] - tokens / rates[0]
    return {
        'device': device,
        'compiled': runtime['compiled'],
        'cache_hits': runtime['cache_hits'],
        'work_s': round(work_s, 2),          # steps 1..last
        'peak_hbm_bytes': peak_hbm(runtime),
        'kernels': kernels,
        'losses': [losses[s] for s in sorted(losses)],
    }


# ---------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument(
        '--rehearse-cpu', action='store_true',
        help='rehearsal: tiny model on the CPU backend (control flow '
             'only). Without it the script runs only on a TPU.')
    args = parser.parse_args()
    mode = REHEARSAL if args.rehearse_cpu else FULL

    # One cache directory for both children and both runs of this
    # script: where the environment says, else the package default.
    cache_dir = os.environ.get(jax_runtime.CACHE_DIR_ENV) or \
        jax_runtime.default_cache_dir()
    marker = uuid.uuid4().hex
    workdir = tempfile.mkdtemp(prefix='chip_smoke_')  # launcher state
    # Full child logs outlive the run (the chip tool copies
    # chiprun_out/ back); failures also print their last lines.
    log_dir = os.path.join(REPO, 'chiprun_out', 'chip_smoke',
                           time.strftime('%Y%m%d-%H%M%S'))
    os.makedirs(log_dir, exist_ok=True)
    env = dict(os.environ)
    env.update({
        'JAX_PLATFORMS': mode['platform'],
        'PYTHONPATH': REPO + os.pathsep + env.get('PYTHONPATH', ''),
        'PYTHONUNBUFFERED': '1',
        MARKER_ENV: marker,
        'SKYTPU_STATE_DIR': os.path.join(workdir, 'state'),
        'SKYTPU_FORCE_PYTHON_AGENT': '1',
    })
    log(f'mode={"rehearsal (cpu, tiny)" if args.rehearse_cpu else "chip"}'
        f' JAX_PLATFORMS={mode["platform"]} (environment had '
        f'{os.environ.get("JAX_PLATFORMS")!r}) cache_dir={cache_dir}')
    try:
        server = server_phase(mode, env, log_dir, marker, cache_dir)
        log(f'server ok: {json.dumps(server)}')
        trainer = trainer_phase(mode, env, log_dir, marker, cache_dir)
        log(f'trainer ok: {json.dumps(trainer)}')
    finally:
        kill_marked(marker)
        shutil.rmtree(workdir, ignore_errors=True)
    if 'jax' in sys.modules:
        raise SmokeFailure('the parent process imported jax')
    if server['device'] != trainer['device']:
        raise SmokeFailure(f'children disagree on the device: '
                           f'{server["device"]} vs {trainer["device"]}')
    device = server['device']
    result = {'ok': True,
              'device': {'platform': device['platform'],
                         'kind': device['device_kind'],
                         'count': device['device_count']}}
    if args.rehearse_cpu:
        result['rehearsal'] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    try:
        sys.exit(main())
    except SmokeFailure as failure:
        log(f'FAILED: {failure}')
        sys.exit(1)
