"""Codegen-over-RPC: small python snippets executed on the head node
through the agent's /exec endpoint.

The reference drives its remote job queue the same way — python
snippets over SSH (``JobLibCodeGen``, ``sky/skylet/job_lib.py:930``;
also ServeCodeGen / ManagedJobCodeGen). Here the transport is the
host agent instead of raw SSH, which keeps one channel for both
control and logs.
"""
import json
import shlex
from typing import Any, Dict, List, Optional


def _wrap(runtime_dir: str, body: str) -> str:
    """Run a python snippet with the head's runtime dir exported."""
    return (f'SKYTPU_RUNTIME_DIR={shlex.quote(runtime_dir)} '
            f'python3 -c {shlex.quote(body)}')


# Controller-side state (managed-jobs DB, serve DB, shipped DAGs/task
# yamls, archived logs) lives in this subdir of the controller
# cluster's runtime dir; jobs/serve codegen snippets and controller
# task run commands all derive SKYTPU_STATE_DIR from it.
CONTROLLER_STATE_SUBDIR = 'managed'

_CONTROLLER_PRELUDE = f'''\
import json, os
_rdir = os.path.expanduser(os.environ['SKYTPU_RUNTIME_DIR'])
os.environ['SKYTPU_STATE_DIR'] = os.path.join(
    _rdir, {CONTROLLER_STATE_SUBDIR!r})
os.makedirs(os.environ['SKYTPU_STATE_DIR'], exist_ok=True)
'''


def controller_wrap(runtime_dir: str, body: str) -> str:
    """Like _wrap, but the snippet sees the CONTROLLER state dir —
    the transport for ManagedJobCodeGen/ServeCodeGen analogs."""
    return _wrap(runtime_dir, _CONTROLLER_PRELUDE + body)


def controller_state_dir_cmd(runtime_dir: str) -> str:
    """Shell fragment exporting the controller-side state dir (used
    in controller task run commands)."""
    return (f'SKYTPU_STATE_DIR={shlex.quote(runtime_dir)}/'
            f'{CONTROLLER_STATE_SUBDIR}')


def add_and_schedule_job(runtime_dir: str, job_name: str,
                         run_timestamp: str, resources_str: str,
                         spec: Dict[str, Any]) -> str:
    """Write the job spec on the head, enqueue it, kick the scheduler
    once, print the job id."""
    spec_json = json.dumps(spec)
    body = f'''
import json, os
from skypilot_tpu.runtime import job_lib
os.makedirs(job_lib.runtime_dir(), exist_ok=True)
spec = json.loads({spec_json!r})
spec_path = os.path.join(job_lib.runtime_dir(),
                         'specs')
os.makedirs(spec_path, exist_ok=True)
spec_path = os.path.join(spec_path, {run_timestamp!r} + '.json')
with open(spec_path, 'w') as f:
    json.dump(spec, f)
job_id = job_lib.add_job({job_name!r}, {run_timestamp!r},
                         {resources_str!r}, spec_path)
job_lib.FIFOScheduler().schedule_step()
print('JOB_ID:' + str(job_id))
'''
    return _wrap(runtime_dir, body)


def get_job_status(runtime_dir: str, job_id: int) -> str:
    body = f'''
from skypilot_tpu.runtime import job_lib
job_lib.update_job_statuses()
job_lib.FIFOScheduler().schedule_step()
s = job_lib.get_status({job_id})
print('STATUS:' + (s.value if s else 'None'))
'''
    return _wrap(runtime_dir, body)


def get_job_queue(runtime_dir: str) -> str:
    body = '''
import json
from skypilot_tpu.runtime import job_lib
job_lib.update_job_statuses()
records = job_lib.get_jobs()
out = [{k: (v.value if hasattr(v, 'value') else v)
        for k, v in r.items()} for r in records]
print('QUEUE:' + json.dumps(out))
'''
    return _wrap(runtime_dir, body)


def cancel_jobs(runtime_dir: str,
                job_ids: Optional[List[int]] = None) -> str:
    ids = 'None' if job_ids is None else repr(list(job_ids))
    body = f'''
import json
from skypilot_tpu.runtime import job_lib
print('CANCELLED:' + json.dumps(job_lib.cancel_jobs({ids})))
'''
    return _wrap(runtime_dir, body)


def set_autostop(runtime_dir: str, idle_minutes: int, down: bool,
                 stop_command: str) -> str:
    body = f'''
from skypilot_tpu.runtime import autostop_lib
autostop_lib.set_autostop({idle_minutes}, {down!r}, {stop_command!r})
print('AUTOSTOP:ok')
'''
    return _wrap(runtime_dir, body)


def get_log_path(runtime_dir: str, job_id: int) -> str:
    body = f'''
import os
from skypilot_tpu.runtime import job_lib
rec = job_lib.get_job({job_id})
if rec is None:
    print('LOG:')
else:
    print('LOG:' + os.path.join(
        job_lib.log_dir_for(rec['run_timestamp']), 'run.log'))
'''
    return _wrap(runtime_dir, body)


def parse_tagged(output: str, tag: str) -> Optional[str]:
    """Extract 'TAG:value' from exec output."""
    for line in output.splitlines():
        if line.startswith(tag + ':'):
            return line[len(tag) + 1:]
    return None
