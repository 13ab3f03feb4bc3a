"""Head-node job queue + FIFO scheduler (analog of
``sky/skylet/job_lib.py``).

sqlite DB lives on the head node (``~/.skypilot_tpu/jobs.db``; tests
point SKYTPU_RUNTIME_DIR elsewhere). Statuses mirror the reference
(``sky/skylet/job_lib.py:118-159``). The scheduler spawns one driver
process per job (``skypilot_tpu.runtime.driver``), which gang-starts
the task on every host and enforces kill-all-on-any-failure.
"""
import enum
import getpass
import json
import os
import signal
import subprocess
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu import tpu_logging
from skypilot_tpu.utils import db_utils

logger = tpu_logging.init_logger(__name__)


def runtime_dir() -> str:
    return os.path.expanduser(
        os.environ.get('SKYTPU_RUNTIME_DIR', '~/.skypilot_tpu'))


def _db_path() -> str:
    return os.path.join(runtime_dir(), 'jobs.db')


def log_dir_for(run_timestamp: str) -> str:
    return os.path.join(runtime_dir(), 'sky_logs', run_timestamp)


class JobStatus(enum.Enum):
    """Lifecycle (reference ``sky/skylet/job_lib.py:118-159``)."""
    INIT = 'INIT'
    PENDING = 'PENDING'
    SETTING_UP = 'SETTING_UP'
    RUNNING = 'RUNNING'
    SUCCEEDED = 'SUCCEEDED'
    FAILED = 'FAILED'          # user code returned non-zero
    FAILED_SETUP = 'FAILED_SETUP'
    FAILED_DRIVER = 'FAILED_DRIVER'  # driver process died
    CANCELLED = 'CANCELLED'

    def is_terminal(self) -> bool:
        return self in _TERMINAL

    @classmethod
    def nonterminal_statuses(cls) -> List['JobStatus']:
        return [s for s in cls if not s.is_terminal()]


_TERMINAL = {JobStatus.SUCCEEDED, JobStatus.FAILED,
             JobStatus.FAILED_SETUP, JobStatus.FAILED_DRIVER,
             JobStatus.CANCELLED}


def _create_tables(cursor, conn):
    cursor.execute("""\
        CREATE TABLE IF NOT EXISTS jobs (
        job_id INTEGER PRIMARY KEY AUTOINCREMENT,
        job_name TEXT,
        username TEXT,
        submitted_at REAL,
        status TEXT,
        run_timestamp TEXT,
        start_at REAL DEFAULT null,
        end_at REAL DEFAULT null,
        resources TEXT,
        pid INTEGER DEFAULT null,
        spec_path TEXT DEFAULT null)""")
    # procs: JSON [[ip, agent_port, proc_id], ...] — the gang's
    # agent-side processes, recorded by the driver during gang start.
    # Task processes run in their OWN sessions on each host
    # (agent.py /run), so killing the driver's process group does NOT
    # reach them; cancellation and dead-driver cleanup kill them
    # through this record (kill_job_processes).
    db_utils.add_column_to_table(cursor, conn, 'jobs', 'procs', 'TEXT')
    conn.commit()


_conns: Dict[str, db_utils.SQLiteConn] = {}


def _db() -> db_utils.SQLiteConn:
    path = _db_path()
    conn = _conns.get(path)
    if conn is None or conn.db_path != path:
        # Host-local per-cluster store, NOT the control plane — but
        # opened through the engine so WAL/busy_timeout tuning lives
        # in exactly one place (state/engine.py apply_pragmas).
        from skypilot_tpu.state import engine as state_engine
        conn = state_engine.open_db(path, _create_tables)
        _conns[path] = conn
    return conn


def queue_lock():
    """Inter-process lock for composite read-modify-write sequences on
    the job queue (skylet's scheduler vs codegen submit both mutate
    jobs.db — sqlite serializes single statements, not
    check-then-act; analog of ``sky/skylet/job_lib.py:37``)."""
    from skypilot_tpu.utils import timeline
    os.makedirs(runtime_dir(), exist_ok=True)
    return timeline.FileLockEvent(
        os.path.join(runtime_dir(), '.jobs.lock'))


# -- queue ops ---------------------------------------------------------


def add_job(job_name: Optional[str], run_timestamp: str,
            resources_str: str = '', spec_path: Optional[str] = None,
            username: Optional[str] = None) -> int:
    db = _db()
    try:
        db.cursor.execute(
            'INSERT INTO jobs (job_name, username, submitted_at, '
            'status, run_timestamp, resources, spec_path) '
            'VALUES (?,?,?,?,?,?,?)',
            (job_name or '-', username or getpass.getuser(),
             time.time(), JobStatus.PENDING.value, run_timestamp,
             resources_str, spec_path))
        job_id = db.cursor.lastrowid
    finally:
        db.conn.commit()
    assert job_id is not None
    return int(job_id)


def set_status(job_id: int, status: JobStatus) -> None:
    db = _db()
    now = time.time()
    if status == JobStatus.RUNNING:
        db.execute_and_commit(
            'UPDATE jobs SET status=?, start_at=COALESCE(start_at, ?) '
            'WHERE job_id=?', (status.value, now, job_id))
    elif status.is_terminal():
        db.execute_and_commit(
            'UPDATE jobs SET status=?, end_at=? WHERE job_id=?',
            (status.value, now, job_id))
    else:
        db.execute_and_commit(
            'UPDATE jobs SET status=? WHERE job_id=?',
            (status.value, job_id))


def set_pid(job_id: int, pid: int) -> None:
    _db().execute_and_commit('UPDATE jobs SET pid=? WHERE job_id=?',
                             (pid, job_id))


def set_procs(job_id: int, procs: List[tuple]) -> None:
    """Record the gang's agent-side processes: [(ip, agent_port,
    proc_id), ...]."""
    import json as json_lib
    _db().execute_and_commit('UPDATE jobs SET procs=? WHERE job_id=?',
                             (json_lib.dumps(procs), job_id))


def get_procs(job_id: int) -> List[tuple]:
    import json as json_lib
    row = _db().cursor.execute(
        'SELECT procs FROM jobs WHERE job_id=?', (job_id,)).fetchone()
    if not row or not row[0]:
        return []
    return [tuple(p) for p in json_lib.loads(row[0])]


def kill_job_processes(job_id: int, wait_seconds: float = 5.0
                       ) -> None:
    """Kill a job's agent-side rank processes through the host
    agents. Idempotent and best-effort: used by cancellation and by
    dead-controller reconciliation — a driver killed by SIGKILL (no
    handler ran) leaves its ranks running, and for a managed-jobs
    controller a surviving rank keeps LAUNCHING task clusters,
    racing (and beating) the teardown that reconcile queued."""
    procs = get_procs(job_id)
    if not procs:
        return
    rec = get_job(job_id)
    token = None
    if rec and rec.get('spec_path') and \
            os.path.exists(rec['spec_path']):
        import json as json_lib
        with open(rec['spec_path'], encoding='utf-8') as f:
            token = json_lib.load(f).get('agent_token')
    from skypilot_tpu.runtime.agent_client import AgentClient
    clients = []
    for (ip, port, proc_id) in procs:
        try:
            client = AgentClient(ip, port, token=token)
            client.kill(proc_id)
            clients.append((client, proc_id))
        except Exception:  # pylint: disable=broad-except
            pass  # host gone is fine — the process died with it
    # SIGTERM is asynchronous: wait for confirmed exit so callers can
    # act on "the controller is dead" (e.g. reap its task cluster)
    # without racing its final writes. Bounded — a wedged process
    # can't hold the reconcile hostage.
    deadline = time.time() + wait_seconds
    for client, proc_id in clients:
        while time.time() < deadline:
            try:
                if not client.status(proc_id).get('running'):
                    break
            except Exception:  # pylint: disable=broad-except
                break
            time.sleep(0.1)


def get_status(job_id: int) -> Optional[JobStatus]:
    row = _db().cursor.execute(
        'SELECT status FROM jobs WHERE job_id=?', (job_id,)).fetchone()
    return JobStatus(row[0]) if row else None


def get_job(job_id: int) -> Optional[Dict[str, Any]]:
    row = _db().cursor.execute(
        'SELECT job_id, job_name, username, submitted_at, status, '
        'run_timestamp, start_at, end_at, resources, pid, spec_path '
        'FROM jobs WHERE job_id=?', (job_id,)).fetchone()
    return _row_to_record(row) if row else None


def _row_to_record(row) -> Dict[str, Any]:
    (job_id, job_name, username, submitted_at, status, run_timestamp,
     start_at, end_at, resources, pid, spec_path) = row
    return {
        'job_id': job_id,
        'job_name': job_name,
        'username': username,
        'submitted_at': submitted_at,
        'status': JobStatus(status),
        'run_timestamp': run_timestamp,
        'start_at': start_at,
        'end_at': end_at,
        'resources': resources,
        'pid': pid,
        'spec_path': spec_path,
    }


def get_jobs(statuses: Optional[List[JobStatus]] = None
             ) -> List[Dict[str, Any]]:
    db = _db()
    if statuses is None:
        rows = db.cursor.execute(
            'SELECT job_id, job_name, username, submitted_at, status, '
            'run_timestamp, start_at, end_at, resources, pid, '
            'spec_path FROM jobs ORDER BY job_id DESC').fetchall()
    else:
        qmarks = ','.join('?' * len(statuses))
        rows = db.cursor.execute(
            'SELECT job_id, job_name, username, submitted_at, status, '
            'run_timestamp, start_at, end_at, resources, pid, '
            f'spec_path FROM jobs WHERE status IN ({qmarks}) '
            'ORDER BY job_id DESC',
            tuple(s.value for s in statuses)).fetchall()
    return [_row_to_record(r) for r in rows]


def get_latest_job_id() -> Optional[int]:
    row = _db().cursor.execute(
        'SELECT job_id FROM jobs ORDER BY job_id DESC LIMIT 1'
    ).fetchone()
    return int(row[0]) if row else None


def cancel_jobs(job_ids: Optional[List[int]] = None,
                only_if_statuses: Optional[List['JobStatus']] = None
                ) -> List[int]:
    """Cancel given jobs (default: all non-terminal). Kills driver
    process groups. ``only_if_statuses`` restricts cancellation to
    jobs whose status — re-read under the queue lock, so the check is
    atomic with the kill — is in the set; jobs that raced past it
    (e.g. a queued controller the scheduler just started) are left
    alone and reported by omission from the returned list."""
    with queue_lock():
        if job_ids is None:
            records = get_jobs(JobStatus.nonterminal_statuses())
            job_ids = [r['job_id'] for r in records]
        cancelled = []
        for job_id in job_ids:
            rec = get_job(job_id)
            if rec is None or rec['status'].is_terminal():
                continue
            if only_if_statuses is not None and \
                    rec['status'] not in only_if_statuses:
                continue
            pid = rec['pid']
            if pid:
                try:
                    os.killpg(os.getpgid(pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
            set_status(job_id, JobStatus.CANCELLED)
            cancelled.append(job_id)
    # The driver's SIGTERM handler gang-kills its ranks, but don't
    # bet on it having run (SIGKILL, handler raced at startup): kill
    # the recorded agent-side processes directly. Outside the queue
    # lock — these are HTTP calls to the host agents — and in
    # parallel with one shared wait budget: a cancel-all of many
    # jobs must stay well inside the backend's 60 s RPC timeout.
    if cancelled:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(
                max_workers=min(16, len(cancelled))) as ex:
            list(ex.map(kill_job_processes, cancelled))
    return cancelled


def is_cluster_idle(idle_minutes: int) -> bool:
    """No non-terminal jobs, and the last job ended more than
    ``idle_minutes`` ago (reference ``job_lib.py:717``)."""
    active = get_jobs(JobStatus.nonterminal_statuses())
    if active:
        return False
    rows = _db().cursor.execute(
        'SELECT MAX(COALESCE(end_at, submitted_at)) FROM jobs'
    ).fetchone()
    last = rows[0] if rows and rows[0] is not None else 0.0
    return (time.time() - last) >= idle_minutes * 60


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def update_job_statuses() -> None:
    """Reconcile: RUNNING/SETTING_UP jobs whose driver died become
    FAILED_DRIVER (reference ``job_lib.update_job_status:555``)."""
    for rec in get_jobs([JobStatus.SETTING_UP, JobStatus.RUNNING]):
        pid = rec['pid']
        if pid is not None and not _pid_alive(pid):
            logger.warning('Job %s driver (pid %s) died; marking '
                           'FAILED_DRIVER', rec['job_id'], pid)
            set_status(rec['job_id'], JobStatus.FAILED_DRIVER)


def job_slots() -> int:
    """Concurrent job slots on this cluster. 1 (default) for TPU
    clusters — a slice is one atomic allocation, concurrent jobs would
    fight over chips. CPU-only clusters (e.g. the managed-jobs
    controller cluster) get more via SKYTPU_JOB_SLOTS, set by the
    backend at skylet start (the reference sizes controller
    concurrency the same way, ``sky/jobs/scheduler.py:257``)."""
    val = os.environ.get('SKYTPU_JOB_SLOTS')
    if val is None:
        # Persisted at provision by the backend (survives skylet
        # restarts and reaches every process using this runtime dir).
        try:
            with open(os.path.join(runtime_dir(), 'job_slots'),
                      encoding='utf-8') as f:
                val = f.read().strip()
        except OSError:
            return 1
    try:
        return max(1, int(val))
    except ValueError:
        return 1


class FIFOScheduler:
    """FIFO with ``job_slots()`` concurrent slots (1 on TPU
    clusters; the reference serializes via Ray resource accounting, we
    serialize explicitly)."""

    def schedule_step(self) -> Optional[int]:
        # check-active-then-start must be atomic across processes: a
        # codegen submit's eager schedule and skylet's periodic
        # schedule racing here would double-start a driver.
        with queue_lock():
            update_job_statuses()
            active = get_jobs([JobStatus.SETTING_UP, JobStatus.RUNNING,
                               JobStatus.INIT])
            if len(active) >= job_slots():
                return None
            pending = get_jobs([JobStatus.PENDING])
            if not pending:
                return None
            job = pending[-1]  # oldest (list is DESC)
            return self._start_driver(job)

    def _start_driver(self, job: Dict[str, Any]) -> int:
        job_id = job['job_id']
        set_status(job_id, JobStatus.INIT)
        log_dir = log_dir_for(job['run_timestamp'])
        os.makedirs(log_dir, exist_ok=True)
        driver_log = os.path.join(log_dir, 'driver.log')
        env = dict(os.environ)
        env['SKYTPU_RUNTIME_DIR'] = runtime_dir()
        with open(driver_log, 'a', encoding='utf-8') as f:
            proc = subprocess.Popen(
                ['python', '-m', 'skypilot_tpu.runtime.driver',
                 '--job-id', str(job_id)],
                stdout=f, stderr=subprocess.STDOUT,
                start_new_session=True, env=env)
        set_pid(job_id, proc.pid)
        logger.debug('Started driver pid %d for job %d', proc.pid,
                     job_id)
        return job_id


def format_job_queue(records: List[Dict[str, Any]]) -> str:
    from skypilot_tpu.utils import ux_utils
    table = ux_utils.Table(['ID', 'NAME', 'USER', 'SUBMITTED',
                            'STARTED', 'STATUS'])
    for r in records:
        table.add_row([
            r['job_id'], r['job_name'], r['username'],
            _fmt_ts(r['submitted_at']), _fmt_ts(r['start_at']),
            r['status'].value
        ])
    return table.get_string()


def _fmt_ts(ts: Optional[float]) -> str:
    if not ts:
        return '-'
    return time.strftime('%Y-%m-%d %H:%M:%S', time.localtime(ts))
