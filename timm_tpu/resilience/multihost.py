"""Multi-process host-loss drill: the executable proof that the `dcn` axis is
real and that a pod survives losing a host mid-epoch.

`run_kill_drill` launches a real N-process JAX cluster on CPU (one device per
process, coordinator on a free localhost port), trains a tiny ViT on the
process-sharded synthetic pipeline with process-local sharded checkpoints,
then SIGKILLs one host mid-epoch via `kill_host@N:P` fault injection. It
asserts the full recovery contract:

  1. the victim dies hard (no recovery save, no consensus vote);
  2. every survivor detects the loss through the KV-store consensus timeout
     (`all_hosts_flag(name=...)`) and exits 0 at the SAME update;
  3. the survivor's post-loss recovery save writes its shard but CANNOT
     commit (the `mode='all'` barrier fails on the dead peer), so the
     previous committed checkpoint remains the newest valid one — the
     manifest-commit ordering is crash-safe by construction;
  4. `--resume auto --elastic` on a fresh (smaller) cluster re-places the
     host-sharded checkpoint under the live mesh and finishes the run;
  5. the final parameters match an uninterrupted single-process baseline.

Used by tests/test_multihost.py (tier-1) and tests/multihost_drill.py (manual /
slow).
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

__all__ = ['run_kill_drill', 'free_port', 'cluster_env']

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def free_port() -> int:
    """An OS-assigned free TCP port for the cluster coordinator."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(('localhost', 0))
        return s.getsockname()[1]


def cluster_env(process_id: int, num_processes: int, port: int,
                devices_per_process: int = 1,
                barrier_timeout: float = 6.0) -> Dict[str, str]:
    """Environment for one member of a CPU JAX cluster (train.py
    --distributed reads COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID)."""
    env = dict(os.environ)
    env.update({
        'JAX_PLATFORMS': 'cpu',
        'XLA_FLAGS': f'--xla_force_host_platform_device_count={devices_per_process}',
        'COORDINATOR_ADDRESS': f'localhost:{port}',
        'NUM_PROCESSES': str(num_processes),
        'PROCESS_ID': str(process_id),
        # consensus at every update so the victim's death is detected at the
        # same step it happens; short barrier so the drill stays fast
        'TIMM_TPU_PREEMPTION_POLL': '1',
        'TIMM_TPU_BARRIER_TIMEOUT': str(barrier_timeout),
    })
    return env


def _train_cmd(workdir: str, experiment: str, *extra: str,
               model: str = 'test_vit', img_size: int = 32,
               global_batch: int = 8, synthetic_len: int = 64,
               epochs: int = 1, recovery_interval: int = 2) -> List[str]:
    return [
        sys.executable, os.path.join(_REPO, 'train.py'),
        '--synthetic-data', '--model', model, '--img-size', str(img_size),
        '-b', str(global_batch), '--synthetic-len', str(synthetic_len),
        '--epochs', str(epochs), '--opt', 'sgd', '--lr', '0.05',
        '--sched', 'cosine', '--warmup-epochs', '0', '--workers', '1',
        '--log-interval', '50', '--recovery-interval', str(recovery_interval),
        '--output', workdir, '--experiment', experiment, *extra,
    ]


def _run(cmd: List[str], env: Dict[str, str], log_path: str, timeout: int):
    with open(log_path, 'w') as f:
        proc = subprocess.run(cmd, env=env, cwd=_REPO, stdout=f, stderr=subprocess.STDOUT,
                              timeout=timeout)
    with open(log_path) as f:
        return proc.returncode, f.read()


def run_kill_drill(workdir: str, processes: int = 2, kill_update: int = 4,
                   victim: Optional[int] = None, synthetic_len: int = 64,
                   global_batch: int = 8, epochs: int = 1,
                   recovery_interval: int = 2, model: str = 'test_vit',
                   img_size: int = 32, barrier_timeout: float = 6.0,
                   compare: bool = True, resume: bool = True,
                   timeout: int = 420, log=None) -> dict:
    """Run the host-loss drill; returns {'ok', 'checks', 'details'}.

    compare=False / resume=False trims the baseline and resume legs (what is
    left proves bring-up + kill + consensus + commit safety).
    """
    from .durable import load_verified, manifest_path, resolve_auto_resume, verify_checkpoint

    log = log or (lambda m: None)
    checks: Dict[str, bool] = {}
    details: Dict[str, object] = {}
    os.makedirs(workdir, exist_ok=True)
    if victim is None:
        victim = processes - 1  # keep process 0 (the coordinator host) alive
    base_kw = dict(model=model, img_size=img_size, global_batch=global_batch,
                   synthetic_len=synthetic_len, epochs=epochs,
                   recovery_interval=recovery_interval)

    # --- leg 0: uninterrupted single-process baseline -----------------------
    if compare:
        log('baseline: single-process uninterrupted run')
        env = cluster_env(0, 1, free_port(), barrier_timeout=barrier_timeout)
        for k in ('COORDINATOR_ADDRESS', 'NUM_PROCESSES', 'PROCESS_ID'):
            env.pop(k, None)
        rc, _ = _run(_train_cmd(workdir, 'baseline', **base_kw), env,
                     os.path.join(workdir, 'baseline.log'), timeout)
        checks['baseline_ok'] = rc == 0

    # --- leg 1: N-process cluster, kill one host mid-epoch ------------------
    log(f'cluster: {processes} processes, kill_host@{kill_update}:{victim}')
    port = free_port()
    procs, log_paths = [], []
    for p in range(processes):
        lp = os.path.join(workdir, f'pod-p{p}.log')
        log_paths.append(lp)
        cmd = _train_cmd(workdir, 'pod', '--distributed',
                         '--fault-inject', f'kill_host@{kill_update}:{victim}',
                         **base_kw)
        procs.append(subprocess.Popen(
            cmd, env=cluster_env(p, processes, port, barrier_timeout=barrier_timeout),
            cwd=_REPO, stdout=open(lp, 'w'), stderr=subprocess.STDOUT))
    deadline = time.time() + timeout
    rcs = [None] * processes
    try:
        for p, proc in enumerate(procs):
            rcs[p] = proc.wait(timeout=max(1, deadline - time.time()))
    except subprocess.TimeoutExpired:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        rcs = [proc.wait() for proc in procs]
        details['timeout'] = True
    finally:
        for proc in procs:
            if proc.stdout:
                proc.stdout.close()
    logs = []
    for lp in log_paths:
        with open(lp) as f:
            logs.append(f.read())
    details['pod_returncodes'] = rcs
    checks['victim_sigkilled'] = rcs[victim] == -signal.SIGKILL
    checks['survivors_exit0'] = all(rcs[p] == 0 for p in range(processes) if p != victim)
    # every survivor must stop via the consensus path (no signal was sent
    # to it) and report the failed post-loss commit barrier
    survivor_logs = [logs[p] for p in range(processes) if p != victim]
    checks['survivor_consensus'] = all('Preempted during epoch' in sl for sl in survivor_logs)
    checks['uncommitted_post_loss_save'] = any(
        'shard barrier failed' in sl for sl in survivor_logs)

    # --- crash-safety: newest VALID checkpoint is the last committed one ----
    pod_dir = os.path.join(workdir, 'pod')
    resolved = resolve_auto_resume(pod_dir) or ''
    details['resolved_resume'] = resolved
    checks['resume_committed'] = bool(resolved) and verify_checkpoint(resolved)[0]
    # the survivor's post-loss shard (written but never committed) must still
    # be on disk, newer than the resolved checkpoint — proof the manifest is
    # the commit record, not the shard write
    litter = [f for f in os.listdir(pod_dir) if '.shard' in f and f.endswith('.npz')]
    logical = lambda f: f.split('.shard')[0] + '.npz'  # noqa: E731
    uncommitted = [f for f in litter
                   if not os.path.exists(manifest_path(os.path.join(pod_dir, logical(f))))]
    details['uncommitted_shards'] = uncommitted
    checks['uncommitted_litter_ignored'] = (
        bool(uncommitted) and bool(resolved)
        and all(logical(f) != os.path.basename(resolved) for f in uncommitted))

    # --- leg 2: fresh smaller cluster resumes the host-sharded checkpoint ---
    if resume:
        log('resume: single-process --resume auto --elastic from the sharded recovery')
        env = cluster_env(0, 1, free_port(), barrier_timeout=barrier_timeout)
        for k in ('COORDINATOR_ADDRESS', 'NUM_PROCESSES', 'PROCESS_ID'):
            env.pop(k, None)
        rc, out = _run(_train_cmd(workdir, 'pod', '--resume', 'auto', '--elastic', **base_kw),
                       env, os.path.join(workdir, 'resume.log'), timeout)
        checks['resume_ok'] = rc == 0
        checks['resumed_mid_epoch'] = 'Resumed mid-epoch from' in out
        checks['elastic_replaced'] = '[elastic] live topology' in out

    # --- final-state parity against the uninterrupted baseline --------------
    if compare and resume:
        final = os.path.join(workdir, 'pod', 'last.npz')
        ref = os.path.join(workdir, 'baseline', 'last.npz')
        if os.path.exists(final) and os.path.exists(ref):
            import numpy as np
            got, _ = load_verified(final)
            want, _ = load_verified(ref)
            keys = [k for k in want if k.startswith(('state_dict.', 'optimizer.'))]
            diffs = [float(np.max(np.abs(np.asarray(got[k], np.float64)
                                         - np.asarray(want[k], np.float64))))
                     for k in keys if k in got]
            details['max_param_diff'] = max(diffs) if diffs else float('inf')
            checks['final_match'] = (len(diffs) == len(keys) > 0
                                     and details['max_param_diff'] <= 1e-6)
        else:
            checks['final_match'] = False

    ok = all(checks.values())
    if not ok:
        failed = [k for k, v in checks.items() if not v]
        log(f'kill drill FAILED checks: {failed}')
        for p, l in enumerate(logs):
            log(f'--- pod-p{p} tail ---\n' + '\n'.join(l.splitlines()[-15:]))
    return {'ok': ok, 'checks': checks, 'details': details}


if __name__ == '__main__':
    wd = sys.argv[1] if len(sys.argv) > 1 else os.path.join('output', 'multihost_drill')
    result = run_kill_drill(wd, log=lambda m: print(f'[multihost] {m}', flush=True))
    print(json.dumps(result, indent=2, default=str))
    sys.exit(0 if result['ok'] else 1)
