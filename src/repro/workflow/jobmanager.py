"""Local subprocess JobManager — the first *real* ExecutionBackend
(ROADMAP open item 4, COSMOS-style ``Job/models/jobmanager*`` analogue).

``LocalProcessBackend`` runs every task attempt as a child process
(``python -m repro.workflow.selfhost '<payload json>'``), carves the host
into virtual nodes with disjoint cpu-affinity sets and per-node scratch
directories, samples peak RSS while attempts run, and reports measured
wall/cpu/RSS/io back to the control plane in the simulator's TaskTrace
units — so Tarema's label/allocate phases run unchanged on real numbers.

Heterogeneity on one container: ``local_nodes()`` splits the visible cores
disjointly across nodes and alternates scratch between a RAM-backed volume
(/dev/shm) and an on-disk tmpdir, so nodes genuinely differ in the one
resource a shared-kernel host can differentiate (storage), while the
Tarema grouping additionally separates them by their measured profiles.

OOM semantics mirror the simulator's sizing model: an attempt whose
*sampled peak RSS* exceeds its request fails with ``oom=True`` (killed
in-flight when the parent-side sampler catches it, post-hoc otherwise) and
the control plane retries it under an escalated request.  Enforcement is
off by default — measurement is the point; enforcement is for the retry
tests and for hosts where a runaway payload must not take the box down.
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import types
from typing import Optional

from repro.core.profiler import NodeSpec, _host_mem_gb
from repro.workflow.controlplane import (AttemptResult, ExecutionBackend,
                                         ResourceRequest)
from repro.workflow.dag import TaskInstance
from repro.workflow.selfhost import RESULT_TAG, make_runner

_SRC_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclasses.dataclass
class LocalNode:
    """One virtual node of the local machine: a cpu-affinity set, a memory
    budget, and a scratch volume."""
    name: str
    cpus: tuple = ()          # empty = inherit the parent's affinity
    mem_gb: float = 1.0
    scratch: str = ""         # payload + io working dir ("" = default tmp)
    kind: str = "local"       # machine tier label (Tarema groups by it too)

    def spec(self) -> NodeSpec:
        """Capacity view for the control plane's feasibility mask.  The
        speed columns are placeholders — real placement quality comes from
        the *measured* NodeProfiles, not from this declaration."""
        return NodeSpec(self.name, self.kind, max(len(self.cpus), 1),
                        self.mem_gb, cpu_speed=1.0, mem_bw=1.0)


def _ram_scratch() -> Optional[str]:
    for cand in ("/dev/shm", "/run/shm"):
        if os.path.isdir(cand) and os.access(cand, os.W_OK):
            return cand
    return None


def local_nodes(n: int = 2, mem_fraction: float = 0.25,
                scratch_root: Optional[str] = None) -> list:
    """Carve the host into ``n`` virtual nodes: disjoint cpu chunks (every
    node gets at least one core — on a single-core host they share it, and
    heterogeneity comes from scratch placement alone) and alternating
    RAM/disk scratch volumes."""
    avail = sorted(os.sched_getaffinity(0)) if \
        hasattr(os, "sched_getaffinity") else list(range(os.cpu_count() or 1))
    per = max(len(avail) // n, 1)
    mem = max((_host_mem_gb() or 4.0) * mem_fraction, 0.5)
    ram = _ram_scratch()
    disk = scratch_root or tempfile.gettempdir()
    nodes = []
    for i in range(n):
        cpus = tuple(avail[i * per:(i + 1) * per]) or (avail[i % len(avail)],)
        use_ram = ram is not None and i % 2 == 0
        base = ram if use_ram else disk
        scratch = tempfile.mkdtemp(prefix=f"tarema_node{i}_", dir=base)
        nodes.append(LocalNode(
            name=f"local{i}", cpus=cpus, mem_gb=mem, scratch=scratch,
            kind="local-ram" if use_ram else "local-disk"))
    return nodes


@dataclasses.dataclass
class _Attempt:
    task: TaskInstance
    node: LocalNode
    request: ResourceRequest
    proc: subprocess.Popen
    start_s: float
    argv: tuple = ()
    execd: bool = False
    peak_rss_gb: float = 0.0
    killed_oom: bool = False
    attempt_id: int = -1
    out_path: Optional[str] = None    # registry mode: stdout/stderr go to
    err_path: Optional[str] = None    # files that survive a plane crash
    adopted: bool = False


def _has_execd(pid: int, argv: tuple) -> bool:
    """True once /proc/<pid>/cmdline shows OUR argv.  Popen with ``cwd=``
    takes CPython's fork+exec path, and between fork and exec the child's
    /proc entries (VmHWM included) still describe the *parent's* address
    space — sampling there reads the control plane's own multi-GB RSS as
    the child's peak and OOM-kills every attempt.  The cmdline flips to
    the spawned argv exactly at exec, so it gates when samples are real."""
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            cmd = tuple(c.decode("utf-8", "replace")
                        for c in f.read().split(b"\0") if c)
    except OSError:
        return False
    return cmd == argv


def _proc_stat(pid: int) -> Optional[tuple]:
    """(state, starttime) from /proc/<pid>/stat — fields 3 and 22, parsed
    after the comm parens so a ``)`` in the process name can't shift them.
    None once the pid is gone."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            data = f.read().decode("ascii", "replace")
        rest = data.rsplit(")", 1)[1].split()
        return rest[0], int(rest[19])
    except (OSError, ValueError, IndexError):
        return None


def _proc_starttime(pid: int) -> Optional[int]:
    """Kernel start time (clock ticks since boot, /proc/<pid>/stat field
    22) — the identity that survives where pids don't: a recycled pid
    cannot reproduce the dead process's start tick, so
    ``(pid, starttime)`` is safe to persist in the attempt registry and
    re-check after a control-plane restart."""
    st = _proc_stat(pid)
    return None if st is None else st[1]


def _proc_live_starttime(pid: int) -> Optional[int]:
    """Like ``_proc_starttime`` but None for zombies: a zombie has finished
    (its output files are complete) and will never run again, it just
    hasn't been reaped — init reaps orphans promptly, but an adopter that
    shares a live ancestor with the original spawner would otherwise wait
    on the corpse forever."""
    st = _proc_stat(pid)
    return None if st is None or st[0] == "Z" else st[1]


class _ExternalProc:
    """Popen-alike for an adopted orphan (a child of the *crashed* plane,
    not ours).  Liveness comes from /proc identity — pid + start tick, so
    pid reuse never reads a stranger as our attempt — and the exit status
    is unknowable (only a parent can reap it): ``returncode`` is reported
    as 0 and success hinges entirely on the ``TAREMA_RESULT`` line in the
    attempt's registry stdout file, exactly like a normal harvest."""

    def __init__(self, pid: int, starttime: Optional[int]):
        self.pid = pid
        self._starttime = starttime
        self.returncode: Optional[int] = None
        if pid <= 0 or starttime is None:
            self.returncode = 0          # already gone at adoption time

    def _alive(self) -> bool:
        return _proc_live_starttime(self.pid) == self._starttime

    def poll(self) -> Optional[int]:
        if self.returncode is None and not self._alive():
            self.returncode = 0
        return self.returncode

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() >= deadline:
                raise subprocess.TimeoutExpired("adopted-attempt", timeout)
            time.sleep(0.02)
        return self.returncode

    def kill(self) -> None:
        if self.poll() is None:
            try:
                os.kill(self.pid, signal.SIGKILL)
            except OSError:
                pass


def _read_vm_hwm_gb(pid: int) -> float:
    """Parent-side peak-RSS sample of a live child (kB -> GB); 0.0 once the
    process is gone."""
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024.0 ** 2
    except (OSError, ValueError):
        pass
    return 0.0


class LocalProcessBackend(ExecutionBackend):
    """Subprocess JobManager over the local machine's virtual nodes."""

    def __init__(self, nodes: Optional[list] = None, runner=None,
                 python: Optional[str] = None, enforce_requests: bool = False,
                 sample_interval_s: float = 0.02, env: Optional[dict] = None,
                 registry_dir: Optional[str] = None):
        self._nodes = list(nodes) if nodes is not None else local_nodes()
        self._by_name = {n.name: n for n in self._nodes}
        self.runner = runner if runner is not None else make_runner("quick")
        self.python = python or sys.executable
        self.enforce_requests = enforce_requests
        self.sample_interval_s = sample_interval_s
        self._env = dict(os.environ if env is None else env)
        # children run on virtual CPU nodes; a child that reached for the
        # accelerator would fail or hang on the lock of a parent that holds
        # it (a chip belongs to one process)
        self._env["JAX_PLATFORMS"] = "cpu"
        pp = self._env.get("PYTHONPATH", "")
        if _SRC_ROOT not in pp.split(os.pathsep):
            self._env["PYTHONPATH"] = (_SRC_ROOT + os.pathsep + pp) if pp \
                else _SRC_ROOT
        self._running: dict[str, _Attempt] = {}
        # crash-recovery registry: one pidfile + stdout/stderr file per
        # attempt, under the run scratch, so a restarted control plane can
        # re-attach to orphans (pipes die with the parent; files don't)
        self.registry_dir = registry_dir
        if registry_dir:
            os.makedirs(registry_dir, exist_ok=True)

    # ----------------------------------------------------------- protocol
    def nodes(self) -> list:
        return list(self._nodes)

    def nodespecs(self) -> list:
        return [n.spec() for n in self._nodes]

    def launch(self, task: TaskInstance, node: str,
               request: ResourceRequest, attempt_id: int = -1) -> None:
        nd = self._by_name[node]
        payload = dict(self.runner(task, nd))
        payload.setdefault("cpus", list(nd.cpus))
        if nd.scratch:
            payload.setdefault("scratch", nd.scratch)
        argv = [self.python, "-m", "repro.workflow.selfhost",
                json.dumps(payload)]
        out_path = err_path = None
        if self.registry_dir and attempt_id >= 0:
            out_path = self._att_path(attempt_id, "out")
            err_path = self._att_path(attempt_id, "err")
            with open(out_path, "wb") as out_f, \
                    open(err_path, "wb") as err_f:
                proc = subprocess.Popen(argv, stdout=out_f, stderr=err_f,
                                        env=self._env,
                                        cwd=nd.scratch or None)
        else:
            proc = subprocess.Popen(
                argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=self._env, cwd=nd.scratch or None)
        self._running[task.instance] = _Attempt(
            task, nd, request, proc, start_s=time.monotonic(),
            argv=tuple(argv), attempt_id=attempt_id,
            out_path=out_path, err_path=err_path)
        if out_path is not None:
            self._write_registry(task, nd, request, proc, attempt_id)

    # --------------------------------------------------- attempt registry
    def _att_path(self, attempt_id: int, ext: str) -> str:
        return os.path.join(self.registry_dir, f"att{attempt_id}.{ext}")

    def _write_registry(self, task, nd, request, proc, attempt_id) -> None:
        """Persist the attempt's identity (atomic rename): enough for a
        future plane to re-attach (pid + start tick + argv) or post-mortem
        the child's stdout file."""
        meta = {"attempt": attempt_id, "instance": task.instance,
                "node": nd.name, "pid": proc.pid,
                "starttime": _proc_starttime(proc.pid),
                "argv": list(self._running[task.instance].argv),
                "cores": request.cores, "mem_gb": request.mem_gb,
                "start_unix": time.time()}
        path = self._att_path(attempt_id, "json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, path)

    def forget(self, attempt_id: int) -> None:
        """Drop an attempt's registry files.  Called by the control plane
        AFTER the retire record is journaled — never at harvest time: a
        crash between harvest and journal would otherwise leave an attempt
        that is in-flight per the WAL but has no registry to reconcile
        against, i.e. guaranteed loss."""
        if not self.registry_dir or attempt_id < 0:
            return
        for ext in ("json", "out", "err"):
            try:
                os.unlink(self._att_path(attempt_id, ext))
            except OSError:
                pass

    def reconcile(self, attempts: dict) -> tuple:
        """Re-attach to orphaned attempts after a control-plane crash.

        ``attempts`` maps attempt id -> info dict (``instance``, ``node``,
        ``cores``, ``mem_gb``, optional ``task`` carrying the live
        TaskInstance), i.e. the WAL's in-flight launches.  Returns
        ``(adopted, lost)`` splitting those ids: adopted attempts are
        children of the dead plane that are either still running (liveness
        re-checked via pid + start tick, VmHWM sampling resumes) or
        finished while orphaned (their registry stdout file already holds
        the result line) — both surface through ``poll()`` like any other
        attempt.  Lost attempts left no adoptable trace; the control plane
        charges them to the fault-retry budget."""
        adopted: dict = {}
        lost: dict = {}
        for aid, info in attempts.items():
            aid = int(aid)
            meta = self._read_registry(aid)
            if meta is None:
                lost[aid] = info
                continue
            inst = meta["instance"]
            task = info.get("task") or types.SimpleNamespace(instance=inst)
            nd = self._by_name.get(meta["node"])
            if nd is None or inst in self._running:
                lost[aid] = info
                continue
            pid, st = meta.get("pid"), meta.get("starttime")
            alive = (pid is not None and st is not None
                     and _proc_live_starttime(pid) == st)
            if not alive and not self._has_result_line(aid):
                lost[aid] = info       # dead without a result: gone for good
                continue
            proc = _ExternalProc(pid if alive else -1, st if alive else None)
            start_s = time.monotonic() - max(
                time.time() - float(meta.get("start_unix", time.time())), 0.0)
            self._running[inst] = _Attempt(
                task, nd, ResourceRequest(int(meta.get("cores", 1)),
                                          float(meta.get("mem_gb", 0.0))),
                proc, start_s=start_s, argv=tuple(meta.get("argv", ())),
                attempt_id=aid, out_path=self._att_path(aid, "out"),
                err_path=self._att_path(aid, "err"), adopted=True)
            adopted[aid] = info
        return adopted, lost

    def _read_registry(self, attempt_id: int) -> Optional[dict]:
        if not self.registry_dir:
            return None
        try:
            with open(self._att_path(attempt_id, "json")) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _has_result_line(self, attempt_id: int) -> bool:
        try:
            with open(self._att_path(attempt_id, "out"),
                      encoding="utf-8", errors="replace") as f:
                return any(line.startswith(RESULT_TAG) for line in f)
        except OSError:
            return False

    def poll(self, timeout: Optional[float] = None) -> list:
        """Harvest every attempt that has ended; block up to ``timeout``
        seconds for the first one.  Each pass also samples live peak RSS
        (and, with ``enforce_requests``, kills over-request attempts)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            done = []
            for iid, att in list(self._running.items()):
                self._sample(att)
                if att.proc.poll() is not None:
                    del self._running[iid]
                    done.append(self._harvest(att))
            if done or not self._running:
                return done
            if deadline is not None and time.monotonic() >= deadline:
                return []
            time.sleep(self.sample_interval_s)

    def kill(self, instance: str) -> None:
        att = self._running.get(instance)
        if att is not None and att.proc.poll() is None:
            att.proc.kill()

    def close(self) -> None:
        for att in self._running.values():
            if att.proc.poll() is None:
                att.proc.kill()
                att.proc.wait()
        self._running.clear()

    # ----------------------------------------------------------- internals
    def _sample(self, att: _Attempt) -> None:
        if att.proc.poll() is not None:
            return
        if not att.execd:
            if not _has_execd(att.proc.pid, att.argv):
                return          # pre-exec: /proc still shows the parent
            att.execd = True
        hwm = _read_vm_hwm_gb(att.proc.pid)
        if hwm > att.peak_rss_gb:
            att.peak_rss_gb = hwm
        if self.enforce_requests and att.request.mem_gb > 0 \
                and att.peak_rss_gb > att.request.mem_gb \
                and not att.killed_oom:
            att.killed_oom = True
            att.proc.kill()

    def _harvest(self, att: _Attempt) -> AttemptResult:
        if att.out_path is not None:
            # registry mode: stdout/stderr live in files (they survive a
            # plane crash where pipes would not); adopted orphans cannot be
            # reaped, so for them the RESULT line *is* the exit status
            att.proc.wait()
            out = self._slurp(att.out_path)
            err = self._slurp(att.err_path)
        else:
            out, err = att.proc.communicate()
        end_s = time.monotonic()
        rc = att.proc.returncode
        reported = None
        for line in reversed((out or "").splitlines()):
            if line.startswith(RESULT_TAG):
                try:
                    reported = json.loads(line[len(RESULT_TAG):])
                except ValueError:
                    pass
                break
        peak = att.peak_rss_gb
        cpu_s = io_mb = 0.0
        extra: dict = {}
        if reported is not None:
            peak = max(peak, float(reported.get("peak_rss_gb", 0.0)))
            cpu_s = float(reported.get("cpu_s", 0.0))
            io_mb = float(reported.get("io_mb", 0.0))
            extra = reported.get("extra", {}) or {}
        ok = rc == 0 and reported is not None
        # OOM determination, mirroring the simulator's "sampled peak
        # exceeds the sized request" model: the sampler's kill, a kernel
        # OOM kill (SIGKILL), a python MemoryError — or, with enforcement
        # on, a post-hoc peak > request even though the attempt finished
        oom = att.killed_oom or "MemoryError" in (err or "")
        if not oom and rc is not None and -rc == 9:
            oom = True
        if ok and self.enforce_requests and att.request.mem_gb > 0 \
                and peak > att.request.mem_gb:
            ok, oom = False, True
        detail = "" if ok else (
            "oom" if oom else
            f"rc={rc}: {(err or '').strip().splitlines()[-1:] or ['?']}")
        return AttemptResult(
            instance=att.task.instance, node=att.node.name, ok=ok,
            start_s=att.start_s, end_s=end_s, cpu_s=cpu_s,
            peak_rss_gb=peak, io_mb=io_mb, oom=oom,
            detail=str(detail), extra=extra, attempt_id=att.attempt_id)

    @staticmethod
    def _slurp(path: Optional[str]) -> str:
        if path is None:
            return ""
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                return f.read()
        except OSError:
            return ""
