"""
Process control: the parent (``run.py``) never touches the chip; it
starts exactly one child that does, and no path out of the parent leaves
that child, or anything the child started, running:

- the child ends by itself or is ended (:func:`wait_child`,
  :func:`end_child`), and is looked at without being reaped, so that its
  process group's id stays taken while the group is swept;
- then whatever is left of its process group, and whatever process
  still carries the run's token in its environment (a descendant that
  left the group), is killed and waited for (:func:`sweep`);
- a parent that is told to end (SIGTERM, SIGINT, SIGHUP, SIGQUIT) or
  that dies of an exception kills what it started first
  (:func:`guard`);
- a parent that is killed outright (SIGKILL, as a driver does to a run
  that outlasts its limit) cannot do any of that, so the child asks the
  kernel to kill it when its parent dies (``procs/common.die_with_parent``).
"""

import atexit
import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Set

from .manifest import chip_dir

#: what JAX_PLATFORMS held before run.py pinned itself to the CPU
PARENT_PLATFORMS = "CHIPBENCH_PARENT_JAX_PLATFORMS"
UNSET = "<unset>"
#: in the environment of the child and of whatever it starts: the run
#: they belong to, and the process the child must not outlive
TOKEN = "CHIPBENCH_RUN_TOKEN"
PARENT_PID = "CHIPBENCH_PARENT_PID"

#: every child this process started that has not been reaped yet
_started: List[subprocess.Popen] = []


def chip_env() -> Dict[str, str]:
    """The environment of the child: the parent's, with ``JAX_PLATFORMS``
    as the parent found it (the parent holds itself to the CPU so that
    it cannot take the chip; the child must see what the machine
    gives). No fallback: where the machine has no chip the child finds
    none and refuses."""
    env = dict(os.environ)
    found = env.pop(PARENT_PLATFORMS, UNSET)
    if found == UNSET:
        env.pop("JAX_PLATFORMS", None)
    else:
        env["JAX_PLATFORMS"] = found
    return env


def start_child(
    root: str,
    proc: str,
    spec: Dict[str, Any],
    script: Optional[str] = None,
    env: Optional[Dict[str, str]] = None,
) -> subprocess.Popen:
    """``procs/<proc>.py <run_dir>/spec.json`` (or ``script``, for the
    tests' stand-ins) as the leader of a new session, its output in
    ``<run_dir>/child.out``."""
    run_dir = spec["run_dir"]
    os.makedirs(run_dir, exist_ok=True)
    spec_path = os.path.join(run_dir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f, indent=1)
    env = dict(env or chip_env())
    env[TOKEN] = f"chipbench-{os.getpid()}-{time.time_ns()}"
    env[PARENT_PID] = str(os.getpid())
    script = script or os.path.join(chip_dir(root), "procs", f"{proc}.py")
    with open(os.path.join(run_dir, "child.out"), "w") as out:
        child = subprocess.Popen(
            [sys.executable, script, spec_path],
            cwd=root,
            env=env,
            stdout=out,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )
    child.token = env[TOKEN]
    _started.append(child)
    return child


def _exited(child: subprocess.Popen, block: bool = False) -> bool:
    """Whether the child has ended, found out without reaping it: its
    pid, and with it the id of its process group, stays taken until
    :func:`sweep` is through, so a signal to the group cannot reach a
    stranger."""
    if child.returncode is not None:
        return True
    options = os.WEXITED | os.WNOWAIT | (0 if block else os.WNOHANG)
    try:
        return os.waitid(os.P_PID, child.pid, options) is not None
    except ChildProcessError:  # reaped elsewhere
        return True


def _carriers(token: str) -> Set[int]:
    """Every process but this one whose environment holds ``token``."""
    found = set()
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                if token.encode() in f.read():
                    found.add(int(entry))
        except OSError:  # gone meanwhile, or not ours to read
            continue
    return found


def _members(leader: int) -> Set[int]:
    """Every live process (a zombie has ended) of the process group or
    the session that ``leader`` leads. Asked only while ``leader`` has
    not been reaped, so the id is still its own."""
    found = set()
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                # pid (comm) state ppid pgrp session ...; comm may hold ")"
                state, _ppid, pgrp, session = f.read().rsplit(")", 1)[1].split()[:4]
        except (OSError, ValueError, IndexError):
            continue
        if state != "Z" and leader in (int(pgrp), int(session)):
            found.add(int(entry))
    return found


def sweep(child: subprocess.Popen, wait: float = 20.0) -> None:
    """Kill the child's process group and session (the child too, if it
    has not ended) and every process that carries its token, wait until
    none of them is left, and reap the child. Once per child."""
    if child not in _started:
        return
    # reaped already (a poll() saw it end): its ids are no longer its
    # own, so only the token says what belongs to it
    reaped = child.returncode is not None
    deadline = time.monotonic() + wait
    while True:
        left = _carriers(child.token)
        if not reaped:
            try:
                os.killpg(child.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            left |= _members(child.pid)
        for pid in left:
            try:
                os.kill(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        if (not left and _exited(child)) or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    if _exited(child):
        child.wait()  # reaps it
    _started.remove(child)


def wait_child(child: subprocess.Popen) -> int:
    """Wait until the child ends by itself, then :func:`sweep`; returns
    its exit code."""
    _exited(child, block=True)
    sweep(child)
    return child.returncode


def end_child(child: subprocess.Popen, sig: int = signal.SIGTERM, wait: float = 60.0) -> Optional[int]:
    """End the child and whatever it started, and wait until all of it
    has ended: ``sig`` first, SIGKILL to its group if it outlasts
    ``wait``, then :func:`sweep` either way."""
    if not _exited(child):
        try:
            child.send_signal(sig)
        except ProcessLookupError:
            pass
        deadline = time.monotonic() + wait
        while not _exited(child) and time.monotonic() < deadline:
            time.sleep(0.05)
    sweep(child)
    return child.returncode


def end_all() -> None:
    """Kill, and wait for, every child this process still has."""
    for child in list(_started):
        sweep(child)


def _told_to_end(signum: int, _frame: Any) -> None:
    end_all()
    os._exit(128 + signum)


def guard() -> None:
    """For the process that is the command (``run.py`` as a program):
    told to end, or ended by an exception, it kills what it started
    before it goes, and prints no result."""
    for signum in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP, signal.SIGQUIT):
        signal.signal(signum, _told_to_end)
    atexit.register(end_all)


def tail(run_dir: str, lines: int = 30) -> str:
    try:
        with open(os.path.join(run_dir, "child.out")) as f:
            return "".join(f.readlines()[-lines:])
    except OSError:
        return ""


def load_report(run_dir: str) -> Dict[str, Any]:
    with open(os.path.join(run_dir, "report.json")) as f:
        return json.load(f)
