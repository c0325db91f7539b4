"""(d) Process control: no path out of the command leaves the child that
holds the chip, or anything that child started, running. Stand-ins for
the children, no JAX: a child that leaves descendants behind, a parent
that is told to end, and a parent that is killed outright."""

import os
import signal
import subprocess
import sys
import time

import pytest

from harness import child as control
from harness import manifest


#: a child that starts a descendant (which writes its pid and sleeps),
#: waits until the pid is written, then ends as the spec says
LEAVES_A_DESCENDANT = """
import json, os, signal, subprocess, sys, time
sys.path.insert(0, {procs!r})
import common
common.die_with_parent()
spec = json.load(open(sys.argv[1]))
sleeper = "import os, sys, time; open(sys.argv[1], 'w').write(str(os.getpid())); time.sleep(600)"
env = dict(os.environ)
if spec["descendant"] == "without_the_token":
    env = {{k: v for k, v in env.items() if not k.startswith("CHIPBENCH")}}
subprocess.Popen(
    [sys.executable, "-c", sleeper, spec["pid_file"]], env=env,
    start_new_session=spec["descendant"] == "in_a_session_of_its_own",
)
while not os.path.exists(spec["pid_file"]) or not open(spec["pid_file"]).read():
    time.sleep(0.02)
if spec["ends"] == "ignores_sigterm":
    signal.signal(signal.SIGTERM, signal.SIG_IGN)
if spec["ends"] != "by_itself":
    open(spec["pid_file"] + ".ready", "w").write(str(os.getpid()))
    time.sleep(600)
"""

#: run.py's part: guard, start the child, say it is up, wait for it
PARENT = """
import sys
sys.path.insert(0, {chip_dir!r})
from harness import child
child.guard()
started = child.start_child({root!r}, "stand_in", {spec!r}, script={script!r})
print(started.pid, flush=True)
child.wait_child(started)
"""


def alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def gone(pid: int, within: float = 10.0) -> bool:
    deadline = time.monotonic() + within
    while alive(pid) and time.monotonic() < deadline:
        time.sleep(0.05)
    return not alive(pid)


def read_pid(path: str, within: float = 20.0) -> int:
    deadline = time.monotonic() + within
    while time.monotonic() < deadline:
        try:
            with open(path) as f:
                return int(f.read())
        except (OSError, ValueError):
            time.sleep(0.05)
    raise AssertionError(f"{path} was never written")


@pytest.fixture
def stand_in(tmp_path):
    script = tmp_path / "stand_in.py"
    script.write_text(
        LEAVES_A_DESCENDANT.format(procs=os.path.join(manifest.CHIP_DIR, "procs"))
    )

    def spec(descendant: str, ends: str) -> dict:
        return {"run_dir": str(tmp_path / "run"), "pid_file": str(tmp_path / "descendant.pid"),
                "descendant": descendant, "ends": ends}

    return str(script), spec


@pytest.mark.parametrize(
    "descendant", ["in_the_childs_group", "in_a_session_of_its_own", "without_the_token"]
)
def test_what_a_child_leaves_behind_is_ended_with_it(stand_in, descendant):
    """The child ends by itself, with exit code 0, and leaves a process
    running: in its group, or out of it but with the run's token, or in
    its group without the token."""
    script, spec = stand_in
    started = control.start_child(str(manifest.ROOT), "stand_in", spec(descendant, "by_itself"), script=script)
    assert control.wait_child(started) == 0
    assert not alive(read_pid(spec(descendant, "by_itself")["pid_file"]))
    assert started not in control._started
    assert control.end_child(started) == 0  # a second end is a no-op


@pytest.mark.parametrize("ends,code", [("on_sigterm", -signal.SIGTERM), ("ignores_sigterm", -signal.SIGKILL)])
def test_end_child_ends_the_child_and_its_descendant(stand_in, ends, code):
    script, spec = stand_in
    given = spec("in_the_childs_group", ends)
    started = control.start_child(str(manifest.ROOT), "stand_in", given, script=script)
    read_pid(given["pid_file"] + ".ready")
    assert control.end_child(started, signal.SIGTERM, wait=1.0) == code
    assert not alive(started.pid) and not alive(read_pid(given["pid_file"]))


@pytest.mark.parametrize("how", [signal.SIGTERM, signal.SIGINT, signal.SIGHUP, signal.SIGKILL])
def test_a_parent_that_is_ended_takes_its_child_along(stand_in, how, tmp_path):
    """SIGTERM, SIGINT, SIGHUP: the parent kills what it started, then
    exits 128 + signal with no result. SIGKILL: the parent can do
    nothing, and the kernel kills the child (``die_with_parent``)."""
    script, spec = stand_in
    given = spec("in_the_childs_group", "on_sigterm")
    program = PARENT.format(chip_dir=manifest.CHIP_DIR, root=str(manifest.ROOT), spec=given, script=script)
    parent = subprocess.Popen(
        [sys.executable, "-c", program], stdout=subprocess.PIPE, text=True, start_new_session=True
    )
    try:
        child_pid = int(parent.stdout.readline())
        read_pid(given["pid_file"] + ".ready")
        parent.send_signal(how)
        code = parent.wait(timeout=30)
        assert code == (-how if how == signal.SIGKILL else 128 + how)
        assert parent.stdout.read() == ""
        assert gone(child_pid)
        if how != signal.SIGKILL:  # the kernel's request covers the child alone
            assert gone(read_pid(given["pid_file"]))
    finally:
        for pid in (parent.pid, read_pid(given["pid_file"])):
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        parent.wait()
