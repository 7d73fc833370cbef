"""Tier-4 integration: real OS processes running the standalone agent over
TCP (the reference's RapidNodeRunner / RapidNodeRunnerTest:
integration-tests spawn `java -jar standalone-agent.jar` subprocesses and
assert liveness; here: `python examples/standalone_agent.py`)."""

import os
import re
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
AGENT = REPO / "examples" / "standalone_agent.py"
def free_ports(count: int):
    """Kernel-assigned ports for agent subprocesses: fixed ranges collide
    with whatever else runs on the host (a concurrent suite run flaked
    exactly that way). Reserve-then-release via the shared helper."""
    from helpers import free_endpoints

    return [ep.port for ep in free_endpoints(count)]


class AgentRunner:
    """Spawn/kill agent subprocesses (RapidNodeRunner.java:63-122 semantics:
    forcible kill on teardown, log-scraped assertions)."""

    def __init__(self, tmp_path: Path):
        self.tmp_path = tmp_path
        self.procs = {}

    def spawn(self, port: int, seed_port: int, role: str = "", extra=()) -> None:
        log = open(self.tmp_path / f"agent-{port}.log", "wb")
        env = dict(os.environ)
        env["PYTHONPATH"] = f"{REPO}:{env.get('PYTHONPATH', '')}"
        env["JAX_PLATFORMS"] = "cpu"  # agents are host processes: no chip
        args = [
            sys.executable, str(AGENT),
            "--listen-address", f"127.0.0.1:{port}",
            "--seed-address", f"127.0.0.1:{seed_port}",
            "--report-interval", "0.25",
        ]
        if role:
            args += ["--role", role]
        args += list(extra)
        self.procs[port] = subprocess.Popen(
            args, stdout=log, stderr=subprocess.STDOUT, env=env, cwd=str(REPO)
        )

    def kill(self, port: int, sig=signal.SIGKILL) -> None:
        proc = self.procs.pop(port, None)
        if proc is not None:
            proc.send_signal(sig)
            proc.wait(timeout=10)

    def teardown(self) -> None:
        for port in list(self.procs):
            self.kill(port)

    def latest_membership_size(self, port: int):
        log_path = self.tmp_path / f"agent-{port}.log"
        if not log_path.exists():
            return None
        sizes = re.findall(rb"membership size: (\d+)", log_path.read_bytes())
        return int(sizes[-1]) if sizes else None

    def wait_for_size(self, ports, size, timeout_s=60.0) -> bool:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if all(self.latest_membership_size(p) == size for p in ports):
                return True
            time.sleep(0.25)
        return False


@pytest.fixture
def runner(tmp_path):
    r = AgentRunner(tmp_path)
    yield r
    r.teardown()


def test_single_agent_starts(runner):
    (port,) = free_ports(1)
    runner.spawn(port, port)
    assert runner.wait_for_size([port], 1, timeout_s=30)
    assert runner.procs[port].poll() is None  # still alive


def test_five_agents_converge_and_survive_a_kill(runner):
    # Ports are allocated immediately before their spawns: reserving the
    # whole set up-front would widen the reserve-then-release race (the
    # running seed's outbound ephemeral connections draw from the same
    # kernel range the reserved ports were released back into).
    (seed_port,) = free_ports(1)
    runner.spawn(seed_port, seed_port)
    assert runner.wait_for_size([seed_port], 1, timeout_s=30)
    ports = [seed_port] + free_ports(4)
    for port in ports[1:]:
        runner.spawn(port, ports[0])
    assert runner.wait_for_size(ports, 5, timeout_s=90)

    # Hard-kill one member; survivors converge to 4 via failure detection
    # (PingPong FD: ~10 intervals) + consensus.
    victim = ports[2]
    runner.kill(victim)
    survivors = [p for p in ports if p != victim]
    assert runner.wait_for_size(survivors, 4, timeout_s=120)


@pytest.mark.slow
def test_ten_agents_converge(runner):
    # RapidNodeRunnerTest's 10-JVM bring-up (RapidNodeRunnerTest.java:28-57):
    # ten real OS processes join through one seed and all converge on the
    # same membership size.
    # Rides the unfiltered check.sh pass (~26 s wall of real-process
    # bring-up); the five-agent converge+kill and windowed-FD kill tests
    # keep the multiprocess path in tier-1.
    (seed_port,) = free_ports(1)
    runner.spawn(seed_port, seed_port)
    assert runner.wait_for_size([seed_port], 1, timeout_s=30)
    ports = [seed_port] + free_ports(9)
    for port in ports[1:]:
        runner.spawn(port, ports[0])
    assert runner.wait_for_size(ports, 10, timeout_s=90)
    for port in ports:
        assert runner.procs[port].poll() is None  # every agent still alive


def test_windowed_fd_agents_detect_kill(runner):
    # Real processes on the PAPER's failure-detection policy (--fd windowed):
    # a SIGKILLed member is detected and evicted by the survivors.
    (seed_port,) = free_ports(1)
    runner.spawn(seed_port, seed_port, extra=["--fd", "windowed"])
    assert runner.wait_for_size([seed_port], 1, timeout_s=30)
    ports = [seed_port] + free_ports(2)
    for port in ports[1:]:
        runner.spawn(port, ports[0], extra=["--fd", "windowed"])
    assert runner.wait_for_size(ports, 3, timeout_s=60)
    runner.kill(ports[2], signal.SIGKILL)
    assert runner.wait_for_size(ports[:2], 2, timeout_s=90)
