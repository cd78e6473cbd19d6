"""The processes a run starts, and HTTP on keep-alive connections.

Copied from ``chip_smoke.py`` (``Fleet``, ``call``, ``wait_until``,
``child_env``). Every child gets a log file of its own in the run's work
directory, is ended with SIGTERM and waited for; one that needs SIGKILL
fails the run (a killed worker can leave the chip locked for the next
run). Nothing here imports jax.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import threading
import time
from http import client as httplib

STOP_TIMEOUT_S = 60.0


class BenchFailure(Exception):
    """The run cannot produce a result: it exits non-zero and prints no
    result line."""


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T0:7.2f}s] {msg}", flush=True)


T0 = time.monotonic()


class Fleet:
    def __init__(self, workdir: str, cwd: str) -> None:
        self.workdir = workdir
        self.cwd = cwd
        self.procs: dict[str, subprocess.Popen] = {}
        self.killed: list[str] = []

    def log_path(self, tag: str) -> str:
        return os.path.join(self.workdir, f"{tag}.log")

    def spawn(self, tag: str, argv: list[str], env: dict,
              pipes: bool = False) -> subprocess.Popen:
        """``pipes``: the child's stdin and stdout are pipes (the control
        channel of a chip-owning child); its stderr goes to the log."""
        logf = open(self.log_path(tag), "ab")
        try:
            p = subprocess.Popen(
                argv, env=env, cwd=self.cwd, stderr=logf,
                stdin=subprocess.PIPE if pipes else subprocess.DEVNULL,
                stdout=subprocess.PIPE if pipes else logf,
                text=True if pipes else None, bufsize=1 if pipes else -1)
        finally:
            logf.close()
        self.procs[tag] = p
        return p

    def alive(self, tag: str) -> bool:
        return self.procs[tag].poll() is None

    def tail(self, tag: str, n: int = 30) -> str:
        try:
            with open(self.log_path(tag), "rb") as f:
                lines = f.read().decode("utf-8", "replace").splitlines()
        except OSError:
            return ""
        return "\n".join(lines[-n:])

    def wait(self, tag: str, timeout: float) -> None:
        """For a child that ends by itself; anything but exit 0 fails."""
        p = self.procs.pop(tag)
        try:
            rc = p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            raise BenchFailure(f"{tag} did not end within {timeout:.0f}s:\n"
                               + self.tail(tag)) from None
        if rc != 0:
            raise BenchFailure(f"{tag} exited {rc}:\n" + self.tail(tag, 40))

    def stop(self, tag: str) -> None:
        p = self.procs.pop(tag)
        if p.stdin is not None:
            try:
                p.stdin.close()
            except OSError:
                pass
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
        try:
            p.wait(timeout=STOP_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            self.killed.append(tag)
        if p.stdout is not None:
            p.stdout.close()

    def stop_all(self) -> None:
        for tag in list(self.procs)[::-1]:
            self.stop(tag)


_conns = threading.local()


def call(hp: tuple[str, int], method: str, path: str,
         body: bytes | None = None, timeout: float = 60.0):
    """One request on this thread's keep-alive connection to ``hp``;
    returns (status, headers, body). A connection the server closed is
    reopened once."""
    pool = _conns.__dict__.setdefault("pool", {})
    for attempt in (0, 1):
        conn = pool.get(hp)
        if conn is None:
            conn = pool[hp] = httplib.HTTPConnection(*hp, timeout=timeout)
        try:
            conn.timeout = timeout
            if conn.sock is not None:
                conn.sock.settimeout(timeout)
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            return resp.status, dict(resp.getheaders()), resp.read()
        except (httplib.HTTPException, OSError) as e:
            conn.close()
            pool.pop(hp, None)
            if attempt or isinstance(e, TimeoutError):
                raise
    raise AssertionError("unreachable")


def get_json(hp, path: str, timeout: float = 30.0):
    status, _h, body = call(hp, "GET", path, timeout=timeout)
    if status != 200:
        raise BenchFailure(f"GET {path} on {hp[1]}: {status} {body[:200]!r}")
    return json.loads(body)


def wait_until(what: str, pred, timeout: float = 120.0,
               interval: float = 0.1, watch=()) -> None:
    """``watch``: (fleet, tag) pairs that must stay alive meanwhile."""
    deadline = time.monotonic() + timeout
    last: object = None
    while time.monotonic() < deadline:
        for fleet, tag in watch:
            if not fleet.alive(tag):
                raise BenchFailure(f"{tag} exited while waiting for "
                                   f"{what}:\n" + fleet.tail(tag))
        try:
            if pred():
                return
        except (OSError, httplib.HTTPException, BenchFailure,
                ValueError) as e:
            last = e
        time.sleep(interval)
    raise BenchFailure(f"timed out waiting for {what} (last: {last!r})")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(platform: str, **extra: str) -> dict:
    """The environment of a child PINNED to ``platform``, so that a chip
    it cannot get is fatal instead of a quiet CPU fallback."""
    env = dict(os.environ)
    for k in ("XLA_FLAGS", "TFIDF_JAX_PLATFORM", "TFIDF_CPU_DEVICES"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = platform
    env.update(extra)
    return env


class PauseWatch(threading.Thread):
    """A witness of pauses of the whole machine. This process is idle
    while a window runs, so a 5 ms sleep of this thread that comes back
    50 ms late or more was not its own doing: on the chip tool's machine
    every process of a run (leader, worker, coordinator, generators, this
    one) showed the same ~110 ms gaps at the same instants, up to four in
    20 s, and each left a queue that took a second or two to drain
    (PERF.md section 5). ``pauses`` is [(start, milliseconds late)]."""

    SLEEP_S, LATE_S = 0.005, 0.050

    def __init__(self) -> None:
        super().__init__(daemon=True, name="pause-watch")
        self.pauses: list[tuple[float, float]] = []

    def run(self) -> None:
        while True:
            t = time.monotonic()
            time.sleep(self.SLEEP_S)
            late = time.monotonic() - t - self.SLEEP_S
            if late >= self.LATE_S:
                self.pauses.append((t, late * 1e3))

    def within(self, t0: float, t1: float) -> dict:
        mine = [ms for t, ms in list(self.pauses) if t0 <= t < t1]
        return {"count": len(mine), "total_ms": sum(mine)}
