"""Start, watch, signal and stop the daemon under test.

The daemon is the program's own ``tsd`` command with the argv its
config file gives, started through ``benchmarks/tsd_traced.py`` (which
runs ``opentsdb_tpu.tools.cli.main`` unchanged and only adds two
dormant signal handlers: one that writes the device's memory figures,
one that records a profiler trace). It is the one process that touches
JAX while it lives.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time


class DaemonFailure(Exception):
    pass


# How the daemon's process is started. The tests of benchmarks/tests put
# their own launcher here, one that breaks a guarantee first.
LAUNCHER = ["-m", "benchmarks.tsd_traced"]


class Daemon:
    def __init__(self, repo: str, cfg: dict, work: str, name: str):
        self.repo, self.cfg, self.work, self.name = repo, cfg, work, name
        self.sig_dir = os.path.join(work, name + ".sig")
        self.logpath = os.path.join(work, name + ".log")
        self.proc: subprocess.Popen | None = None
        self.port = 0

    def start(self) -> None:
        os.makedirs(self.sig_dir, exist_ok=True)
        os.makedirs(os.path.join(self.work, "qcache"), exist_ok=True)
        argv = [a.format(store=os.path.join(self.work, "store"),
                         qcache=os.path.join(self.work, "qcache"))
                for a in self.cfg["daemon"]]
        cmd = [sys.executable, *LAUNCHER, "--signal-dir", self.sig_dir,
               "--"] + argv
        env = dict(os.environ)
        env["PYTHONPATH"] = self.repo + os.pathsep + env.get(
            "PYTHONPATH", "")
        with open(self.logpath, "w") as logf:
            self.proc = subprocess.Popen(cmd, cwd=self.repo, env=env,
                                         stdout=logf,
                                         stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(self.logpath) as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def wait_ready(self, deadline: float) -> int:
        """The port from the daemon's complete ready line."""
        while True:
            with open(self.logpath) as f:
                for ln in f:
                    if ln.startswith("Ready to serve on ") \
                            and ln.endswith("\n"):
                        self.port = int(ln.strip().rsplit(":", 1)[1])
                        return self.port
            if self.proc.poll() is not None:
                raise DaemonFailure(
                    f"{self.name} exited {self.proc.returncode} during "
                    f"startup:\n{self.log_tail()}")
            if time.monotonic() > deadline:
                raise DaemonFailure(f"{self.name} not ready in time:\n"
                                    f"{self.log_tail()}")
            time.sleep(0.2)

    def _signal_file(self, signum: int, fname: str, timeout: float):
        """Send ``signum`` and wait for the launcher to write ``fname``."""
        path = os.path.join(self.sig_dir, fname)
        if os.path.exists(path):
            os.unlink(path)
        self.proc.send_signal(signum)
        t_end = time.monotonic() + timeout
        while not os.path.exists(path):
            if self.proc.poll() is not None or time.monotonic() > t_end:
                raise DaemonFailure(
                    f"{self.name} did not write {fname}:\n{self.log_tail()}")
            time.sleep(0.05)
        with open(path) as f:
            return json.load(f)

    def memory(self) -> dict:
        return self._signal_file(signal.SIGUSR1, "memory.json", 30.0)

    def start_trace(self) -> None:
        self.proc.send_signal(signal.SIGUSR2)

    def trace_result(self, timeout: float) -> dict:
        path = os.path.join(self.sig_dir, "trace.json")
        t_end = time.monotonic() + timeout
        while not os.path.exists(path):
            if self.proc.poll() is not None or time.monotonic() > t_end:
                raise DaemonFailure(
                    f"{self.name} wrote no trace:\n{self.log_tail()}")
            time.sleep(0.1)
        with open(path) as f:
            return json.load(f)

    def stop_trace(self) -> None:
        """End the trace: the launcher records until this file is there."""
        with open(os.path.join(self.sig_dir, "trace.stop"), "w"):
            pass

    def kill(self) -> None:
        """SIGKILL and reap. How every run ends its daemon: the store
        is a throw-away copy, and a load cell's kill is the crash it
        recounts after."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
        if self.proc is not None:
            self.proc.wait()

    def scan_log(self) -> str:
        """A traceback or a failed upload in what the daemon logged once
        it was ready. (Before that, a timer checkpoint that fires while
        the device window is still being refilled trips over a TSDB
        that is not yet whole and logs a traceback; the thread lives on
        and the next checkpoint works. PERF.md lists it.)"""
        text = self.log_tail(1 << 30)
        text = text[text.find("Ready to serve on "):]
        for marker in ("Traceback (most recent call last)",
                       "devwindow upload failed"):
            if marker in text:
                at = text.index(marker)
                return text[max(at - 300, 0):at + 1500]
        return ""
