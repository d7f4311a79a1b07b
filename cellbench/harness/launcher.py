"""Start, watch and stop the process that holds the chip.

The parent (cellbench/run.py) never initialises a JAX backend: a chip
belongs to one process at a time, and that process is the server child (and
after it has gone, the reference child). The child runs with
`JAX_PLATFORMS=<platform>`, so JAX fails instead of falling back to the
CPU. A configuration's flags are data (`serving.flags` in its file, with
{model} {port} {seed} {tokenizer} filled in), and so is its entry point
(`serving.entry`: the server itself, or the router that spawns replicas)."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from harness.manifest import BENCH_DIR, ROOT


def state_dir() -> str:
    """Where a run keeps what it makes (logs, traces, tokenizer files): a
    fixed, gitignored directory of the checkout."""
    d = os.path.join(ROOT, ".cellbench")
    os.makedirs(d, exist_ok=True)
    return d


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(platform: str, devices: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS=platform, TOKENIZERS_PARALLELISM="false")
    env.pop("BENCH_RUN", None)  # the driver's own; nothing here may read it
    if platform == "cpu":  # the rehearsal: kernels interpreted, virtual devices
        env.setdefault("DLI_PALLAS_INTERPRET", "1")
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(devices, 1)}"
        )
    return env


class Server:
    def __init__(self, config_path: str, config: dict, seed: int, platform: str,
                 tokenizer_dir: str, tag: str, extra_flags=()):
        self.config = config
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.trace_base = os.path.join(state_dir(), "traces")
        self.log_path = os.path.join(state_dir(), "logs", f"{tag}.log")
        fill = {
            "model": config["name"], "port": str(self.port), "seed": str(seed),
            "tokenizer": tokenizer_dir,
        }
        flags = [f.format(**fill) for f in config["serving"]["flags"]]
        self.cmd = [
            sys.executable, os.path.join(BENCH_DIR, "harness", "serve.py"),
            "--config", config_path, "--trace-dir", self.trace_base, "--",
        ] + flags + list(extra_flags)
        # a router's replicas hold one chip each (the program gives child i
        # chip i); only a single server is handed all of the cell's devices
        single = config["serving"].get("entry", "server") == "server"
        self.env = child_env(platform, int(config["serving"].get("chips", 1)) if single else 1)
        self.proc = None
        self._log = None
        self.ready_s = None

    def start(self, timeout_s: float = 1100.0):
        os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
        os.makedirs(self.trace_base, exist_ok=True)
        self._log = open(self.log_path, "w")
        t0 = time.monotonic()
        self.proc = subprocess.Popen(
            self.cmd, cwd=ROOT, env=self.env, stdout=self._log,
            stderr=subprocess.STDOUT,
        )
        while True:
            if self.proc.poll() is not None:
                raise SystemExit(
                    f"server exited with code {self.proc.returncode} before "
                    f"/ready:\n{self.log_tail()}"
                )
            try:
                with urllib.request.urlopen(self.url + "/ready", timeout=2) as r:
                    if r.status == 200:
                        break
            except (urllib.error.URLError, OSError):
                pass
            if time.monotonic() - t0 > timeout_s:
                self.stop()
                raise SystemExit(f"server not ready after {timeout_s:.0f} s:\n{self.log_tail()}")
            time.sleep(0.25)
        self.ready_s = time.monotonic() - t0
        return self

    def stop(self):
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        if self._log is not None:
            self._log.close()
            self._log = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def log_tail(self, n: int = 40) -> str:
        if self._log is not None:
            self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    WATCHED = ("continuous_loop_crashed", "request_preempted", "queue_full",
               "request_quarantined", "preempt_resume_restored", "slo_shed")

    def log_events(self) -> dict:
        """How often the program logged each event a reader of the result
        should know about (scheduler crashes, preemptions, a full queue)."""
        if self._log is not None:
            self._log.flush()
        counts = {}
        with open(self.log_path, errors="replace") as f:
            for line in f:
                for ev in self.WATCHED:
                    if f'"event": "{ev}"' in line:
                        counts[ev] = counts.get(ev, 0) + 1
        return counts

    def get(self, path: str, raw: bool = False, timeout: float = 60):
        with urllib.request.urlopen(self.url + path, timeout=timeout) as r:
            data = r.read()
        return data if raw else json.loads(data)

    def post(self, path: str, body: dict, timeout: float = 600) -> dict:
        req = urllib.request.Request(
            self.url + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    def _replicas(self) -> list:
        """Base URLs of the processes that hold chips: this server, or, behind
        a router (`serving.entry: "router"`), the replicas it spawned."""
        if "device" in self.get("/health"):
            return [self.url]
        reps = self.get("/stats").get("replicas", {})
        return [r["url"].rstrip("/") for r in reps.values()]

    @staticmethod
    def _get(url: str) -> dict:
        with urllib.request.urlopen(url, timeout=60) as r:
            return json.loads(r.read())

    def device(self) -> dict:
        """{platform, kind, count} of the processes that hold the chips. A
        router's replicas must agree, and the count is their sum."""
        devs = [self._get(u + "/health")["device"] for u in self._replicas()]
        if not devs:
            raise SystemExit("no serving process reports a device")
        if len({(d["platform"], d["kind"]) for d in devs}) != 1:
            raise SystemExit(f"replicas on different devices: {devs}")
        return {**devs[0], "count": sum(d["count"] for d in devs)}

    def memory(self) -> list:
        """[{bytes_in_use, peak_bytes_in_use, ...}] per device (/workers)."""
        rows = []
        for u in self._replicas():
            for stage in self._get(u + "/workers").get("detail", []):
                rows.extend(stage.get("memory", []))
        return rows


def run_child(cmd: list, env: dict, log_path: str, timeout_s: float) -> int:
    """Run a helper child (the reference) to its end; its output goes to a
    log file. It is killed, and waited for, if it outlasts the timeout."""
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT)
        try:
            return proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
            return -9
