"""The server child of a run, and the staged weights it boots from.

Nothing here imports JAX: the child is the one owner of the chip."""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "benchmark"
WORK = ROOT / ".cache" / "benchmark"  # git-ignored; never copied back


def child_env(cpu: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT) + (os.pathsep + env["PYTHONPATH"]
                                     if env.get("PYTHONPATH") else "")
    env.pop("TPUSERVE_LOCKWATCH", None)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def stage_weights(config: dict, serve: dict, rehearse: bool) -> Path:
    """The staged checkpoint of this configuration and weight seed; written
    by a CPU child on the first run in a checkout, found by every later one."""
    w = config["weights"]
    tag = f"{config['name']}-{w['seed']}" + ("-rehearse" if rehearse else "")
    path = WORK / "weights" / f"{tag}.tpu.safetensors"
    if not path.is_file():
        subprocess.run([sys.executable, str(HERE / "stage_weights.py"),
                        str(path), config["file"], json.dumps(serve)],
                       check=True, cwd=str(ROOT), env=child_env(cpu=True))
    return path


def dir_size(directory: Path) -> tuple[int, int]:
    files = [p for p in directory.rglob("*") if p.is_file()] \
        if directory.is_dir() else []
    return len(files), sum(p.stat().st_size for p in files)


class Server:
    """``tpuserve serve`` for one configuration, through ``serve_child.py``."""

    def __init__(self, cell: str, serve: dict, checkpoint: Path,
                 rehearse: bool):
        import yaml

        self.dir = WORK / "run" / cell
        shutil.rmtree(self.dir / "traces", ignore_errors=True)  # old captures
        self.dir.mkdir(parents=True, exist_ok=True)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.url = f"http://127.0.0.1:{port}"
        cfg = {"host": "127.0.0.1", "port": port, "warmup_at_boot": False,
               "ingest_workers": 0, "trace_dir": str(self.dir / "traces"),
               "models": [{"name": serve["model"],
                           "builder": serve["builder"],
                           "checkpoint": str(checkpoint),
                           "dtype": serve["dtype"],
                           "batch_buckets": serve["batch_buckets"],
                           "seq_buckets": serve["seq_buckets"],
                           "extra": serve["extra"]}]}
        (self.dir / "serve.yaml").write_text(yaml.safe_dump(cfg,
                                                            sort_keys=False))
        # The compile cache lives inside the checkout, at a fixed path, and
        # is not capped: one configuration's programs (17 MB each for XL)
        # outgrow the 192 MiB some machines set, and a cache that evicts
        # makes every run compile.
        self.cache_dir = ROOT / ".cache" / "xla"
        self.cache_start = dir_size(self.cache_dir)
        self.log_path = self.dir / "server.log"
        self.mem_path = self.dir / "memory.json"
        self.mem_path.unlink(missing_ok=True)
        cmd = [sys.executable, str(HERE / "serve_child.py"),
               str(self.mem_path), "serve", "--config",
               str(self.dir / "serve.yaml"), "--port", str(port)]
        if rehearse:
            cmd += ["--platform", "cpu"]
        env = child_env(cpu=rehearse)
        env["JAX_LOG_COMPILES"] = "1"  # every compile leaves a log line
        env["JAX_COMPILATION_CACHE_DIR"] = str(self.cache_dir)
        env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        self._log = open(self.log_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(cmd, cwd=str(ROOT), stdout=self._log,
                                     stderr=subprocess.STDOUT, env=env)
        self.boot_s = None
        self._mark = 0

    def log_tail(self, n: int = 2000) -> str:
        self._log.flush()
        return self.log_path.read_text(errors="replace")[-n:]

    def wait_healthy(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SystemExit(f"server exited {self.proc.returncode} "
                                 f"while booting\n{self.log_tail()}")
            try:
                with urllib.request.urlopen(self.url + "/healthz",
                                            timeout=10.0) as resp:
                    self.boot_s = time.monotonic() - self.t_spawn
                    return json.loads(resp.read())
            except (urllib.error.URLError, OSError):
                time.sleep(0.25)
        raise SystemExit(f"server not healthy after {timeout:.0f}s\n"
                         f"{self.log_tail()}")

    def log_event(self, msg: str) -> dict:
        """The first JSON log record with this ``msg``."""
        for line in self.log_path.read_text(errors="replace").splitlines():
            if line.startswith("{") and f'"msg": "{msg}"' in line:
                return json.loads(line)
        return {}

    def boot_split(self) -> dict:
        """Spawn to healthy, split at the engine's own cold-start clock."""
        engine = float(self.log_event("engine ready").get(
            "cold_start_seconds", 0.0))
        return {"spawn_to_engine_s": self.boot_s - engine,
                "engine_weights_s": engine}

    def mark(self) -> None:
        self._mark = self.log_path.stat().st_size

    def compiles_since_mark(self) -> int:
        with open(self.log_path, "rb") as f:
            f.seek(self._mark)
            return sum(1 for line in f if b"Compiling " in line)

    def cache_note(self) -> str:
        n0, b0 = self.cache_start
        n1, b1 = dir_size(self.cache_dir)
        return (f"{n0} entries {b0 / 2**20:.1f} MiB at start, {n1} entries "
                f"{b1 / 2**20:.1f} MiB at end")

    def stop(self) -> dict:
        """SIGINT, as an operator's ctrl-c; returns the child's memory note."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        if self.proc.returncode != 0:
            raise SystemExit(f"server exited {self.proc.returncode}\n"
                             + self.log_path.read_text(errors="replace")[-2000:])
        return json.loads(self.mem_path.read_text())
