#!/usr/bin/env python3
"""Smoke of the serving path on one TPU v5e (or, with ``--chips 4``, of the
multi-chip path on four): the quickest proof that the system still starts on
the chip.

    python chip_smoke.py             # one chip; the run the driver makes
    python chip_smoke.py --chips 4   # the mesh phase and nothing else
    python chip_smoke.py --rehearse  # same phases on the CPU at tiny widths

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``, with
the device as JAX reports it; everything else goes on earlier lines.  Any
failed phase, any non-200, any device that is not a TPU (``--rehearse``
excepted, whose last line says ``cpu`` and so cannot pass for a chip run)
exits non-zero without that line.

One process per chip: libtpu gives the chip to one process at a time, so this
process never imports JAX — it starts the real entry points as children, one
after the other, and talks HTTP to them.

One-chip phases, at the published widths (weights random, from the builders'
fixed seed; inputs generated from ``SEED`` into ``smoke_out/``):

- *serve*    ``tpuserve serve`` with resnet50 (224 px, buckets 1 and 8) and
             GPT-2 small three times — slot scheduler, paged KV, int8
             weights; ``:predict`` / ``:generate`` requests, ``/healthz``,
             ``/metrics``, SIGINT.
- *restart*  the same config again: the boot must hit the compile cache.
- *kernels*  ``int8_matmul``, ``flash_attention``, ``prompt_attention`` and
             ``decode_attention`` with ``interpret=False`` at real shapes
             against ``jax.numpy`` references, on the chip; then
             ``int8_matmul`` alone by the profiler's clock,
             microseconds a call at the five shapes of
             GPT-2 large's decode step beside the least their bytes allow,
             and ``decode_attention`` alone, microseconds a layer at three
             fills of the serving pools beside the least their live bytes
             allow (EvaByte's pool, 32 heads of 128 at ``D`` 4096, in spans
             with a start), and a prefill's prompt attention in both its
             forms, microseconds a layer at the benchmark's prefill shapes.
- *burst*    the servable's own prefill at ``gpt2-large-int8``'s and
             ``gpt2-xl``'s published widths and all their layers, at the
             admission batches a burst forms (8 and 16 prompts of 512 and
             768) into the pool, then two segments: as built (the prompt
             attention's kernel) against the same programs with the picker
             held to the ``jax.numpy`` form, and a float32 attention as the
             arbiter.
- *segment*  the decode segment program at GPT-2 XL's serving shape (8 slots
             of 960 positions, 48 layers, compiled from shapes alone): its
             optimised HLO must hold no ``copy``, ``slice`` or ``transpose``
             whose result is as large as one layer of the slot pool.  On the
             chip only; ``--rehearse`` prints what the CPU's compiler made.
- *evabyte*  EvaByte at its published widths, 16 layers: the programs the
             servable jits (``prefill_start`` over 4,090 bytes, then four
             segments of 8 across position 4,096) against the plain
             reference computed on the same device in float32 at ``highest``
             precision, logits at every decoded position; and the control,
             the reference with its weights through int8, which must fail.
- *lfm2*     LFM2 at the benchmark cell's widths, ten layers: the grouped
             ``decode_attention``, the gated ``expert_matmul`` and the
             prompt attention's flash form alone by the profiler's clock,
             then the cell's 16 reference prompts (600 and 3,000 tokens)
             through ``prefill_start`` into the pool and segments against the
             plain reference on the same device, by the logits and by the
             cell's own ``judge``; and the int8 control, which must fail.
- *mellum*   Mellum 2 at the benchmark cell's widths (eight layers, two
             kinds of K/V layer): ``decode_attention`` at a group of 8 of
             128 over a ring and over full rows, ``flash_attention`` with
             and without the band at the four buckets, the gated
             ``expert_matmul`` at ``K`` 2304 and ``F`` 896, alone by the
             profiler's clock; then the cell's 16 reference prompts (700 and
             5,000 tokens) through ``prefill_start`` into both leaf pairs
             and segments against the plain reference, by the logits and by
             the cell's own ``judge``; and the three controls (int8, the
             window layers read as full, no YaRN), each of which must fail.
- *joyai*    JoyAI-LLM-Flash at the benchmark cell's widths (ten layers, 32
             of 256 experts, a pool of one leaf): ``latent_attention`` at 64
             slots over spans of 2k-9k rows with one pool operand and, for
             the record, with the leaf handed twice to ``decode_attention``;
             ``flash_attention`` at keys of 192 and values of 128 at the
             four buckets; the gated ``expert_matmul`` at 2 and 256 rows an
             expert, alone by the profiler's clock; then an 8,192 and a
             2,300 prompt and the cell's 16 reference prompts (3,000 and
             5,000 tokens) through ``prefill_start`` into the leaf and
             segments against the plain reference, by the logits and by the
             cell's own ``judge``; and the three controls (int8, the rope
             part of the score left out, the latent kept without its norm),
             each of which must fail.
- *sd15*     Stable Diffusion 1.5 at 512x512, two steps, one ``:submit``
             polled to ``done`` (flash attention's only serving caller).

Not a phase of the run, because it proves nothing about starting: *retrieval*
(``python -c "import chip_smoke; chip_smoke.phase_retrieval(False)"``, three
children, about 5 min on the chip) takes apart what JAX books as one number,
a program's retrieval from the compile cache: one XL prefill (``[4, 768]``),
the XL segment and one int8 prefill, each lowered and then compiled against a
warm ``.cache/xla`` in fresh processes, with the seconds inside the file's
read, its decompression and ``deserialize_executable`` beside the entry's
bytes, as a process's first retrieval and as its third.  A measurement: it
fails only if a warm program is not served by the cache.
"""

from __future__ import annotations

import argparse
import base64
import io
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PKG = ROOT / "pytorch_zappa_serverless_tpu"
OUT = ROOT / "smoke_out"
SEED = 20260926
GEN_TOKENS = 16  # tokens asked of every :generate stream
# EvaByte's programs in bfloat16 against the float32 reference, in logits of
# spread about 0.8 (PERF.md section 6, PR 35, has both readings).
EVABYTE_LOGIT_TOL = 0.1
# Nemotron-H's programs in bfloat16 against the float32 reference, in logits
# of spread 1.28: the largest difference in any logit and the root mean
# square of all (PERF.md section 6, PR 45, has the readings and the control
# that fails them: a router's choice of 22 of 512 flips on a rounding, so the
# largest differences are a flipped expert's and the mean is the steadier
# reading).  The served tokens are judged by the cell's own comparison and
# limits (benchmark/families/nemotron_h.py ``judge``).
NEMOTRON_LOGIT_TOL = 0.6
NEMOTRON_RMS_TOL = 0.05
# LFM2's programs in bfloat16 against the float32 reference, in logits of
# spread 0.90: the root mean square of all differences read 0.0752 sound and
# 0.1385 under the int8 control (PERF.md section 6, PR 48).  The largest
# difference is reported and not judged: it is one flipped expert's on either
# side (0.905 and 1.056).
LFM2_RMS_TOL = 0.105
# Mellum 2's programs in bfloat16 against the float32 reference, in logits
# of spread 1.0: the root mean square of all differences read 0.0137 sound
# and 0.0400 under the int8 control (PERF.md section 6, PR 52).
MELLUM_RMS_TOL = 0.025
# JoyAI-LLM-Flash's programs in bfloat16 against the float32 reference, in
# logits of spread 0.91: the root mean square of all differences read 0.0334
# (the cell's 16 prompts) and 0.0397 (an 8,192 and a 2,300 prompt) sound and
# 0.0724 and 0.0733 under the int8 control (PERF.md section 6, PR 55).
JOYAI_RMS_TOL = 0.053
# --rehearse widths: d_model is one 128-lane tile, the int8 kernel's floor.
TINY_GPT2 = {"d_model": 128, "layers": 2, "heads": 2, "ffn_dim": 256,
             "vocab_size": 512, "max_positions": 128}


# Where the cache lives when JAX_COMPILATION_CACHE_DIR does not place it.
IN_CHECKOUT_CACHE = ROOT / ".cache" / "xla"


def entries(directory: Path) -> int:
    return len(list(directory.iterdir())) if directory.is_dir() else 0


class SmokeFailure(Exception):
    """A phase did not do what it must; the message says which and why."""


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- children ----------------------------------------------------------------

_CHILDREN: list[subprocess.Popen] = []


def child_env(rehearse: bool, devices: int = 1) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT) + (os.pathsep + env["PYTHONPATH"]
                                     if env.get("PYTHONPATH") else "")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={devices}")
    return env


def run_child(code: str, rehearse: bool, log_name: str, *, devices: int = 1,
              timeout: float = 900.0) -> dict:
    """Run ``code`` in a fresh interpreter that owns the chip while it lives;
    its last stdout line is its JSON result, its stderr goes to a log."""
    log_path = OUT / log_name
    with open(log_path, "wb") as log:
        proc = subprocess.Popen([sys.executable, "-c", code], cwd=str(ROOT),
                                env=child_env(rehearse, devices),
                                stdout=subprocess.PIPE, stderr=log)
        _CHILDREN.append(proc)
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            raise SmokeFailure(f"{log_name}: child still running after "
                               f"{timeout:.0f}s") from None
    lines = out.decode(errors="replace").strip().splitlines()
    if proc.returncode != 0 or not lines:
        tail = log_path.read_text(errors="replace").strip()[-1500:]
        raise SmokeFailure(f"{log_name}: child exited {proc.returncode}\n"
                           f"{tail}")
    for line in lines[:-1]:
        if not line.startswith("{"):  # the engine's JSON log records
            say(f"  {line}")
    return json.loads(lines[-1])


_PROBE = """\
import json, jax
from pytorch_zappa_serverless_tpu.engine.cache import resolve_compile_cache_dir
from pytorch_zappa_serverless_tpu.utils.device import device_info
print(json.dumps({"device": device_info(), "jax": jax.__version__,
                  "cache_dir": resolve_compile_cache_dir()}))
"""


def probe_device(rehearse: bool, chips: int) -> dict:
    """What JAX finds, from a child that exits before any other starts."""
    info = run_child(_PROBE, rehearse, "probe.log", devices=chips,
                     timeout=300.0)
    dev = info["device"]
    say(f"device: {dev}  jax {info['jax']}  compile cache: "
        f"{info['cache_dir']}")
    want = "cpu" if rehearse else "tpu"
    check(dev["platform"] == want,
          f"JAX found platform {dev['platform']!r}, this run needs {want!r}"
          + ("" if rehearse else " (no accelerator: nothing to smoke; "
             "--rehearse runs the phases on the CPU)"))
    check(dev["count"] >= chips,
          f"--chips {chips} needs {chips} devices, JAX found {dev['count']}")
    return info


# -- inputs, generated from the seed ------------------------------------------

def make_inputs(rehearse: bool) -> dict:
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(SEED)
    (OUT / "inputs").mkdir(parents=True, exist_ok=True)
    jpegs = []
    for i in range(8):
        arr = rng.integers(0, 256, (320, 400, 3), np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG")
        (OUT / "inputs" / f"img{i}.jpg").write_bytes(buf.getvalue())
        jpegs.append(buf.getvalue())
    vocab = 512 if rehearse else 50257
    prompts = [[int(t) for t in rng.integers(1, vocab - 2, n)]
               for n in (12, 9, 14)]
    (OUT / "inputs" / "prompts.json").write_text(json.dumps(prompts))
    return {"jpegs": jpegs, "prompts": prompts}


def write_configs(rehearse: bool) -> tuple[Path, Path]:
    """The YAML profiles ``tpuserve serve`` boots: full published widths, or
    tiny ones under ``--rehearse``."""
    import yaml

    if rehearse:
        arch = TINY_GPT2
        seq, max_new = 16, GEN_TOKENS
        resnet = {"name": "resnet50", "batch_buckets": [1, 8],
                  "extra": {"image_size": 64, "resize_to": 72}}
        sd_extra = {"variant": "tiny", "height": 64, "width": 64}
    else:
        arch, seq, max_new = None, 64, 32
        resnet = {"name": "resnet50", "batch_buckets": [1, 8]}
        sd_extra = {"height": 512, "width": 512,
                    "params_dtype": "bfloat16"}

    def gpt2(name, batch, params_dtype, **kw):
        extra = {"max_new_tokens": max_new, "params_dtype": params_dtype,
                 "gen_slots": 8, "segment_tokens": 8}
        if arch:  # tiny kernels sit under the default quantization floor
            extra.update(arch=arch, quantize_min_size=1024)
        return {"name": name, "builder": "gpt2", "batch_buckets": [batch],
                "seq_buckets": [seq], "extra": extra, **kw}

    serve = {"warmup_at_boot": True, "models": [
        resnet,
        gpt2("gpt2", 1, "bfloat16"),
        gpt2("gpt2_paged", 1, "bfloat16", kv_cache="paged"),
        gpt2("gpt2_int8", 8, "int8"),
    ]}
    sd15 = {"warmup_at_boot": True, "models": [
        {"name": "sd15", "batch_buckets": [1],
         "extra": {"num_steps": 2, **sd_extra}}]}
    paths = []
    for name, cfg in (("serve.yaml", serve), ("sd15.yaml", sd15)):
        path = OUT / name
        path.write_text(yaml.safe_dump(cfg, sort_keys=False))
        paths.append(path)
    return paths[0], paths[1]


# -- HTTP against a live server ------------------------------------------------

def http(url: str, *, data: bytes | None = None, headers: dict | None = None,
         timeout: float = 300.0):
    req = urllib.request.Request(url, data=data, headers=headers or {},
                                 method="POST" if data is not None else "GET")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def http_json(url: str, payload=None, **kw):
    data = None if payload is None else json.dumps(payload).encode()
    headers = {"Content-Type": "application/json"} if data else {}
    status, body = http(url, data=data, headers=headers, **kw)
    try:
        return status, json.loads(body)
    except ValueError:
        return status, {"raw": body[:300].decode(errors="replace")}


class Server:
    """One ``tpuserve serve`` child: started through the CLI, stopped with
    SIGINT, its log kept under ``smoke_out/``."""

    def __init__(self, config: Path, rehearse: bool, log_name: str):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.url = f"http://127.0.0.1:{port}"
        self.log_path = OUT / log_name
        cmd = [sys.executable, "-m", "pytorch_zappa_serverless_tpu.cli",
               "serve", "--config", str(config), "--port", str(port)]
        if rehearse:
            cmd += ["--platform", "cpu"]
        self._log = open(self.log_path, "wb")
        self.t_start = time.monotonic()
        self.proc = subprocess.Popen(cmd, cwd=str(ROOT), stdout=self._log,
                                     stderr=subprocess.STDOUT,
                                     env=child_env(rehearse))
        _CHILDREN.append(self.proc)
        self.boot_s = None

    def wait_healthy(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited {self.proc.returncode} while booting\n"
                    + self.log_tail())
            try:
                status, body = http_json(self.url + "/healthz", timeout=10.0)
            except (urllib.error.URLError, OSError):
                time.sleep(0.5)
                continue
            if status == 200:
                self.boot_s = time.monotonic() - self.t_start
                return body
            time.sleep(0.5)
        raise SmokeFailure(f"server not healthy after {timeout:.0f}s\n"
                           + self.log_tail())

    def log_tail(self, n: int = 1500) -> str:
        self._log.flush()
        return self.log_path.read_text(errors="replace")[-n:]

    def log_events(self, msg: str) -> list[dict]:
        """The server's JSON log records with this ``msg``."""
        self._log.flush()
        out = []
        for line in self.log_path.read_text(errors="replace").splitlines():
            if line.startswith("{") and f'"msg": "{msg}"' in line:
                out.append(json.loads(line))
        return out

    def stop(self) -> None:
        """SIGINT, as an operator's ctrl-c; anything but a clean exit fails."""
        self.proc.send_signal(signal.SIGINT)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise SmokeFailure("server still running 90s after SIGINT\n"
                               + self.log_tail()) from None
        finally:
            self._log.close()
        check(rc == 0, f"server exited {rc} on SIGINT\n"
              + self.log_path.read_text(errors="replace")[-1500:])


def predict_image(srv: Server, jpeg: bytes) -> dict:
    status, body = http(srv.url + "/v1/models/resnet50:predict", data=jpeg,
                        headers={"Content-Type": "image/jpeg"})
    check(status == 200, f"resnet50:predict -> {status}: {body[:300]!r}")
    return check_topk(json.loads(body)["predictions"])


def check_topk(pred: dict) -> dict:
    import math

    top = pred["top_k"]
    probs = [e["prob"] for e in top]
    check(len(top) == 5 and all(math.isfinite(p) and 0.0 <= p <= 1.0
                                for p in probs)
          and probs == sorted(probs, reverse=True) and sum(probs) <= 1.001,
          f"resnet50 top_k is not 5 sorted finite probabilities: {top}")
    return pred


def generate(srv: Server, model: str, ids: list[int]) -> list[int]:
    """One greedy SSE stream; returns the tokens, checked against the
    stream's own ``done`` event."""
    req = urllib.request.Request(
        f"{srv.url}/v1/models/{model}:generate",
        data=json.dumps({"input_ids": ids,
                         "max_new_tokens": GEN_TOKENS}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    streamed, final = [], None
    try:
        with urllib.request.urlopen(req, timeout=600.0) as resp:
            check(resp.headers.get_content_type() == "text/event-stream",
                  f"{model}:generate answered "
                  f"{resp.headers.get_content_type()}, not an SSE stream")
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                ev = json.loads(line[len("data: "):])
                if "token" in ev:
                    streamed.append(int(ev["token"]))
                elif ev.get("done"):
                    final = ev
                elif "error" in ev:
                    raise SmokeFailure(f"{model}:generate stream error: {ev}")
    except urllib.error.HTTPError as e:
        raise SmokeFailure(f"{model}:generate -> {e.code}: "
                           f"{e.read()[:300]!r}") from None
    check(final is not None, f"{model}:generate stream ended without done")
    check(streamed == final["tokens"],
          f"{model}: streamed tokens differ from the done event's")
    check(len(streamed) >= GEN_TOKENS,
          f"{model}: {len(streamed)} tokens streamed, asked {GEN_TOKENS}")
    return streamed


def read_metrics(srv: Server) -> dict:
    status, metrics = http_json(srv.url + "/metrics")
    check(status == 200, f"/metrics -> {status}")
    return metrics


def check_health(health: dict, device: dict) -> None:
    check(health.get("device_ok") is True, f"/healthz device_ok: {health}")
    check(health.get("device") == device,
          f"/healthz device block {health.get('device')} is not what the "
          f"probe saw {device}")
    for name, m in health["models"].items():
        check(m["buckets_compiled"] == m["buckets_total"],
              f"{name}: {m} — not every bucket was warmed at boot")


def phase_serve(config: Path, inputs: dict, probe: dict,
                rehearse: bool) -> dict:
    srv = Server(config, rehearse, "serve-boot1.log")
    health = srv.wait_healthy(900.0)
    check_health(health, probe["device"])
    boot = read_metrics(srv)["cold_start"]
    say(f"serve: boot {srv.boot_s:.1f}s wall, engine cold start "
        f"{boot['seconds']}s of which compile {boot['compile_seconds_total']}s"
        f" ({len(boot['compile_entries'])} executables)")

    # resnet50: JPEGs singly, then the same eight as one instances body.
    jpegs = inputs["jpegs"]
    singles = [predict_image(srv, j) for j in jpegs[:3]]
    status, body = http_json(
        srv.url + "/v1/models/resnet50:predict",
        {"instances": [{"b64": base64.b64encode(j).decode()} for j in jpegs]})
    check(status == 200 and len(body.get("predictions", [])) == 8,
          f"resnet50 8-instance predict -> {status}: {str(body)[:300]}")
    for pred in body["predictions"]:
        check_topk(pred)
    for one, many in zip(singles, body["predictions"]):
        gap = max(abs(a["prob"] - b["prob"])
                  for a, b in zip(one["top_k"], many["top_k"]))
        check(gap <= 2e-2, f"resnet50: a JPEG alone and in the batch of 8 "
              f"differ by {gap} in top-k probability")
    say("serve: resnet50 3 single + 1x8-instance predicts ok "
        f"(batch_size {body['timing']['batch_size']})")

    # GPT-2 streams on both schedulers: greedy, so a prompt sent alone twice
    # gives the same tokens.
    tokens = {}
    for model in ("gpt2", "gpt2_paged"):
        first = generate(srv, model, inputs["prompts"][0])
        again = generate(srv, model, inputs["prompts"][0])
        check(first == again, f"{model}: the same prompt gave different "
              f"greedy tokens twice:\n  {first}\n  {again}")
        other = generate(srv, model, inputs["prompts"][1])
        tokens[model] = [first, other]
        say(f"serve: {model} 3 streams of {len(first)} tokens, "
            f"repeat identical; first: {first}")
    agree = tokens["gpt2"] == tokens["gpt2_paged"]
    n_same = sum(a == b for s, p in zip(tokens["gpt2"], tokens["gpt2_paged"])
                 for a, b in zip(s, p))
    say(f"serve: slot and paged schedulers agree: {agree} "
        f"({n_same}/{2 * GEN_TOKENS} token positions equal)")

    status, body = http_json(srv.url + "/v1/models/gpt2_int8:predict",
                             {"input_ids": inputs["prompts"][2]})
    check(status == 200, f"gpt2_int8:predict -> {status}: {str(body)[:300]}")
    int8_tokens = body["predictions"]["tokens"]
    check(len(int8_tokens) >= GEN_TOKENS
          and all(isinstance(t, int) and t >= 0 for t in int8_tokens),
          f"gpt2_int8 tokens: {int8_tokens}")
    say(f"serve: gpt2_int8 predict {len(int8_tokens)} tokens: "
        f"{int8_tokens[:8]}...")

    status, health = http_json(srv.url + "/healthz")
    check(status == 200, f"/healthz -> {status} after the requests")
    check_health(health, probe["device"])
    metrics = read_metrics(srv)
    errors = {m: s["errors"] for m, s in metrics["models"].items()
              if s["errors"]}
    check(not errors, f"/metrics reports request errors: {errors}")
    after = metrics["cold_start"]
    # The ledger books the generation lanes' first uses too (they compile at
    # their first request by design); a :predict bucket may not join them.
    late = [e for e in after["compile_entries"][len(boot["compile_entries"]):]
            if e["program"] == "predict"]
    check(not late, f"a bucket compiled after warm-up: {late}")
    say(f"serve: /healthz ok, /metrics no errors over "
        f"{sum(s['requests'] for s in metrics['models'].values())} requests, "
        "no bucket compiled after warm-up")
    ready = srv.log_events("engine ready")
    check(ready and ready[0].get("device") == probe["device"]
          and ready[0].get("compile_cache_dir") == probe["cache_dir"],
          f"boot log does not name the device and cache dir: {ready}")
    srv.stop()
    say("serve: SIGINT -> clean exit")
    return {"boot_s": srv.boot_s, "compile_s": boot["compile_seconds_total"],
            "tokens": tokens, "int8_tokens": int8_tokens,
            "resnet": singles[0]}


def phase_restart(config: Path, inputs: dict, probe: dict, first: dict,
                  cache_was_empty: bool, rehearse: bool) -> None:
    cache_dir = Path(probe["cache_dir"])
    check(entries(cache_dir),
          f"compile cache {cache_dir} is empty after the first boot")
    check(cache_dir == IN_CHECKOUT_CACHE
          or entries(IN_CHECKOUT_CACHE) == probe["in_checkout_entries"],
          f"the cache was placed at {cache_dir}, yet {IN_CHECKOUT_CACHE} "
          "grew: something set a directory of its own")
    srv = Server(config, rehearse, "serve-boot2.log")
    health = srv.wait_healthy(900.0)
    check_health(health, probe["device"])
    boot = read_metrics(srv)["cold_start"]
    say(f"restart: boot {srv.boot_s:.1f}s wall (first {first['boot_s']:.1f}s),"
        f" compile {boot['compile_seconds_total']}s "
        f"(first {first['compile_s']}s), cache {cache_dir}")
    if cache_was_empty:
        check(boot["compile_seconds_total"] < first["compile_s"],
              f"second boot compiled for {boot['compile_seconds_total']}s, "
              f"the first for {first['compile_s']}s: the cache did not hit")
    else:
        say("restart: the cache held entries before the first boot, so the "
            "first boot was not cold: compile seconds not compared")
    pred = predict_image(srv, inputs["jpegs"][0])
    check(pred["top_k"][0]["index"] == first["resnet"]["top_k"][0]["index"]
          or abs(pred["top_k"][0]["prob"]
                 - first["resnet"]["top_k"][0]["prob"]) <= 2e-2,
          "resnet50 answers differently after the restart")
    for model in ("gpt2", "gpt2_paged"):
        check(generate(srv, model, inputs["prompts"][0])
              == first["tokens"][model][0],
              f"{model}: tokens differ after the restart")
    status, body = http_json(srv.url + "/v1/models/gpt2_int8:predict",
                             {"input_ids": inputs["prompts"][2]})
    check(status == 200
          and body["predictions"]["tokens"] == first["int8_tokens"],
          f"gpt2_int8 differs after the restart: {status} {str(body)[:200]}")
    srv.stop()
    say("restart: one request per model ok, same answers, clean exit")


def phase_sd15(config: Path, probe: dict, rehearse: bool) -> None:
    srv = Server(config, rehearse, "serve-sd15.log")
    health = srv.wait_healthy(1000.0)
    check_health(health, probe["device"])
    say(f"sd15: boot {srv.boot_s:.1f}s wall")
    status, body = http_json(srv.url + "/v1/models/sd15:submit",
                             {"prompt": "a photo of a tpu", "seed": SEED})
    check(status == 202, f"sd15:submit -> {status}: {str(body)[:300]}")
    job_id = body["job"]["id"]
    deadline = time.monotonic() + 600.0
    job = body["job"]
    while time.monotonic() < deadline:
        status, body = http_json(f"{srv.url}/v1/jobs/{job_id}")
        check(status == 200, f"job poll -> {status}")
        job = body["job"]
        if job["status"] in ("done", "failed"):
            break
        time.sleep(1.0)
    check(job["status"] == "done", f"sd15 job ended {job['status']}: "
          f"{str(job)[:300]}")
    from PIL import Image

    img = Image.open(io.BytesIO(base64.b64decode(job["result"]["image_b64"])))
    img.load()
    side = 64 if rehearse else 512
    check(img.format == "PNG" and img.size == (side, side),
          f"sd15 image is {img.format} {img.size}, wanted PNG {side}x{side}")
    lo, hi = img.convert("L").getextrema()
    check(hi > lo, "sd15 image is one flat colour")
    say(f"sd15: job done, PNG {img.size[0]}x{img.size[1]} decodes")
    srv.stop()
    say("sd15: SIGINT -> clean exit")


# -- children that own the chip themselves ---------------------------------------

def _kernels_child(rehearse: bool) -> None:
    """The Pallas kernels on the serving path, compiled for the device
    (``interpret=False``; the interpreter under ``--rehearse``), against
    ``jax.numpy`` references at the tolerances of tests/test_int8_matmul.py
    and tests/test_flash_attention.py (its bf16 case)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_zappa_serverless_tpu.models.evabyte import TwoTier
    from pytorch_zappa_serverless_tpu.ops import hostops
    from pytorch_zappa_serverless_tpu.ops.decode_attention import (
        decode_attention)
    from pytorch_zappa_serverless_tpu.ops import (
        flash_attention as flash_attention_module)
    from pytorch_zappa_serverless_tpu.ops.flash_attention import (
        flash_attention, masked_attention, prompt_attention, prompt_block,
        prompt_mask)
    from pytorch_zappa_serverless_tpu.ops.int8_matmul import (
        int8_matmul, quantize_per_channel)

    interpret = rehearse
    rng = np.random.default_rng(SEED)
    mm = ([(8, 128, 256)] if rehearse else
          [(8, 768, 2304), (8, 768, 50257), (8, 3072, 768), (128, 768, 3072),
           (16, 1280, 3840), (16, 5120, 1280)])
    for m, k, n in mm:
        x = jnp.asarray(rng.standard_normal((m, k)) * 0.5, jnp.bfloat16)
        w_q, scale = quantize_per_channel(
            (rng.standard_normal((k, n)) * 0.02).astype(np.float32), axis=0)
        got = int8_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                          interpret=interpret)
        w = (jnp.asarray(w_q, jnp.float32)
             * jnp.asarray(scale)[None, :]).astype(jnp.bfloat16)
        want = jnp.dot(x, w, preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=2e-2, atol=2e-2)
        print(f"int8_matmul [{m},{k}]x[{k},{n}] matches its reference")
    # GPT-2 large's decode step at 16 slots: qkv, out, fc1, fc2, and the
    # head, whose logits are float32.
    for row in time_int8_matmul(
            functools.partial(int8_matmul, interpret=interpret),
            [(8, 128, 256, None)] if rehearse else
            [(16, 1280, 3840, None), (16, 1280, 1280, None),
             (16, 1280, 5120, None), (16, 5120, 1280, None),
             (16, 1280, 50257, jnp.float32)], not rehearse):
        print("int8_matmul " + json.dumps(row))

    fa = ([(1, 256, 256, 2, 64, False), (1, 128, 128, 2, 64, True)]
          if rehearse else
          [(2, 4096, 4096, 8, 64, False), (8, 4096, 4096, 8, 64, False),
           (2, 1024, 1024, 8, 80, False), (2, 256, 256, 8, 160, False),
           (2, 4096, 77, 8, 64, False), (8, 128, 128, 12, 64, True)])

    @jax.jit
    def reference(q, k, v, causal_bias):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       precision="highest") * q.shape[-1] ** -0.5
        p = jax.nn.softmax(s + causal_bias, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")

    for b, tq, tk, h, d, causal in fa:
        q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)),
                               jnp.bfloat16) for t in (tq, tk, tk))
        got = flash_attention(q, k, v, causal=causal, interpret=interpret)
        bias = (jnp.where(jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :],
                          0.0, -1e9) if causal else jnp.zeros((tq, tk)))
        want = reference(q, k, v, bias)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=3e-2, atol=3e-2)
        print(f"flash_attention q[{b},{tq},{h},{d}] kv {tk} causal={causal} "
              "matches its reference")
    # A prefill's prompt attention, causal and ragged, rows with the heads
    # side by side: GPT-2 XL's and large's admission batches, and the
    # batches a burst admits on the int8 lane (20 heads, 8 and 16 prompts of
    # 512 and 768).  Real rows against the other form's, every row finite,
    # the rows of a block of queries wholly past a length zeros.
    pa = ([(3, 288, 5)] if rehearse else
          [(8, 512, 20), (16, 512, 20), (4, 768, 20), (8, 768, 20),
           (16, 768, 20), (4, 768, 25), (8, 512, 25), (1, 512, 25),
           (16, 256, 20)])
    for b, p, h in pa:
        q, k, v = (jnp.asarray(rng.standard_normal((b, p, h * 64)),
                               jnp.bfloat16) for _ in range(3))
        lengths = edge_lengths(b, p)
        n = jnp.asarray(lengths, jnp.int32)
        got = np.asarray(prompt_attention(q, k, v, n, heads=h,
                                          interpret=interpret), np.float32)
        want = np.asarray(masked_attention(q, k, v, prompt_mask(n, p), h),
                          np.float32)
        block = prompt_block(p)
        for row, length in enumerate(lengths):
            np.testing.assert_allclose(got[row, :length], want[row, :length],
                                       rtol=3e-2, atol=3e-2)
            dead = -(-length // block) * block
            assert not got[row, dead:].any(), (row, length, "not zeros")
        assert np.isfinite(got).all(), "a padded row is not finite"
        print(f"prompt_attention [{b},{p},{h},64] lengths "
              f"{sorted(lengths)} matches the jax.numpy form; rows of "
              "skipped blocks are zeros")
    # Both forms alone at the benchmark's prefill shapes: the table the
    # picker's rule (ops/flash_attention.prompt_form) is read from.
    for row in time_prompt_attention(
            {"kernel": lambda q, k, v, n, heads: prompt_attention(
                q, k, v, n, heads=heads, interpret=interpret),
             "einsum": lambda q, k, v, n, heads: masked_attention(
                 q, k, v, prompt_mask(n, q.shape[1]), heads)},
            [(3, 96, 5)] if rehearse else PROMPT_SHAPES, not rehearse):
        print("prompt_attention " + json.dumps(row))
    # EvaByte's windowed prompt attention alone, its forms at its four
    # prefill buckets and the cell's two prompts (models/evabyte.py
    # ``TwoTier.attention``: a window's exact keys and the summaries of the
    # windows before it in one softmax).
    eva = (TwoTier(64, 4, 2, 8, block_q=32) if rehearse
           else TwoTier(2048, 16, 32, 64))
    if rehearse:  # ``TwoTier`` reaches the kernel by its module
        flash_attention_module.prompt_attention = functools.partial(
            prompt_attention, interpret=True)
    for p, length, heads in ([(192, 150, 2)] if rehearse
                             else [(6144, 5739, 32), (4096, 2049, 32)]):
        q, k, v, kbar, vbar = (
            jnp.asarray(rng.standard_normal((1, n, heads * 128)) * 0.5,
                        jnp.bfloat16)
            for n in (p, p, p, p // eva.chunk, p // eva.chunk))
        got, want = (np.asarray(eva.attention(
            form, heads, q, k, v, kbar, vbar, jnp.asarray([length])),
            np.float32) for form in EVA_FORMS)
        np.testing.assert_allclose(got[0, :length], want[0, :length],
                                   rtol=3e-2, atol=3e-2)
        assert np.isfinite(got).all(), "a padded row is not finite"
        print(f"eva prompt attention [1,{p},{heads},128] length {length}: "
              "the kernel matches the windows form on every real row")
    for row in time_prompt_attention(
            {form: lambda q, k, v, n, heads, kbar, vbar, form=form:
             eva.attention(form, heads, q, k, v, kbar, vbar, n)
             for form in EVA_FORMS},
            [(1, 192, 2, [150])] if rehearse else EVA_PROMPT_SHAPES,
            not rehearse, head_dim=128, chunk=eva.chunk,
            score_elements=lambda b, h, p: b * h * eva.block_q * (
                eva.window + p // eva.chunk)):
        print("eva_prompt_attention " + json.dumps(row))
    # Decode attention over a slot pool [L, S, T, D]: the benchmark's two
    # serving shapes, slots at 0, mid-block, a block edge and the last row,
    # and a dead one, whose row of the pool holds NaN and is read nowhere.
    # The third pool is EvaByte's (32 heads of 128), read in spans with a
    # start: a summary tier of ``lead`` rows below a ring.
    da = ([(2, 4, 32, 128, 2, 0), (2, 4, 48, 128, 2, 16)] if rehearse else
          [(2, 8, 960, 1600, 25, 0), (2, 16, 960, 1280, 20, 0),
           (2, 8, 2880, 4096, 32, 832)])
    for layers, slots, total, d, heads, lead in da:
        q = jnp.asarray(rng.standard_normal((slots, d)), jnp.bfloat16)
        wpos = jnp.asarray(([0, -1, 5, total // 4 - 1, total // 4, total - 1]
                            * 3)[:slots], jnp.int32)
        first = None
        if lead:  # spans that start and end inside blocks, around ``lead``
            wpos = jnp.asarray(([lead, -1, lead + 5, lead + 904, total - 1,
                                 lead + 64] * 3)[:slots], jnp.int32)
            first = jnp.asarray(([lead, 7, lead - 3, lead - 256, lead - 700,
                                  lead - 128] * 3)[:slots], jnp.int32)
            wpos = jnp.where(wpos < 0, wpos, jnp.minimum(wpos, total - 1))
            first = jnp.maximum(first, 0)
        ck, cv = (jnp.asarray(rng.standard_normal((layers, slots, total, d)),
                              jnp.bfloat16).at[:, 1].set(jnp.nan)
                  for _ in range(2))
        dh = d // heads
        got = decode_attention(q * dh ** -0.5, ck, cv, wpos, None, first,
                               layer=1, heads=heads, interpret=interpret)
        live = np.asarray(wpos) >= 0
        seen = jnp.arange(total)[None, :] <= wpos[:, None]
        if first is not None:
            seen &= jnp.arange(total)[None, :] >= first[:, None]
        bias = jnp.where(seen, 0.0, -1e9)[:, None, None, :]
        want = reference(*(a.reshape(slots, -1, heads, dh)[live]
                           for a in (q[:, None], ck[1], cv[1])), bias[live])
        np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                                   np.asarray(want).reshape(-1, d),
                                   rtol=3e-2, atol=3e-2)
        assert not np.asarray(got, np.float32)[~live].any(), "dead row not 0"
        none = decode_attention(q, ck, cv, jnp.full((slots,), -1, jnp.int32),
                                layer=0, heads=heads, interpret=interpret)
        assert not np.asarray(none, np.float32).any(), "no live slot, not 0"
        print(f"decode_attention pool[{layers},{slots},{total},{d}] "
              f"{heads} heads"
              + (f", spans from row {lead} down" if lead else "")
              + " matches its reference; dead slots give zeros")
        for row in time_decode_attention(
                lambda q, ck, cv, wpos, work, layer, block_t, first=None:
                decode_attention(q, ck, cv, wpos, work, first, layer=layer,
                                 heads=heads, block_t=block_t,
                                 interpret=interpret),
                slots, total, d, not rehearse,
                fills=span_fills(slots, total, lead) if lead else None):
            print("decode_attention " + json.dumps(row))
    print("admission_prefill " + json.dumps(
        time_admission_prefill(not rehearse)))
    print("preprocess path: "
          + ("native (hostops.cpp built with g++)" if hostops.native_available()
             else "PIL (no native library: no compiler here)"))
    print(json.dumps({"int8_matmul": len(mm), "flash_attention": len(fa),
                      "prompt_attention": len(pa),
                      "decode_attention": len(da)}))


_TIMED_CALLS = 16  # kernel calls chained in one timed program


def decode_fills(slots: int, total: int) -> dict[str, list[int]]:
    """``wpos`` of the three fills the kernel is timed at: the chat cells'
    own (2 of 8 or 3 of 16 slots live at about 250 of 960 positions, the
    others dead), every slot live at about half of ``total``, every slot full."""
    live = (slots + 2) // 5
    chat = [total * (215 + 70 * j // max(1, live - 1)) // 960
            for j in range(live)]
    half = [total // 2 - 64 * total // 960 + 128 * total // 960 * j // slots
            for j in range(slots)]
    return {"chat": chat + [-1] * (slots - live), "half": half,
            "full": [total - 1] * slots}


def span_fills(slots: int, total: int, lead: int) -> dict:
    """``(first, last)`` rows a slot of the three fills a pool read in spans
    is timed at (a ring above row ``lead``, summaries below it, 128 a
    finished window): the document cell's own (3 of 8 slots live, at about
    4,600, 6,300 and 2,700 bytes: 2, 3 and 1 finished windows), every slot
    live half way through its third window, every slot at its last row with
    six windows finished."""
    def at(windows, exact):
        return max(lead - 128 * windows, 0), min(lead + exact, total - 1)

    live = (slots + 2) // 5 + 1
    docqa = [at(w, e) for w, e in ((2, 500), (3, 150), (1, 650))][:live]
    return {"docqa": docqa + [(0, -1)] * (slots - live),
            "half": [at(2, 1024)] * slots,
            "full": [at(6, total)] * slots}


def _device_ns(run):
    """``(compute, counts, busy)`` of one profiled ``run()``: device
    nanoseconds and events of the profiler's ``XLA Ops`` by operation name
    (a loop's envelope left out, its body's operations counted), and the
    union of all their intervals, which holds a loop whole."""
    import tempfile

    import jax

    from pytorch_zappa_serverless_tpu.utils.xplane import (
        op_time_breakdown, read_capture)

    OUT.mkdir(exist_ok=True)  # the phase may run alone, without main()
    with tempfile.TemporaryDirectory(dir=OUT) as trace_dir:
        jax.profiler.start_trace(trace_dir)
        run().block_until_ready()
        jax.profiler.stop_trace()
        capture = read_capture(trace_dir)
        compute, counts, _, _ = op_time_breakdown(trace_dir, capture)
    busy = until = 0
    for plane in capture[0]:
        for start, end, _, is_op, _ in plane["ops"]:
            if is_op and end > until:
                busy += end - max(start, until)
                until = end
    return compute, counts, busy


def _device_us(run, name: str) -> float:
    """Device microseconds of one call of the kernel ``name`` inside
    ``run()``, a program that chains ``_TIMED_CALLS`` of them: the mean of
    the profiler's ``XLA Ops`` events of that name."""
    compute, counts, _ = _device_ns(run)
    assert counts[name] == _TIMED_CALLS, (counts[name], dict(counts))
    return round(compute[name] / counts[name] / 1e3, 2)


def time_int8_matmul(matmul, shapes, on_device: bool):
    """Device microseconds of one ``int8_matmul`` call, the kernel alone:
    one row a shape ``(M, K, N, out_dtype)`` (K in whole lane tiles), with
    the blocks and grid steps its plan gives, beside the least the chip
    could take over the weight's bytes as stored (819 GB/s).  ``matmul(x,
    w_q, scale, out_dtype=)`` is the kernel under the clock; the weight is
    stored as the int8 lane stores a head (``pad_weights``).  Off the device
    the rows carry no time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_zappa_serverless_tpu.ops.int8_matmul import (
        pad_weights, plan_summary)

    rng = np.random.default_rng(SEED)
    rows = []
    for m, k, n, out_dtype in shapes:
        w_q, scale = pad_weights(
            rng.integers(-127, 128, (k, n), dtype=np.int8),
            rng.random(n, np.float32) * 1e-3 + 1e-4)
        row = {"shape": [m, k, n], **plan_summary(m, *w_q.shape),
               "floor_us": round(w_q.size / 819e9 * 1e6, 2)}
        x = jnp.asarray(rng.standard_normal((m, k)) * 0.5, jnp.bfloat16)
        w_q, scale = jnp.asarray(w_q), jnp.asarray(scale)

        @jax.jit
        def chain(x, w_q, scale):
            for _ in range(_TIMED_CALLS):  # each call waits for the last
                y = matmul(x, w_q, scale, out_dtype=out_dtype)
                x = x + (y[:, :1] * 1e-6).astype(x.dtype)
            return x

        chain(x, w_q, scale).block_until_ready()
        if on_device:
            row["us_a_call"] = _device_us(
                lambda: chain(x, w_q, scale), "int8_matmul")
            row["gb_per_s"] = round(w_q.size / row["us_a_call"] / 1e3, 1)
        rows.append(row)
    return rows


def time_decode_attention(attend, slots: int, total: int, d: int,
                          on_device: bool, blocks=(None,), fills=None):
    """Device microseconds of one ``decode_attention`` call, the kernel
    alone, by the profiler's ``XLA Ops`` events of that name: one row a fill
    (:func:`decode_fills`) and a block length of ``blocks`` (None: the
    kernel's own choice), beside the least the chip could take over the live
    positions' bytes (K and V in bfloat16 at 819 GB/s).  ``attend(q, ck,
    cv, wpos, work, layer, block_t)`` is the kernel under the clock; ``work``
    is its list of live blocks, built once a program as the segment builds
    it once a step.  ``fills`` (:func:`span_fills`) times spans with a start,
    which ``attend`` then takes as ``first``.  Off the device the rows carry
    no time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_zappa_serverless_tpu.ops.decode_attention import (
        pick_block_t, work_list)

    layers = 2
    rng = np.random.default_rng(SEED)
    q = jnp.asarray(rng.standard_normal((slots, d)) * 0.1, jnp.bfloat16)
    ck, cv = (jnp.asarray(rng.standard_normal((layers, slots, total, d)),
                          jnp.bfloat16) for _ in range(2))
    rows = []
    for block_t in blocks:
        bt = block_t or pick_block_t(total, d, jnp.bfloat16)

        @jax.jit
        def chain(q, ck, cv, wpos, first):
            work = work_list(wpos, total, bt, first)
            for j in range(_TIMED_CALLS):
                q = q + (attend(q, ck, cv, wpos, work, j % layers, bt)
                         if fills is None else
                         attend(q, ck, cv, wpos, work, j % layers, bt, first))
            return q

        spans = fills or {fill: [(0, w) for w in wpos] for fill, wpos
                          in decode_fills(slots, total).items()}
        for fill, span in spans.items():
            live = sum(w - f + 1 for f, w in span if w >= 0)
            row = {"pool": [slots, total, d], "fill": fill, "block_t": bt,
                   "live_positions": live,
                   "read_positions": sum((w // bt - f // bt + 1) * bt
                                         for f, w in span if w >= 0),
                   "floor_us": round(live * d * 2 * 2 / 819e9 * 1e6, 2)}
            first = jnp.asarray([f for f, _ in span], jnp.int32)
            wpos = jnp.asarray([w for _, w in span], jnp.int32)
            chain(q, ck, cv, wpos, first).block_until_ready()
            if on_device:
                row["us_a_layer"] = _device_us(
                    lambda: chain(q, ck, cv, wpos, first), "decode_attention")
            rows.append(row)
    return rows


# The benchmark's prefill shapes ``(batch, bucket, heads)`` at heads of 64:
# GPT-2 XL's and GPT-2 large's, admission batches by bucket.
PROMPT_SHAPES = (
    [(b, p, 25) for p in (512, 768) for b in (1, 2, 4, 8)]
    + [(b, p, 20) for p in (256, 512, 768) for b in (1, 8, 16)])


def prompt_lengths(batch: int, bucket: int) -> list[int]:
    """Ragged lengths of a timed or checked prefill batch: a bucket 70-100%
    full, as the benchmark's buckets are, shortest first."""
    return [bucket * (70 + 30 * (j + 1) // batch) // 100 for j in range(batch)]


def edge_lengths(batch: int, bucket: int) -> list[int]:
    """Ragged lengths of a checked prefill batch, from 1 to ``bucket``: the
    edges of the kernel's blocks of 256 queries first (one short of, at and
    one past them), then an even spread."""
    edges = [n for n in (257, 1, bucket, 256, 513, 255, 512) if n <= bucket]
    spread = [max(1, bucket * (j + 1) // (batch + 1)) for j in range(batch)]
    return list(dict.fromkeys(edges + spread))[:batch]


EVA_FORMS = ("kernel", "windows")  # of ``TwoTier.attention``

# EvaByte's prefill buckets at the published widths (a prompt a dispatch, 32
# heads of 128, windows of 2,048, a summary a chunk of 16): each bucket full,
# then the two prompts of the benchmark's cell.
EVA_PROMPT_SHAPES = (
    [(1, p, 32, [p]) for p in (4096, 6144, 8192, 12288)]
    + [(1, 4096, 32, [2923]), (1, 6144, 32, [5739])])


def time_prompt_attention(forms: dict, shapes, on_device: bool,
                          head_dim: int = 64, chunk: int | None = None,
                          score_elements=None):
    """Device microseconds a layer of a prefill's prompt attention in each
    of ``forms`` (``{name: attend(q, k, v, lengths, heads)}``: the kernel
    and the ``jax.numpy`` form), alone: one row a shape ``(batch, bucket,
    heads[, lengths])`` (heads of ``head_dim``, bfloat16,
    :func:`prompt_lengths` where none are given) and a form.  With
    ``chunk`` the forms are a windowed family's, ``attend(q, k, v, lengths,
    heads, kbar, vbar)`` with a summary ``[batch, bucket / chunk, D]`` a
    chunk.  ``us_a_layer`` is everything the chained program runs over its
    calls (the kernel, or the fusions that write and read the scores; a
    scan whole, by the union of the device's intervals), ``kernel_us`` the
    profiler's events named ``prompt_attention`` alone; beside them the
    bytes of the float32 scores the ``jax.numpy`` form writes at once
    (``score_elements(batch, heads, bucket)``: ``[B, H, P, P]`` unless
    said), which the picker's rule reads.  Off the device the rows carry no
    time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(SEED)
    rows = []
    for batch, bucket, heads, *given in shapes:
        d = heads * head_dim
        q, k, v = (jnp.asarray(rng.standard_normal((batch, bucket, d)) * 0.5,
                               jnp.bfloat16) for _ in range(3))
        summaries = () if chunk is None else tuple(
            jnp.asarray(rng.standard_normal((batch, bucket // chunk, d))
                        * 0.5, jnp.bfloat16) for _ in range(2))
        lengths = jnp.asarray(
            given[0] if given else prompt_lengths(batch, bucket), jnp.int32)
        elements = (score_elements(batch, heads, bucket) if score_elements
                    else batch * heads * bucket ** 2)
        for form, attend in forms.items():
            @jax.jit
            def chain(q, k, v, lengths, *summaries):
                for _ in range(_TIMED_CALLS):  # each waits for the last
                    q = q + attend(q, k, v, lengths, heads, *summaries) * 0.01
                return q

            row = {"shape": [batch, bucket, d], "form": form,
                   "score_mb": round(elements * 4 / 2 ** 20, 1)}
            if given:
                row["lengths"] = given[0]
            chain(q, k, v, lengths, *summaries).block_until_ready()
            if on_device:
                compute, counts, busy = _device_ns(
                    lambda: chain(q, k, v, lengths, *summaries))
                row["us_a_layer"] = round(busy / _TIMED_CALLS / 1e3, 2)
                name = "prompt_attention"
                if form == "kernel":
                    assert counts[name] == _TIMED_CALLS, dict(counts)
                    row["kernel_us"] = round(
                        compute[name] / _TIMED_CALLS / 1e3, 2)
            rows.append(row)
    return rows


_MOVES = ("copy", "slice", "dynamic-slice", "transpose")


def pool_sized_moves(hlo_text: str, elements: int,
                     ops: tuple = ()) -> list[tuple[int, str]]:
    """The instructions of an optimised HLO module that materialise a copy,
    a slice or a transposition (or one of ``ops`` besides: a prefill is
    asked for ``pad`` and ``broadcast`` too, what a cache made of zeros
    compiles to) of at least ``elements`` elements, largest first, as
    ``(elements, "computation: instruction = shape op")``.

    Counted: ``copy``, ``slice``, ``dynamic-slice`` and ``transpose`` (their
    asynchronous ``-start`` / ``-done`` halves too) outside fused
    computations, and fusions the compiler named after one of them
    (``slice_bitcast_fusion``, ``copy_fusion``).  Not counted: the same
    operations inside a fusion, where they are the consumer's addressing and
    write nothing, and the in-place scatter that adds a step's row to the
    pool (its result has the pool's shape and the pool's buffer).
    """
    import re

    fused = set(re.findall(r"\bcalls=%?([\w.\-]+)", hlo_text))
    head = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
    inst = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = "
                      r"\(?([a-z]+[0-9]*)\[([0-9,]*)\]\S* ([\w\-]+)\(")
    found, comp = [], ""
    for line in hlo_text.splitlines():
        m = head.match(line)
        if m:
            comp = m.group(1)
            continue
        m = inst.match(line)
        if not m or comp in fused:
            continue
        name, dtype, dims, op = m.groups()
        base = re.sub(r"-(start|done)$", "", op)
        named = op == "fusion" and any(
            part in _MOVES + ops for part in re.split(r"[_.]", name))
        if base not in _MOVES + ops and not named:
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        if n >= elements:
            found.append((n, f"{comp}: {name} = {dtype}[{dims}] {op}"))
    return sorted(found, reverse=True)


def prefill_pool_moves(hlo_text: str, rows: int, width: int) -> list:
    """What a compiled prefill moves of the pool beside writing its rows:
    :func:`pool_sized_moves`, a ``pad`` or a ``broadcast`` among them, of a
    slot's rows of one layer or more (``rows`` x ``width`` elements) with
    the pool's ``rows`` among its dimensions (an activation ``[B, P, D]``
    or a weight has not).  A prefill that makes no cache of its own and
    copies nothing into the pool afterwards has none."""
    import re

    return [m for m in pool_sized_moves(hlo_text, rows * width,
                                        ("pad", "broadcast"))
            if re.search(rf"\[(\d+,)*{rows}(,\d+)*\]", m[1])]


def time_admission_prefill(on_device: bool) -> dict:
    """GPT-2 XL's admission prefill of four prompts of 768 into slots of the
    benchmark's pool (8 x 960), alone: the device's milliseconds a run by
    the profiler (the mean of three, each writing other slots of the pool it
    was handed back), the heaviest operations of a run, and what its
    compiled text still moves of the pool (:func:`prefill_pool_moves`: a
    cache of zeros, a copy of a slot).  Off the device a tiny shape and no
    time."""
    import jax
    import jax.numpy as jnp

    from pytorch_zappa_serverless_tpu.models import gpt2

    if on_device:
        cfg = gpt2.GPT2Config(d_model=1600, layers=48, heads=25, ffn_dim=6400)
        batch, bucket, slots, total = 4, 768, 8, 960
    else:
        cfg = gpt2.GPT2Config(**TINY_GPT2)
        batch, bucket, slots, total = 4, 16, 8, 32
    runs = 3
    prefill, shapes = prefill_program(cfg, batch, bucket, slots, total)
    compiled = prefill.lower(*shapes).compile()
    moves = prefill_pool_moves(compiled.as_text(), total, cfg.d_model)
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 1024))
    params = jax.tree.map(
        lambda sd: (jax.random.normal(next(keys), sd.shape, jnp.float32)
                    * 0.02).astype(sd.dtype), shapes[0])
    pool = [jnp.zeros(sd.shape, sd.dtype) for sd in shapes[1:3]]
    tokens = jax.random.randint(next(keys), (batch, bucket), 0,
                                cfg.vocab_size - 1, jnp.int32)
    lengths = jnp.full((batch,), bucket, jnp.int32)

    def run():
        nonlocal pool
        for n in range(runs):
            at = (jnp.arange(batch, dtype=jnp.int32) + 3 * n) % slots
            logits, *pool = compiled(params, *pool, at, tokens, lengths)
        jax.block_until_ready(pool)
        return logits

    run()
    row = {"shape": [batch, bucket], "pool": [cfg.layers, slots, total,
                                              cfg.d_model],
           "pool_moves": [desc.split(": ", 1)[1] for _, desc in moves[:4]],
           "pool_moves_count": len(moves),
           "temp_mb": round(
               compiled.memory_analysis().temp_size_in_bytes / 2 ** 20, 1)}
    if on_device:
        compute, _, busy = _device_ns(run)
        row["device_ms_a_run"] = round(busy / runs / 1e6, 3)
        row["ops_ms_a_run"] = {
            name: round(ns / runs / 1e6, 3) for name, ns in sorted(
                compute.items(), key=lambda kv: -kv[1])[:8]}
    return row


def _gpt2_shapes(cfg, sharding):
    """``(sd, params)``: a bfloat16 shape maker placed by ``sharding`` where
    one is given, and GPT-2's parameter tree as shapes alone."""
    import jax
    import jax.numpy as jnp

    D, F = cfg.d_model, cfg.ffn_dim

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def dense(i, o):
        return {"kernel": sd(i, o), "bias": sd(o)}

    def ln():
        return {"scale": sd(D), "bias": sd(D)}

    params = {"wte": sd(cfg.vocab_size, D), "wpe": sd(cfg.max_positions, D),
              "ln_f": ln()}
    for i in range(cfg.layers):
        params[f"layer{i}"] = {
            "ln1": ln(), "ln2": ln(), "q": dense(D, D), "k": dense(D, D),
            "v": dense(D, D), "out": dense(D, D), "fc1": dense(D, F),
            "fc2": dense(F, D)}
    return sd, params


def segment_program(cfg, slots: int, total: int, sharding=None):
    """The decode segment (8 tokens) over a bfloat16 pool of ``slots`` x
    ``total`` as a jitted function, and its arguments as shapes alone
    (nothing is allocated), placed by ``sharding`` where one is given."""
    import jax
    import jax.numpy as jnp

    from pytorch_zappa_serverless_tpu.models import decoder, gpt2

    sd, params = _gpt2_shapes(cfg, sharding)
    pool = sd(cfg.layers, slots, total, cfg.d_model)
    i32, f32 = sd(slots, dtype=jnp.int32), sd(slots, dtype=jnp.float32)
    segment = jax.jit(
        lambda p, ck, cv, tok, pos, st, fin, temp, seeds, topk, topp:
        decoder.decode_segment(gpt2.family(cfg), p,
                               decoder.slot_pool(ck, cv), tok, pos, st, fin,
                               temp, seeds, 8, jnp.bfloat16, top_k=topk,
                               top_p=topp),
        donate_argnums=(1, 2))
    return segment, (params, pool, pool, i32, i32, i32,
                     sd(slots, dtype=jnp.bool_), f32, i32, i32, f32)


def prefill_program(cfg, batch: int, bucket: int, slots: int, total: int,
                    sharding=None):
    """The admission prefill of ``batch`` prompts of ``bucket`` positions
    into a bfloat16 pool of ``slots`` x ``total``, which it is given to
    write into (donated) with the slot of each prompt, as a jitted function,
    and its arguments as shapes alone, as :func:`segment_program`."""
    import jax
    import jax.numpy as jnp

    from pytorch_zappa_serverless_tpu.models import decoder, gpt2

    sd, params = _gpt2_shapes(cfg, sharding)
    pool = sd(cfg.layers, slots, total, cfg.d_model)
    prefill = jax.jit(
        lambda p, ck, cv, at, tokens, lengths:
        decoder.prefill(gpt2.family(cfg), p, tokens, lengths, (ck, cv), at,
                        jnp.bfloat16), donate_argnums=(1, 2))
    return prefill, (params, pool, pool, sd(batch, dtype=jnp.int32),
                     sd(batch, bucket, dtype=jnp.int32),
                     sd(batch, dtype=jnp.int32))


def _segment_child(rehearse: bool) -> None:
    """Compile the decode segment at GPT-2 XL's serving shape, from shapes
    alone (nothing is allocated), and look through its optimised HLO for a
    layer of the pool being moved (:func:`pool_sized_moves`)."""
    from pytorch_zappa_serverless_tpu.models import gpt2

    if rehearse:
        cfg = gpt2.GPT2Config(**TINY_GPT2)
        slots, total = 4, 32
    else:
        cfg = gpt2.GPT2Config(d_model=1600, layers=48, heads=25,
                              ffn_dim=6400)
        slots, total = 8, 960
    D = cfg.d_model
    segment, args = segment_program(cfg, slots, total)
    text = segment.lower(*args).compile().as_text()
    layer = slots * total * D
    # What is there at all: the largest such move of a tenth of a layer up.
    moves = pool_sized_moves(text, layer // 10)
    print(f"segment [{cfg.layers} layers, {slots} slots x {total} x {D}]: "
          f"one layer of the pool is {layer} elements; largest copy, slice "
          "or transpose outside a fusion: "
          + (f"{moves[0][0]} elements ({moves[0][1]})" if moves else
             f"none of {layer // 10} elements or more"))
    # Of the pool: whole rows of ``total x D`` (the embedding table, which
    # the compiler lays out anew once a segment, is larger and is not).
    whole = [m for m in moves if m[0] >= layer and m[0] % (total * D) == 0]
    if not rehearse:
        assert not whole, (
            f"the segment program moves a whole layer of the pool "
            f"{len(whole)} times a step:\n  "
            + "\n  ".join(desc for _, desc in whole[:6]))
    print(json.dumps({"pool_sized_moves": len(whole),
                      "decode_kernel": "decode_attention" in text}))


def _evabyte_child(rehearse: bool) -> None:
    """EvaByte's own programs against its plain reference, on one device.

    The weights are drawn on the device (what a host draw of 3.2 billion
    values would cost a minute for) as ``init_evabyte_params`` draws them;
    the programs are the ones ``decoder.make_servable`` hands the scheduler
    (``prefill_start`` and ``decode_segment`` over the family's rows), the
    prompt ends six positions before a window's end, and the four segments
    that follow cross it.  ``choose`` is watched, not replaced: it reports
    the logits it was given, and the greedy bytes are the program's own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import evabyte as reference
    from pytorch_zappa_serverless_tpu.models import decoder, evabyte
    from pytorch_zappa_serverless_tpu.ops import decode_attention as da

    if rehearse:
        cfg = evabyte.EvaByteConfig(
            vocab_size=320, hidden_size=64, layers=2, heads=2,
            intermediate_size=96, max_positions=512, window_size=32,
            chunk_size=4, init_std=0.2, eos_id=320)
        dtype, prompt, bucket, total = jnp.float32, 58, 64, 64 + 40
    else:
        cfg = evabyte.EvaByteConfig(layers=16, eos_id=320)
        dtype, prompt, bucket, total = jnp.bfloat16, 4090, 4096, 12288 + 768
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16 * cfg.layers))

    def w(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * cfg.init_std).astype(dtype)

    def pool():
        return jnp.clip(jax.random.normal(next(keys), (D,)), -1.0,
                        1.0) * cfg.init_std

    params = {"embed": w(V, D), "norm": jnp.zeros((D,)),
              "head": w(D, V * cfg.num_pred_heads)}
    for i in range(cfg.layers):
        params[f"layer{i}"] = {
            "n1": jnp.zeros((D,)), "n2": jnp.zeros((D,)), "q": w(D, D),
            "k": w(D, D), "v": w(D, D), "o": w(D, D), "mu": pool(),
            "phi": pool(), "gate": w(D, F), "up": w(D, F), "down": w(F, D)}
    align = min(da.block_rows(D, dtype), cfg.window_size)
    fam = evabyte.family(cfg, evabyte.TwoTier(
        cfg.window_size, cfg.chunk_size, cfg.heads, align))
    T = fam.rows.count(total)
    print(f"evabyte: {cfg.layers} layers of {D}, {T} rows a slot, read in "
          f"blocks of {da.read_block(T, D, dtype)}")
    assert rehearse or da.read_block(T, D, dtype) == 64, "the jnp form serves"
    form = fam.rows.prompt_form(1, cfg.heads, bucket, D // cfg.heads)
    print(f"evabyte: the prompt attention of a [1, {bucket}] prefill takes "
          f"form {form}")
    assert form == ("windows" if rehearse else "kernel"), form

    seen = {}
    choose = decoder.choose

    def watched(logits, temperature, seeds, t, top_k=None, top_p=None):
        jax.debug.callback(
            lambda lg, tt: seen.update({int(tt[0]): np.asarray(lg[0])}),
            logits, t)
        return choose(logits, temperature, seeds, t, top_k, top_p)

    decoder.choose = watched
    rng = np.random.default_rng(SEED)
    ids = [int(t) for t in rng.integers(0, V, prompt)]
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :prompt] = ids
    z, zi = jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32)
    prefill = jax.jit(lambda p, cache, toks, lens: decoder.prefill_start(
        fam, p, toks, lens, z, zi, cache, zi, dtype, top_k=zi, top_p=z + 1),
        donate_argnums=(1,))
    segment = jax.jit(
        lambda p, ck, cv, tok, pos, st, fin: decoder.decode_segment(
            fam, p, decoder.slot_pool(ck, cv, fam.rows), tok, pos, st, fin,
            z, zi, 8, dtype, top_k=zi, top_p=z + 1), donate_argnums=(1, 2))
    t0 = time.monotonic()
    tok, ck, cv = prefill(params, decoder.zero_cache(fam, 1, total, dtype),
                          jnp.asarray(toks), jnp.asarray([prompt], jnp.int32))
    pos, st, fin = jnp.asarray([prompt], jnp.int32), zi, jnp.zeros((1,), bool)
    served = []
    for _ in range(4):
        emits, ck, cv, tok, pos, st, fin = segment(params, ck, cv, tok, pos,
                                                   st, fin)
        served += [int(t) for t in np.asarray(emits)[0]]
    jax.effects_barrier()
    print(f"evabyte: prefill of {prompt} bytes and 4 segments in "
          f"{time.monotonic() - t0:.1f} s (compiles included); bytes "
          f"{served[:8]}...")
    got = np.stack([seen[t] for t in range(32)])
    config = {"num_attention_heads": cfg.heads,
              "window_size": cfg.window_size, "chunk_size": cfg.chunk_size,
              "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
              "vocab_size": V}
    report = {}
    for name, int8 in (("float32", False), ("int8", True)):
        t0 = time.monotonic()
        ref = reference.forward(params, ids + served[:-1], config, cfg.layers,
                                int8)[prompt - 1:]
        deficit = max(float(np.max(r) - r[t]) for r, t in zip(ref, served))
        report[name] = {"max_abs_logit_diff": float(np.max(np.abs(got - ref))),
                        "deficit": deficit,
                        "seconds": round(time.monotonic() - t0, 1)}
    report["logit_std"] = float(np.std(got))
    print("evabyte " + json.dumps(report))
    # bfloat16 activations through the layers against float32: the logits
    # (of spread ``logit_std``) within LOGIT_TOL everywhere; the reference
    # through int8 weights further off than that, or the check could not
    # tell the two precisions apart.
    tol = 1e-3 if rehearse else EVABYTE_LOGIT_TOL
    assert report["float32"]["max_abs_logit_diff"] <= tol, report
    assert rehearse or report["int8"]["max_abs_logit_diff"] > tol, report
    print(json.dumps(report))


def _against_reference(forward, judge, config, runs, got, control,
                       judged_tokens=None) -> dict:
    """A child's served logits ``got`` (one ``[tokens, V]`` a run) against
    ``forward(run, control)``, the plain reference's at the same positions:
    the largest and the root-mean-square difference over all of them, and
    the cell's own comparison of the served tokens (the family's ``judge``
    with the configuration's limits, over the first ``judged_tokens`` a run
    where the cell asks for fewer than were served)."""
    import numpy as np

    t0 = time.monotonic()
    worst, squares, count, refs = 0.0, 0.0, 0, []
    for run, mine in zip(runs, got):
        ref = forward(run, control)
        worst = max(worst, float(np.max(np.abs(mine - ref))))
        squares += float(np.sum(np.square(mine - ref)))
        count += mine.size
        refs.append(ref[:judged_tokens])
    judged = judge(config, [{**r, "tokens": r["tokens"][:judged_tokens]}
                            for r in runs], refs)
    return {"max_abs_logit_diff": worst,
            "rms_logit_diff": (squares / count) ** 0.5,
            "far_share": judged["worst"], "ok": judged["ok"],
            "judged": judged["note"],
            "seconds": round(time.monotonic() - t0, 1)}


def _nemotron_child(rehearse: bool) -> None:
    """Nemotron-H's programs against its plain reference, on one device, at
    the benchmark cell's widths and sizes (``benchmark/configs/
    nemotron3-super-11l.json``; its ``rehearse`` widths on the CPU).

    The servable is ``decoder.make_servable``'s over the tree the benchmark
    stages (the program's own seeded weights, the routers' biases balanced:
    ``benchmark/families/nemotron_h.py``), and the jitted programs are
    ``build_gen_kernels``'s, as
    the scheduler runs them: a prefill of 1, 2, 4 and 8 prompts at every
    bucket, each prompt's rows written into a pool of every slot (the later
    ones re-use a slot), then 256 decode steps with every slot live.
    ``choose`` is watched, not replaced: it reports the logits it was given.
    Then the reference's full forward pass over prompt + served tokens, a
    sequence at a time, for a few of the slots; the same comparison against
    the reference in the precision below (``int8``), which must fail, by
    the logits and by the cell's own comparison of the served tokens; and
    ``expert_matmul`` against ``jax.lax.ragged_dot`` at the group layouts a
    step and a prefill produce."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import nemotron_h as bench_family
    from benchmark.reference import nemotron_h as reference
    from pytorch_zappa_serverless_tpu.config import ModelConfig
    from pytorch_zappa_serverless_tpu.models import decoder, nemotron_h
    from pytorch_zappa_serverless_tpu.ops import expert_matmul as em
    from pytorch_zappa_serverless_tpu.serving.generation import (
        build_gen_kernels)

    config = json.loads((ROOT / "benchmark" / "configs"
                         / "nemotron3-super-11l.json").read_text())
    serve = config["serve"]
    buckets, extra = serve["seq_buckets"], dict(serve["extra"])
    dtype, steps = "bfloat16", 256
    if rehearse:
        buckets = config["rehearse"]["seq_buckets"]
        extra.update(config["rehearse"]["extra"])
        dtype, steps = "float32", 16
        config["weights"]["dtype"] = "float32"
    cfg = nemotron_h.config_from_arch(extra["arch"])
    t0 = time.monotonic()
    tree = bench_family.init_tree(SEED, config, {"extra": extra})
    print(f"nemotron: {len(cfg.pattern)} layers ({cfg.pattern}) drawn and "
          f"their routers balanced in {time.monotonic() - t0:.0f} s",
          flush=True)
    sv = decoder.make_servable(
        "nemotron_h", ModelConfig(name="nemotron_h", dtype=dtype,
                                  batch_buckets=(1,), seq_buckets=buckets,
                                  extra=extra),
        nemotron_h.family(cfg, jnp.dtype(dtype)), tree)
    del tree
    params, meta = sv.params, sv.meta["continuous"]
    kernels = build_gen_kernels(types.SimpleNamespace(servable=sv))
    S, seg, V = meta["slots"], meta["segment_tokens"], cfg.vocab_size
    print("nemotron: cache leaves " + json.dumps(
        [[list(shape), str(np.dtype(dt))]
         for shape, dt in meta["cache_leaves"]]), flush=True)

    seen = []
    choose = decoder.choose

    def watched(logits, temperature, seeds, t, top_k=None, top_p=None):
        jax.debug.callback(lambda lg: seen.append(np.asarray(lg)), logits)
        return choose(logits, temperature, seeds, t, top_k, top_p)

    decoder.choose = watched
    rng = np.random.default_rng(SEED)
    cache = kernels["alloc_cache"]()
    held = {}        # slot -> (ids, the logits its prefill chose from)
    tok, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
    n = 0
    t0 = time.monotonic()
    for bucket in buckets:
        for B in (1, 2, 4, 8):
            # Ragged: one prompt fills the bucket, one is shorter than a
            # chunk, the others fall between.
            lens = [bucket, 3, *rng.integers(4, bucket, 6)][:B]
            toks = np.zeros((B, bucket), np.int32)
            for j, m in enumerate(lens):
                toks[j, :m] = rng.integers(0, V, m)
            payload = {"input_ids": toks, "length": np.asarray(lens, np.int32),
                       "temperature": np.zeros(B, np.float32),
                       "seed": np.zeros(B, np.int32),
                       "top_k": np.zeros(B, np.int32),
                       "top_p": np.ones(B, np.float32)}
            seen.clear()
            first, *cache = kernels["prefill"](
                params, tuple(cache),
                np.asarray([(n + j) % S for j in range(B)], np.int32),
                payload)
            first = np.asarray(first)
            jax.effects_barrier()
            for j, m in enumerate(lens):
                slot = n % S
                held[slot] = ([int(t) for t in toks[j, :m]], seen[0][j])
                tok[slot], pos[slot] = first[j], m
                n += 1
    live = sorted(held)
    fin = np.ones(S, bool)
    fin[live] = False
    print(f"nemotron: {n} prompts prefilled in batches of 1, 2, 4, 8 at "
          f"{buckets} into {len(live)} of {S} slots in "
          f"{time.monotonic() - t0:.0f} s (compiles included)", flush=True)
    zf, zi = np.zeros(S, np.float32), np.zeros(S, np.int32)
    st = zi.copy()
    seen.clear()
    emits, counts = [], np.zeros(3, np.int64)
    t0 = time.monotonic()
    for _ in range(steps // seg):
        packed, *cache = kernels["segment"](params, tuple(cache), tok, pos,
                                            st, fin, zf, zi, zi, zf + 1)
        packed = np.asarray(packed)
        emits.append(packed[:, :seg])
        tok, pos, st = (packed[:, seg + k].copy() for k in range(3))
        counts += packed[0, seg + 4:]
    jax.effects_barrier()
    emits = np.concatenate(emits, axis=1)                     # [S, steps]
    layers = cfg.pattern.count("E")
    print(f"nemotron: {steps} decode steps in {time.monotonic() - t0:.1f} s "
          f"(compile included); a layer a step {counts[0] / layers / steps:.1f}"
          f" rows reach {counts[1] / layers / steps:.1f} of "
          f"{cfg.experts_held} held experts, at most "
          f"{counts[2] / layers / steps:.1f} on one", flush=True)
    assert len(seen) == steps and not fin[live].any()
    del cache
    decoder.choose = choose

    # The reference, a sequence at a time: the shortest prompt, the longest,
    # and every fourth slot (the first of them were re-used).
    by_len = sorted(live, key=lambda s: len(held[s][0]))
    picked = sorted({by_len[0], by_len[-1], *live[::4]})
    keys = bench_family.published({"extra": extra})
    report = {"logit_std": float(np.std(seen[0][picked]))}
    controls = (None,) if rehearse else (None, "int8")
    runs = [{"ids": held[slot][0], "tokens": emits[slot].tolist()}
            for slot in picked]
    # The logits each served token was chosen from: the prefill's, then every
    # step's but the last.
    got = [np.stack([held[slot][1]]
                    + [seen[t][slot] for t in range(steps - 1)])
           for slot in picked]

    def forward(run, control):
        return reference.forward(params, run["ids"] + run["tokens"][:-1],
                                 keys, control)[len(run["ids"]) - 1:]

    for control in controls:
        report[control or "float32"] = _against_reference(
            forward, bench_family.judge, config, runs, got, control)
        print(f"nemotron: reference {control or 'float32'} over slots "
              f"{picked}: " + json.dumps(report[control or "float32"]),
              flush=True)

    # The grouped matmul in its two forms, at the layouts a step (704 rows,
    # a row or two an expert, some empty) and a prefill (8 x 512 x 22 rows,
    # a quarter of them held) produce; every row on one expert; none held.
    G, L, F = cfg.experts_held, cfg.latent_size, cfg.expert_width
    w1 = params[f"layer{cfg.pattern.index('E')}"]["w1"]
    layouts = {}
    for name, rows_ in (("step", S * cfg.top_k),
                        ("prefill", 8 * buckets[-1] * cfg.top_k)):
        share = G / cfg.experts_published
        sizes = rng.multinomial(int(rows_ * share), np.ones(G) / G)
        layouts[name] = (rows_, sizes)
    one = np.zeros(G, np.int64)
    one[G // 3] = S * cfg.top_k
    layouts["one expert"] = (S * cfg.top_k, one)
    layouts["none held"] = (S * cfg.top_k, np.zeros(G, np.int64))
    for name, (rows_, sizes) in layouts.items():
        x = jnp.asarray(rng.standard_normal((rows_, L)), w1.dtype)
        sz = jnp.asarray(sizes, jnp.int32)
        want = jax.lax.ragged_dot(x, w1, sz,
                                  preferred_element_type=jnp.float32)
        want = np.asarray(jnp.square(jnp.maximum(want, 0)))[:sizes.sum()]
        got = np.asarray((em.expert_matmul_kernel(
            x, w1, sz, relu2=True, interpret=rehearse)).astype(jnp.float32)
        )[:sizes.sum()]
        off = float(np.max(np.abs(got - want) / (np.abs(want) + 1e-2))) \
            if sizes.sum() else 0.0
        report[f"expert_matmul {name}"] = {
            "rows": int(rows_), "held": int(sizes.sum()),
            "empty": int((sizes == 0).sum()), "max_rel_diff": off}
        # One rounding to bfloat16 apart (float32 on the CPU).
        assert off <= (1e-4 if rehearse else 2 ** -7), (name, off)
    stats = jax.local_devices()[0].memory_stats() or {}
    report["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    print("nemotron " + json.dumps(report))
    limits = {"max_abs_logit_diff": NEMOTRON_LOGIT_TOL,
              "rms_logit_diff": NEMOTRON_RMS_TOL}
    if rehearse:
        limits = dict.fromkeys(limits, 1e-3)
    for key, limit in limits.items():
        assert report["float32"][key] <= limit, (key, report)
    assert report["float32"]["ok"], report
    # The precision below must fail: every matrix through int8 does, by the
    # two limits on the logits and by the cell's own comparison of the served
    # tokens (how many lie far under the reference's best, not how far the
    # farthest: that is one flipped expert's on either side).
    assert rehearse or (
        report["int8"]["rms_logit_diff"] > NEMOTRON_RMS_TOL
        and report["int8"]["max_abs_logit_diff"] > NEMOTRON_LOGIT_TOL
        and not report["int8"]["ok"]), report
    print(json.dumps(report))


def _serve_prompts_alone(kernels, params, meta, buckets, prompts,
                         new: int):
    """Each of ``prompts`` prefilled alone into a slot of the pool (slot
    ``j % S``) and decoded for ``new`` tokens in whole segments, the longest
    for twice as many, by the programs ``build_gen_kernels`` jitted, as the
    scheduler runs them → ``(runs, got)``: ``{"ids", "tokens"}`` a prompt and
    the logits each served token was chosen from ``[tokens, V]``.
    ``decoder.choose`` is watched, not replaced: it reports the logits it
    was given."""
    import jax
    import numpy as np

    from pytorch_zappa_serverless_tpu.models import decoder

    seen = []
    choose = decoder.choose

    def watched(logits, temperature, seeds, t, top_k=None, top_p=None):
        jax.debug.callback(lambda lg: seen.append(np.asarray(lg)), logits)
        return choose(logits, temperature, seeds, t, top_k, top_p)

    decoder.choose = watched
    S, seg = meta["slots"], meta["segment_tokens"]
    long_one = max(range(len(prompts)), key=lambda j: len(prompts[j]))
    cache = kernels["alloc_cache"]()
    zf, zi = np.zeros(S, np.float32), np.zeros(S, np.int32)
    runs, got = [], []
    try:
        for j, ids in enumerate(prompts):
            bucket = next(b for b in buckets if b >= len(ids))
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :len(ids)] = ids
            payload = {"input_ids": toks,
                       "length": np.asarray([len(ids)], np.int32),
                       "temperature": np.zeros(1, np.float32),
                       "seed": np.zeros(1, np.int32),
                       "top_k": np.zeros(1, np.int32),
                       "top_p": np.ones(1, np.float32)}
            seen.clear()
            slot = j % S
            first, *cache = kernels["prefill"](params, tuple(cache),
                                               np.asarray([slot], np.int32),
                                               payload)
            tok, pos, fin = zi.copy(), zi.copy(), np.ones(S, bool)
            tok[slot], pos[slot], fin[slot] = int(np.asarray(first)[0]), \
                len(ids), False
            st, emits = zi.copy(), []
            steps = 2 * new if j == long_one else new  # four segments for one
            for _ in range(steps // seg):
                packed, *cache = kernels["segment"](params, tuple(cache), tok,
                                                    pos, st, fin, zf, zi, zi,
                                                    zf + 1)
                packed = np.asarray(packed)
                emits.append(packed[:, :seg])
                tok, pos, st = (packed[:, seg + k].copy() for k in range(3))
            jax.effects_barrier()
            served = np.concatenate(emits, axis=1)[slot].tolist()
            # The logits each served token was chosen from: the prefill's,
            # then every step's but the last.
            got.append(np.stack([seen[0][0]]
                                + [lg[slot] for lg in seen[1:steps]]))
            runs.append({"ids": ids, "tokens": served})
    finally:
        decoder.choose = choose
    return runs, got


def _busy_us(run) -> float:
    """Device microseconds of one of the ``_TIMED_CALLS`` calls a profiled
    ``run()`` chains: the union of every device interval, so a form that is
    XLA's fusions and a form that is one kernel are held to one clock."""
    return round(_device_ns(run)[2] / _TIMED_CALLS / 1e3, 2)


def time_grouped_attention(shapes, on_device: bool, interpret: bool):
    """``decode_attention`` with grouped queries, alone, beside the
    ``jax.numpy`` grouped form over the same pool: a row a shape ``(slots,
    total, kv_heads, head_dim, heads, live)`` (every slot holds ``live``
    rows) and a form, with the least the chip could take over the live rows'
    bytes (K and V, bfloat16, 819 GB/s).  The ``jax.numpy`` form reads all
    ``total`` rows of every slot.  Off the device the rows carry no time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_zappa_serverless_tpu.ops import decode_attention as da

    rng = np.random.default_rng(SEED)
    rows, pools = [], {}
    for slots, total, kv, dh, heads, live in shapes:
        d = kv * dh
        if (slots, total, d) not in pools:
            pools.clear()  # one pool at a time on the device
            pools[slots, total, d] = [jnp.asarray(
                rng.standard_normal((2, slots, total, d), dtype=np.float32),
                jnp.bfloat16) for _ in range(2)]
        ck, cv = pools[slots, total, d]
        q = jnp.asarray(rng.standard_normal((slots, heads * dh)) * 0.1,
                        jnp.bfloat16)
        wpos = jnp.full((slots,), live - 1, jnp.int32)
        bt = da.pick_block_t(total, d, jnp.bfloat16)

        def kernel(q, ck, cv, wpos, work, layer):
            return da.decode_attention(q, ck, cv, wpos, work, layer=layer,
                                       heads=heads, block_t=bt,
                                       interpret=interpret)

        def numpy_form(q, ck, cv, wpos, work, layer):
            return da._attend_grouped(q[:, None], ck[layer], cv[layer],
                                      wpos[:, None], None, heads)[:, 0]

        floor = slots * live * d * 2 * 2 / 819e9 * 1e6
        got = {}
        for form, attend in (("kernel", kernel), ("jax.numpy", numpy_form)):
            @jax.jit
            def chain(q, ck, cv, wpos):
                work = da.work_list(wpos, total, bt)
                for j in range(_TIMED_CALLS):
                    q = q + attend(q, ck, cv, wpos, work, j % 2)
                return q

            chain(q, ck, cv, wpos).block_until_ready()
            got[form] = attend(q, ck, cv, wpos, da.work_list(wpos, total, bt),
                               0)
            row = {"pool": [slots, total, d], "heads": heads, "live": live,
                   "form": form, "block_t": bt, "floor_us": round(floor, 2)}
            if on_device:
                row["us_a_layer"] = _busy_us(lambda: chain(q, ck, cv, wpos))
                row["gb_per_s"] = round(
                    floor * 819 / row["us_a_layer"], 1)
                row["share_of_819"] = round(floor / row["us_a_layer"], 3)
            rows.append(row)
        off = float(jnp.max(jnp.abs(got["kernel"].astype(jnp.float32)
                                    - got["jax.numpy"].astype(jnp.float32))))
        assert off < 0.05, (slots, total, kv, live, off)
    return rows


def expert_sizes(kind: str, experts: int, rows_an_expert: int, rng):
    """Rows on each of ``experts`` experts, int64 [experts]: ``uniform``
    gives every expert ``rows_an_expert`` (every group then begins on a tile
    boundary, which a router's groups do not); ``routed`` draws ``experts x
    rows_an_expert`` assignments evenly over the experts, as a balanced
    router spreads a prompt's ``top_k`` x P."""
    import numpy as np

    if kind == "uniform":
        return np.full(experts, rows_an_expert, np.int64)
    return rng.multinomial(experts * rows_an_expert,
                           np.ones(experts) / experts)


def time_gated_experts(rows_an_expert, on_device: bool, interpret: bool,
                       experts: int = 64, width: int = 2048,
                       inner: int = 1536, kinds=("uniform", "routed")):
    """The gated ``expert_matmul`` (``silu(x W1) * (x W3)``) and the plain
    one after it (``W2``), alone, at a mean of ``rows_an_expert`` rows on
    each of ``experts`` experts, with the sizes of each of ``kinds``
    (:func:`expert_sizes`): device microseconds a call beside the share of
    819 GB/s (every expert's matrices once, the rows in and out) and of 197
    TFLOP/s (two operations a weight a row), the grid steps a block of
    columns the call made (``visits``) over the fewest tiles that hold its
    groups (``least``), and the largest relative difference from
    ``jax.lax.ragged_dot``.  Off the device no time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_zappa_serverless_tpu.ops import expert_matmul as em

    rng = np.random.default_rng(SEED)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32) * 0.02,
                           jnp.bfloat16)

    w1, w3, w2 = (w(experts, width, inner), w(experts, width, inner),
                  w(experts, inner, width))
    out = []
    for r, kind in ((r, k) for r in rows_an_expert for k in kinds):
        M = experts * r
        drawn = expert_sizes(kind, experts, r, rng)
        sizes = jnp.asarray(drawn, jnp.int32)
        x = jnp.asarray(rng.standard_normal((M, width)), jnp.bfloat16)
        y = jnp.asarray(rng.standard_normal((M, inner)) * 0.1, jnp.bfloat16)
        want = (jax.nn.silu(jax.lax.ragged_dot(
            x, w1, sizes, preferred_element_type=jnp.float32))
            * jax.lax.ragged_dot(x, w3, sizes,
                                 preferred_element_type=jnp.float32))
        chosen = em.plan(M, width, inner, experts, 2)
        kw = {"interpret": interpret}
        if chosen.regime == "tiles":
            # The rows where ``experts`` would write them, each group on a
            # multiple of the tile: the kernel alone.
            at = em.lay_out(sizes, M, chosen.tile)
            rows = em.laid_rows(M, experts, chosen.tile)
            x, y = (jnp.zeros((rows, a.shape[1]), a.dtype).at[at].set(a)
                    for a in (x, y))
            kw.update(tile=chosen.tile, laid_out=chosen.tile)
            visits = int(em.tile_list(sizes, rows // chosen.tile,
                                      chosen.tile)[-1])
        else:
            at = jnp.arange(M)
            visits = int(em.work_list(
                sizes, -(-M // chosen.tile) * chosen.tile, chosen.tile)[3])
        got = em.expert_matmul_kernel(x, w1, sizes, w3, **kw)[at]
        off = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)
                            / (jnp.abs(want) + 1e-2)))
        assert off <= 2 ** -6, (r, kind, off)  # one rounding to bfloat16
        for name, a, mats in (("gated", x, (w1, w3)), ("down", y, (w2,))):
            @jax.jit
            def chain(a, *mats):
                for _ in range(_TIMED_CALLS):  # each waits for the last
                    o = em.expert_matmul_kernel(a, mats[0], sizes, *mats[1:],
                                                **kw)
                    a = a + (o[:, :1] * 1e-6).astype(a.dtype)
                return a

            chain(a, *mats).block_until_ready()
            n_out = inner if name == "gated" else width
            moved = (len(mats) * experts * width * inner + M * a.shape[1]
                     + M * n_out) * 2
            flops = 2 * M * width * inner * len(mats)
            row = {"rows_an_expert": r, "sizes": kind, "call": name,
                   "regime": chosen.regime, "tile": chosen.tile,
                   "visits": visits,
                   "least": int(-(-drawn // chosen.tile).sum()),
                   "max_rel_diff": round(off, 5)}
            if on_device:
                us = _busy_us(lambda: chain(a, *mats))
                row.update(us_a_call=us,
                           share_of_819=round(moved / 819e9 * 1e6 / us, 3),
                           share_of_197=round(flops / 197e12 * 1e6 / us, 3))
            out.append(row)
    return out


def time_expert_unsort(tokens: int, top_k: int, width: int, experts: int,
                       on_device: bool, interpret: bool):
    """The ``tiles`` regime's way round its two grouped calls, alone, for
    ``tokens`` rows routed ``top_k`` ways over ``experts`` experts of
    ``width`` (a router's draw: ``top_k`` distinct experts a row):

    - ``expert_combine`` by its name, device microseconds a call beside the
      share of 819 GB/s (every gathered row in, the result out; it reads
      above 1 where the compiler keeps the chained calls' result in VMEM,
      75 MB at 8,192 rows of 2,304), and its largest difference from the
      form it replaced;
    - the whole un-sort, the gather and what follows it, in both forms:
      ``combine``, and ``einsum`` (a cast of every gathered row to float32,
      ``einsum("knd,kn->nd")`` and a select, as until PR 54);
    - the two gathers (``in``: the rows laid out for the first call;
      ``out``: the second call's rows read back), each in the forms XLA
      offers for the same rows: as :func:`experts` writes it, with
      ``promise_in_bounds``, with the indices sorted (and declared so: what
      the order itself costs, no form the layer could take), and the rows
      viewed as 32-bit words; nanoseconds a gathered row.

    Every array is an argument of the timed program, and each call's
    indices or weights hang on the call before it, so that nothing is folded
    at compile time or lifted out of the chain.  Off the device no time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_zappa_serverless_tpu.ops import expert_matmul as em

    rng = np.random.default_rng(SEED)
    A = tokens * top_k
    tile = em.plan(A, width, width, experts).tile
    group = jnp.asarray(np.argsort(rng.random((tokens, experts)),
                                   axis=1)[:, :top_k], jnp.int32)
    sizes = em.group_sizes(group, experts)
    src, back = em.sorted_places(jnp.argsort(group.reshape(-1), stable=True),
                                 sizes, top_k, tile)
    back = back.T.reshape(-1)
    u = jnp.asarray(rng.standard_normal((tokens, width)), jnp.bfloat16)
    y = jnp.asarray(rng.standard_normal(
        (em.laid_rows(src.shape[0] - tile, experts, tile), width)),
        jnp.bfloat16)
    w = jnp.asarray(rng.random((tokens, top_k)), jnp.float32)
    shape = {"tokens": tokens, "top_k": top_k, "width": width}

    def einsum_form(rows, w):
        out = jnp.einsum("knd,kn->nd", rows.astype(jnp.float32), w.T)
        return jnp.where(sizes.sum() > 0, out, 0)

    def combine(rows, w):
        return em.expert_combine(rows, w, interpret=interpret)

    def unsort(then):
        return lambda y, at, w: then(
            y[at + (w[0, 0] < 0).astype(at.dtype)].reshape(
                top_k, tokens, width), w)

    def chained(step, *arrays):
        """``step(*arrays, w) -> [tokens, width]`` sixteen times, each
        call's weights from the call before."""
        @jax.jit
        def chain(w, *arrays):
            for _ in range(_TIMED_CALLS):
                w = w + step(*arrays, w)[:, :top_k] * 1e-9
            return w
        chain(w, *arrays).block_until_ready()
        return lambda: chain(w, *arrays)

    rows = y[back].reshape(top_k, tokens, width)
    off = float(jnp.max(jnp.abs(combine(rows, w) - einsum_form(rows, w))))
    assert off < 1e-4, off
    moved = A * width * 2 + tokens * width * 4 + A * 4
    out = [{"what": "expert_combine", **shape,
            "rows_a_block": em.pick_combine_rows(top_k, width, 2),
            "max_diff_from_einsum": round(off, 7)}]
    if on_device:
        us = _device_us(chained(combine, rows), "expert_combine")
        out[0].update(us_a_call=us,
                      share_of_819=round(moved / 819e9 * 1e6 / us, 3))
    del rows
    for form, then in (("combine", combine), ("einsum", einsum_form)):
        row = {"what": "unsort", **shape, "form": form}
        if on_device:
            row["us_a_call"] = _busy_us(chained(unsort(then), y, back))
        out.append(row)

    def words(x):
        return jax.lax.bitcast_convert_type(
            x.reshape(x.shape[0], -1, 2), jnp.uint32)

    forms = {
        "as written": lambda x, i: x[i],
        "promise_in_bounds": lambda x, i: x.at[i].get(
            mode="promise_in_bounds"),
        "sorted indices": lambda x, i: x.at[i].get(
            mode="promise_in_bounds", indices_are_sorted=True),
        "32-bit words": lambda x, i: jax.lax.bitcast_convert_type(
            words(x).at[i].get(mode="promise_in_bounds"),
            x.dtype).reshape(i.shape[0], -1)}
    for name, x, at in (("in", u, src), ("out", y, back)):
        for form, gather in forms.items():
            idx = jnp.sort(at) if form == "sorted indices" else at
            assert bool(jnp.all(gather(x, idx) == x[idx])), (name, form)

            @jax.jit
            def chain(x, idx):
                for _ in range(_TIMED_CALLS):  # each waits for the last
                    got = gather(x, idx)
                    idx = idx + (got[0, 0] != got[0, 0]).astype(idx.dtype)
                return idx

            chain(x, idx).block_until_ready()
            row = {"what": f"gather {name}", **shape, "form": form,
                   "rows": [int(at.shape[0]), int(x.shape[0])]}
            if on_device:
                us = _busy_us(lambda: chain(x, idx))
                row.update(us_a_call=us,
                           ns_a_row=round(us * 1e3 / at.shape[0], 1))
            out.append(row)
    return out


def _lfm2_child(rehearse: bool) -> None:
    """LFM2's kernels alone, then its programs against its plain reference,
    on one device, at the benchmark cell's widths (``benchmark/configs/
    lfm2-24b-10l.json``; its ``rehearse`` widths on the CPU).

    Alone, by the profiler's clock: ``decode_attention`` with grouped
    queries at 32 slots of 2k, 4k and 8k live rows and at Nemotron-H's shape
    beside the ``jax.numpy`` grouped form; the gated ``expert_matmul`` at 2,
    128, 256, 384 and 512 rows an expert, the sizes even and as a router
    draws them, each by the kernel's own plan; the prompt attention's two
    forms at the four buckets (the ``jax.numpy`` form where its scores fit).

    Then the servable (``decoder.make_servable`` over the tree the benchmark
    stages: the program's own seeded weights, the routers' biases balanced)
    and the programs ``build_gen_kernels`` jits, as the scheduler runs them:
    the cell's own 16 reference prompts (600 and 3,000 tokens, drawn as
    ``benchmark/run.py`` draws them), each prefilled alone into a slot of a
    pool of 32 and decoded for two segments, the first 3,000-token
    one for four.  ``choose`` is watched, not replaced: it reports the logits
    it was given.  The reference's full forward pass over prompt + served
    tokens gives the largest and the root-mean-square logit difference and,
    through the cell's own ``check`` rule (``judge``), how many served
    tokens lie far; the same against the reference through int8, which must
    fail by each."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic
    from benchmark.families import lfm2 as bench_family
    from benchmark.reference import lfm2 as reference
    from pytorch_zappa_serverless_tpu.config import ModelConfig
    from pytorch_zappa_serverless_tpu.models import decoder, lfm2
    from pytorch_zappa_serverless_tpu.serving.generation import (
        build_gen_kernels)

    on_device = not rehearse
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "lfm2-24b-10l.json").read_text())
    serve = config["serve"]
    buckets, extra = serve["seq_buckets"], dict(serve["extra"])
    dtype, scale = "bfloat16", 1.0
    if rehearse:
        buckets = config["rehearse"]["seq_buckets"]
        extra.update(config["rehearse"]["extra"])
        dtype, scale = "float32", config["rehearse"]["scale"]
        config["weights"]["dtype"] = "float32"
    cfg = lfm2.config_from_arch(extra["arch"])
    report = {}

    # -- the kernels alone ------------------------------------------------
    S = extra["gen_slots"]
    T = lfm2.family(cfg, jnp.dtype(dtype)).rows.count(
        buckets[-1] + extra["max_new_tokens"])
    shapes = [(S, T, cfg.kv_heads, cfg.head_dim, cfg.heads,
               max(T * live // 8704, 1)) for live in (2048, 4096, 8192)]
    if not rehearse:
        shapes.append((32, 1024, 2, 128, 32, 768))   # Nemotron-H's pool
    report["decode_attention grouped"] = time_grouped_attention(
        shapes, on_device, interpret=rehearse)
    for row in report["decode_attention grouped"]:
        print("lfm2 decode_attention " + json.dumps(row), flush=True)
    report["expert_matmul gated"] = time_gated_experts(
        (2, 4) if rehearse else (2, 128, 256, 384, 512), on_device,
        interpret=rehearse, experts=cfg.experts_held, width=cfg.hidden_size,
        inner=cfg.expert_width)
    for row in report["expert_matmul gated"]:
        print("lfm2 expert_matmul " + json.dumps(row), flush=True)
    report["expert unsort"] = time_expert_unsort(
        buckets[-1], cfg.top_k, cfg.hidden_size, cfg.experts_held, on_device,
        interpret=rehearse)
    for row in report["expert unsort"]:
        print("lfm2 expert_unsort " + json.dumps(row), flush=True)
    kv, heads, dh = cfg.kv_heads, cfg.heads, cfg.head_dim

    def prompt_in(form):
        """The family's own prompt attention, held to ``form``."""
        rows = lfm2.GroupedFlashRows(kv)
        rows.prompt_form = lambda *shape: form

        def attend(q, k, v, lengths, heads_):
            P = q.shape[1]
            cache = tuple(jnp.zeros((1, 1, P, kv * dh), q.dtype)
                          for _ in range(2))
            put = decoder.slot_put(jnp.zeros((1,), jnp.int32))
            return rows.prompt(heads, lengths, P, put)(
                None, cache, jnp.int32(0), q, k, v)[1]

        return attend

    rng = np.random.default_rng(SEED)
    report["prompt attention"] = []
    for P in buckets:
        q = jnp.asarray(rng.standard_normal((1, P, heads * dh)) * 0.5,
                        jnp.bfloat16)
        k, v = (jnp.asarray(rng.standard_normal((1, P, kv * dh)) * 0.5,
                            jnp.bfloat16) for _ in range(2))
        lengths = jnp.asarray([P - 7], jnp.int32)
        outs = {}
        for form, attend in (("flash", prompt_in("flash")),
                             ("jax.numpy", prompt_in("grouped"))):
            if form == "jax.numpy" and heads * P * P * 4 > 3 << 30:
                continue  # its float32 scores alone are past 3 GiB
            @jax.jit
            def chain(q, k, v, lengths):
                for _ in range(_TIMED_CALLS):
                    q = q + attend(q, k, v, lengths, heads) * 0.01
                return q

            outs[form] = attend(q, k, v, lengths, heads)
            row = {"shape": [1, P, heads * dh], "form": form,
                   "score_mb": round(heads * P * P * 4 / 2 ** 20, 1)}
            chain(q, k, v, lengths).block_until_ready()
            if on_device:
                row["us_a_layer"] = _busy_us(
                    lambda: chain(q, k, v, lengths))
                row["share_of_197"] = round(
                    2 * 2 * heads * dh * P * P / 2 / 197e12 * 1e6
                    / row["us_a_layer"], 3)
            report["prompt attention"].append(row)
            print("lfm2 prompt_attention " + json.dumps(row), flush=True)
        if len(outs) == 2:
            off = float(jnp.max(jnp.abs(
                (outs["flash"] - outs["jax.numpy"])[:, :P - 7].astype(
                    jnp.float32))))
            assert off < 0.05, (P, off)
        del q, k, v, outs

    # -- the programs against the reference -----------------------------------
    t0 = time.monotonic()
    tree = bench_family.init_tree(config["weights"]["seed"], config,
                                  {"extra": extra})
    print(f"lfm2: {len(cfg.layer_types)} layers drawn and their routers "
          f"balanced in {time.monotonic() - t0:.0f} s", flush=True)
    sv = decoder.make_servable(
        "lfm2", ModelConfig(name="lfm2", dtype=dtype, batch_buckets=(1,),
                            seq_buckets=buckets, extra=extra),
        lfm2.family(cfg, jnp.dtype(dtype)), tree)
    del tree
    params, meta = sv.params, sv.meta["continuous"]
    kernels = build_gen_kernels(types.SimpleNamespace(servable=sv))
    V = cfg.vocab_size
    print("lfm2: cache leaves " + json.dumps(
        [[list(shape), str(np.dtype(dt))]
         for shape, dt in meta["cache_leaves"]])
        + f", read_block {meta['read_block']}, prompt forms "
        + json.dumps({b: meta["prompt_form"](1, b) for b in buckets}),
        flush=True)

    # The cell's reference prompts, as benchmark/run.py draws them.
    rng = np.random.default_rng(config["weights"]["seed"] + 1)
    prompts = [traffic.token_ids(rng, max(2, round(n * scale)), V)
               for n in config["reference_prompts"]]
    new = min(16, extra["max_new_tokens"])
    t0 = time.monotonic()
    runs, got = _serve_prompts_alone(kernels, params, meta, buckets, prompts,
                                     new)
    print(f"lfm2: {len(prompts)} prompts of {sorted({len(p) for p in prompts})}"
          f" tokens prefilled alone into a slot and decoded in "
          f"{time.monotonic() - t0:.0f} s (compiles included)", flush=True)

    keys = bench_family.published({"extra": extra})
    report["logit_std"] = float(np.std(got[0][0]))

    def forward(run, control):
        return reference.forward(params, run["ids"] + run["tokens"][:-1],
                                 keys, control, len(run["tokens"]))

    for control in (None,) if rehearse else (None, "int8"):
        # Judged over the 16 tokens a prompt the cell asks for.
        report[control or "float32"] = _against_reference(
            forward, bench_family.judge, config, runs, got, control, new)
        print(f"lfm2: reference {control or 'float32'}: "
              + json.dumps(report[control or "float32"]), flush=True)
    stats = jax.local_devices()[0].memory_stats() or {}
    report["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    print("lfm2 " + json.dumps(report))
    limit = 1e-3 if rehearse else LFM2_RMS_TOL
    assert report["float32"]["rms_logit_diff"] <= limit, report["float32"]
    assert report["float32"]["ok"], report["float32"]
    # The precision below must fail, by the logits and by the cell's own
    # comparison of the served tokens.
    assert rehearse or (report["int8"]["rms_logit_diff"] > LFM2_RMS_TOL
                        and not report["int8"]["ok"]), report["int8"]
    print(json.dumps(report))


def _mellum_child(rehearse: bool) -> None:
    """Mellum 2's kernels alone, then its programs against its plain
    reference, on one device, at the benchmark cell's widths
    (``benchmark/configs/mellum2-12b-8l.json``; its ``rehearse`` widths on
    the CPU).

    Alone, by the profiler's clock: ``decode_attention`` at 32 slots and a
    group of 8 queries a K/V head of 128, over a ring (1,024 rows, all live)
    and over the full layers' rows at 4k, 8k and 16k live; ``flash_attention``
    with the K/V heads read through the tile map, with and without the band,
    at the four buckets (the share of 197 TFLOP/s is of the work the grid
    visits); the gated ``expert_matmul`` at ``K`` 2304, ``F`` 896 at 4, 512,
    1,024 and 2,048 rows an expert, each by the kernel's own plan.

    Then the servable over the tree the benchmark stages and the programs
    ``build_gen_kernels`` jits, as the scheduler runs them: the cell's own 16
    reference prompts (700 and 5,000 tokens, drawn as ``benchmark/run.py``
    draws them), each prefilled alone into a slot of a pool of 32 and decoded
    for two segments, the first 5,000-token one for four.  ``choose`` is
    watched, not replaced.  The reference's full forward pass over prompt +
    served tokens gives the largest and the root-mean-square logit
    difference and, through the cell's own ``check`` rule (``judge``), how
    many served tokens lie far; the same against the reference's three
    controls (int8; the window layers read as full; the full layers turned
    as window layers), each of which must fail."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic
    from benchmark.families import mellum as bench_family
    from benchmark.reference import mellum as reference
    from pytorch_zappa_serverless_tpu.config import ModelConfig
    from pytorch_zappa_serverless_tpu.models import decoder, mellum
    from pytorch_zappa_serverless_tpu.ops import expert_matmul as em
    from pytorch_zappa_serverless_tpu.ops.flash_attention import (
        band_blocks, flash_attention, masked_attention)
    from pytorch_zappa_serverless_tpu.serving.generation import (
        build_gen_kernels)

    on_device = not rehearse
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "mellum2-12b-8l.json").read_text())
    serve = config["serve"]
    buckets, extra = serve["seq_buckets"], dict(serve["extra"])
    dtype, scale = "bfloat16", 1.0
    if rehearse:
        buckets = config["rehearse"]["seq_buckets"]
        extra.update(config["rehearse"]["extra"])
        dtype, scale = "float32", config["rehearse"]["scale"]
        config["weights"]["dtype"] = "float32"
    cfg = mellum.config_from_arch(extra["arch"])
    fam = mellum.family(cfg, jnp.dtype(dtype))
    report = {}

    # -- the kernels alone ------------------------------------------------
    S, W = extra["gen_slots"], cfg.sliding_window
    kv, heads, dh = cfg.kv_heads, cfg.heads, cfg.head_dim
    T = fam.rows.count(buckets[-1] + extra["max_new_tokens"])
    shapes = [(S, W, kv, dh, heads, W)] + [
        (S, T, kv, dh, heads, max(T * live // 17408, 1))
        for live in (4096, 8192, 16384)]
    report["decode_attention a kind"] = time_grouped_attention(
        shapes, on_device, interpret=rehearse)
    for row in report["decode_attention a kind"]:
        print("mellum decode_attention " + json.dumps(row), flush=True)
    report["expert_matmul gated"] = time_gated_experts(
        (2, 4) if rehearse else (4, 512, 1024, 2048), on_device,
        interpret=rehearse, experts=cfg.experts_held, width=cfg.hidden_size,
        inner=cfg.expert_width, kinds=("routed",))
    for row in report["expert_matmul gated"]:
        print("mellum expert_matmul " + json.dumps(row), flush=True)
    report["expert unsort"] = time_expert_unsort(
        buckets[1], cfg.top_k, cfg.hidden_size, cfg.experts_held, on_device,
        interpret=rehearse)
    for row in report["expert unsort"]:
        print("mellum expert_unsort " + json.dumps(row), flush=True)
    report["expert_plans"] = {
        rows: em.plan_summary(rows // cfg.top_k, cfg.top_k, cfg.hidden_size,
                              cfg.expert_width, cfg.experts_held, True, 2)
        for rows in (S * cfg.top_k, buckets[-1] * cfg.top_k)}
    print("mellum expert_plans " + json.dumps(report["expert_plans"]),
          flush=True)
    rng = np.random.default_rng(SEED)
    report["prompt attention"] = []
    for P in buckets:
        q = jnp.asarray(rng.standard_normal((1, P, heads, dh)) * 0.5,
                        jnp.bfloat16)
        k, v = (jnp.asarray(rng.standard_normal((1, P, kv, dh)) * 0.5,
                            jnp.bfloat16) for _ in range(2))
        for form, window in (("flash", None), ("flash_band", W)):
            def attend(q, k, v):
                return flash_attention(q, k, v, causal=True, window=window,
                                       interpret=rehearse)

            @jax.jit
            def chain(q, k, v):
                for _ in range(_TIMED_CALLS):
                    q = q + attend(q, k, v) * 0.01
                return q

            got = attend(q, k, v)
            chain(q, k, v).block_until_ready()
            blk = 1024 if P >= 1024 else 512
            nq = -(-P // blk)
            visited = (nq * (nq + 1) // 2 if window is None else sum(
                min(i + 1, band_blocks(nq, blk, blk, W)) for i in range(nq)))
            row = {"shape": [1, P, heads * dh], "kv_heads": kv, "form": form,
                   "blocks_visited": visited}
            if on_device:
                row["us_a_layer"] = _busy_us(lambda: chain(q, k, v))
                row["share_of_197"] = round(
                    2 * 2 * heads * dh * visited * blk * blk / 197e12 * 1e6
                    / row["us_a_layer"], 3)
            if P <= 4096:  # the form that writes its scores, where they fit
                at = np.arange(P)
                behind = at[:, None] - at[None, :]
                keep = (behind >= 0) & (behind < (window or P))
                want = masked_attention(
                    q.reshape(1, P, heads * dh),
                    *(jnp.repeat(a, heads // kv, axis=2).reshape(
                        1, P, heads * dh) for a in (k, v)),
                    jnp.asarray(np.where(keep, 0.0, -1e9)[None, None],
                                jnp.float32), heads)
                off = float(jnp.max(jnp.abs(
                    got.reshape(1, P, heads * dh).astype(jnp.float32)
                    - want.astype(jnp.float32))))
                assert off < 0.05, (P, form, off)
                row["max_diff_from_masked"] = round(off, 4)
            report["prompt attention"].append(row)
            print("mellum prompt_attention " + json.dumps(row), flush=True)
        del q, k, v

    # -- the programs against the reference -----------------------------------
    t0 = time.monotonic()
    tree = bench_family.init_tree(config["weights"]["seed"], config,
                                  {"extra": extra})
    print(f"mellum: {len(cfg.layer_types)} layers drawn in "
          f"{time.monotonic() - t0:.0f} s", flush=True)
    sv = decoder.make_servable(
        "mellum", ModelConfig(name="mellum", dtype=dtype, batch_buckets=(1,),
                              seq_buckets=buckets, extra=extra), fam, tree)
    del tree
    params, meta = sv.params, sv.meta["continuous"]
    kernels = build_gen_kernels(types.SimpleNamespace(servable=sv))
    V = cfg.vocab_size
    print("mellum: cache leaves " + json.dumps(
        [[list(shape), str(np.dtype(dt))]
         for shape, dt in meta["cache_leaves"]])
        + ", read_block " + json.dumps(
            {k["name"]: k["read_block"] for k in meta["kinds"]})
        + ", prompt forms "
        + json.dumps({b: meta["prompt_form"](1, b) for b in buckets}),
        flush=True)

    # The cell's reference prompts, as benchmark/run.py draws them.
    rng = np.random.default_rng(config["weights"]["seed"] + 1)
    prompts = [traffic.token_ids(rng, max(2, round(n * scale)), V)
               for n in config["reference_prompts"]]
    new = min(16, extra["max_new_tokens"])
    t0 = time.monotonic()
    runs, got = _serve_prompts_alone(kernels, params, meta, buckets, prompts,
                                     new)
    print(f"mellum: {len(prompts)} prompts of "
          f"{sorted({len(p) for p in prompts})} tokens prefilled alone into "
          f"a slot and decoded in {time.monotonic() - t0:.0f} s (compiles "
          f"included)", flush=True)

    keys = bench_family.published({"extra": extra})
    report["logit_std"] = float(np.std(got[0][0]))

    refs = {}

    def forward(run, control):
        at = (id(run), control)  # a pass a run a control, however often read
        if at not in refs:
            refs[at] = reference.forward(
                params, run["ids"] + run["tokens"][:-1], keys, control,
                len(run["tokens"]))
        return refs[at]

    controls = ("int8", "window_as_full", "no_yarn")
    for control in (None,) + controls:
        # Judged over the 16 tokens a prompt the cell asks for.
        report[control or "float32"] = _against_reference(
            forward, bench_family.judge, config, runs, got, control, new)
        print(f"mellum: reference {control or 'float32'}: "
              + json.dumps(report[control or "float32"]), flush=True)
    # What the cell's limits are set between: the share of served tokens
    # that lie far under the reference's best, by how far "far" is.
    report["far_share_by_tolerance"] = {
        str(tol): {control or "float32": _against_reference(
            forward, bench_family.judge, {**config,
                                          "reference_tolerance": tol},
            runs, got, control, new)["far_share"]
            for control in (None,) + controls}
        for tol in (0.01, 0.02, 0.03, 0.05)}
    print("mellum: far share by tolerance "
          + json.dumps(report["far_share_by_tolerance"]), flush=True)
    stats = jax.local_devices()[0].memory_stats() or {}
    report["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    print("mellum " + json.dumps(report))
    limit = 1e-3 if rehearse else MELLUM_RMS_TOL
    assert report["float32"]["rms_logit_diff"] <= limit, report["float32"]
    assert report["float32"]["ok"], report["float32"]
    # Each control must fail, by the logits and (on the chip, where a
    # window's worth of positions is read) by the cell's own comparison of
    # the served tokens.
    for control in controls:
        assert report[control]["rms_logit_diff"] > limit, report[control]
        assert rehearse or not report[control]["ok"], report[control]
    print(json.dumps(report))


def time_latent_attention(slots: int, total: int, width: int, values: int,
                          heads: int, lives, on_device: bool,
                          interpret: bool, forms=None):
    """``latent_attention`` alone over a leaf ``[2, slots, total, width]``
    whose every slot holds ``live`` rows, a row a form a ``live``: device
    microseconds a layer and a grid step, beside the least the chip could
    take to copy a step's block and a layer's live rows as stored (bfloat16,
    819 GB/s).

    ``forms`` are ``(name, block_t, buffers)``; ``block_t`` None is the
    block the served path picks (``pick_block_t``) and ``buffers`` None
    hands the leaf twice to the grouped ``decode_attention`` instead (a block
    is then fetched as K and as V by the pipeline's own copies).  The default
    is PERF.md's table of PR 56: the kernel as served; the same with two
    buffers (a block's copy started in the step before the one that reads
    it, the order of the pipeline's own copies, which is what the kernel ran
    until PR 56) and with one more than served; a block twice as long; the
    leaf handed twice.  Off the device, where the rows carry no time, the
    ``jax.numpy`` form is held against each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_zappa_serverless_tpu.ops import decode_attention as da

    rng = np.random.default_rng(SEED)
    leaf = jnp.asarray(rng.standard_normal((2, slots, total, width),
                                           dtype=np.float32), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((slots, heads * width)) * 0.02,
                    jnp.bfloat16)
    served = da.pick_block_t(total, width, jnp.bfloat16)
    if forms is None:
        forms = [("one pool operand", None, da._LATENT_BUFFERS),
                 ("two buffers", None, 2),
                 ("a buffer more", None, da._LATENT_BUFFERS + 1),
                 ("a block twice as long", 2 * served, da._LATENT_BUFFERS),
                 ("the leaf handed twice", None, None)]
    forms = [(name, bt or served, buffers) for name, bt, buffers in forms
             if total % (bt or served) == 0]
    rows = []

    def attend(bt, buffers, q, leaf, wpos, work, layer):
        if buffers is not None:
            return da.latent_attention(q, leaf, wpos, work, layer=layer,
                                       heads=heads, values=values, block_t=bt,
                                       buffers=buffers, interpret=interpret)
        out = da.decode_attention(q, leaf, leaf, wpos, work, layer=layer,
                                  heads=heads, block_t=bt,
                                  interpret=interpret)
        return out.reshape(slots, heads, width)[..., :values].reshape(
            slots, heads * values)

    for live in lives:
        wpos = jnp.full((slots,), live - 1, jnp.int32)
        floor = slots * live * width * 2 / 819e9 * 1e6
        want = da.attend_latent(q[:, None], leaf, 0, wpos[:, None], heads,
                                values)[:, 0]
        for form, bt, buffers in forms:
            @jax.jit
            def chain(q, leaf, wpos):
                work = da.work_list(wpos, total, bt)
                for j in range(_TIMED_CALLS):
                    # Each call's queries hang on the one before it: calls
                    # alike in every operand would be folded into one.
                    out = attend(bt, buffers, q, leaf, wpos, work, j % 2)
                    q = q + jnp.pad(
                        out.reshape(slots, heads, values),
                        ((0, 0), (0, 0), (0, width - values))).reshape(
                            q.shape) * 0.01
                return q

            chain(q, leaf, wpos).block_until_ready()
            got = attend(bt, buffers, q, leaf, wpos,
                         da.work_list(wpos, total, bt), 0)
            # (On the chip ``attend_latent`` is the kernel as served.)
            off = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            assert off < 0.05, (live, form, off)
            steps = slots * -(-live // bt)
            row = {"leaf": [slots, total, width], "heads": heads,
                   "values": values, "live": live, "form": form,
                   "block_t": bt, "buffers": buffers, "grid_steps": steps,
                   "floor_us": round(floor, 2),
                   "copy_floor_us_a_step": round(
                       bt * width * 2 * (1 if buffers else 2) / 819e9 * 1e6,
                       4)}
            if on_device:
                row["us_a_layer"] = _busy_us(lambda: chain(q, leaf, wpos))
                row["us_a_step"] = round(row["us_a_layer"] / steps, 4)
                row["gb_per_s"] = round(floor * 819 / row["us_a_layer"], 1)
                row["share_of_819"] = round(floor / row["us_a_layer"], 3)
            rows.append(row)
    return rows


def _joyai_child(rehearse: bool) -> None:
    """JoyAI-LLM-Flash's kernels alone, then its programs against its plain
    reference, on one device, at the benchmark cell's widths
    (``benchmark/configs/joyai-flash-10l.json``; its ``rehearse`` widths on
    the CPU).

    Alone, by the profiler's clock: ``latent_attention`` at 64 slots over
    the leaf at 2k, 4k, 6k and all 9,216 rows live, a layer and a grid
    step, as served and in the forms ``time_latent_attention`` lists (fewer
    and more buffers, a longer block, the leaf handed twice);
    ``flash_attention`` at keys of 192 and
    values of 128 at the four buckets (the share of 197 TFLOP/s is of the
    work the grid visits, keys padded to 256 lanes); the gated
    ``expert_matmul`` at ``K`` 2048, ``F`` 768 at 2 and 256 rows an expert,
    each by the kernel's own plan.

    Then the servable over the tree the benchmark stages and the programs
    ``build_gen_kernels`` jits, as the scheduler runs them: an 8,192 and a
    2,300 prompt, then the cell's own 16 reference prompts (drawn as
    ``benchmark/run.py`` draws them), each prefilled alone into a slot of
    the pool and decoded for two segments.  ``choose`` is watched, not
    replaced.  The reference's full forward pass (non-absorbed, in blocks
    of queries) over prompt + served tokens gives the largest and the
    root-mean-square logit difference and, through the cell's own ``check``
    rule (``judge``, over the cell's 16), how many served tokens lie far;
    the same against the reference's three controls, each of which must
    fail."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic
    from benchmark.families import joyai as bench_family
    from benchmark.reference import joyai as reference
    from pytorch_zappa_serverless_tpu.config import ModelConfig
    from pytorch_zappa_serverless_tpu.models import decoder, joyai
    from pytorch_zappa_serverless_tpu.ops import expert_matmul as em
    from pytorch_zappa_serverless_tpu.ops.flash_attention import (
        flash_attention)
    from pytorch_zappa_serverless_tpu.serving.generation import (
        build_gen_kernels)

    on_device = not rehearse
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "joyai-flash-10l.json").read_text())
    serve = config["serve"]
    buckets, extra = serve["seq_buckets"], dict(serve["extra"])
    dtype, scale = "bfloat16", 1.0
    if rehearse:
        buckets = config["rehearse"]["seq_buckets"]
        extra.update(config["rehearse"]["extra"])
        dtype, scale = "float32", config["rehearse"]["scale"]
        config["weights"]["dtype"] = "float32"
    cfg = joyai.config_from_arch(extra["arch"])
    fam = joyai.family(cfg, jnp.dtype(dtype))
    report = {}

    # -- the kernels alone ------------------------------------------------
    S, heads = extra["gen_slots"], cfg.heads
    T = fam.rows.count(buckets[-1] + extra["max_new_tokens"])
    report["latent_attention"] = time_latent_attention(
        S, T, fam.width, fam.rows.values, heads,
        [max(T * live // 9216, 1) for live in (2048, 4096, 6144, 9216)],
        on_device, interpret=rehearse)
    for row in report["latent_attention"]:
        print("joyai latent_attention " + json.dumps(row), flush=True)
    report["expert_matmul gated"] = time_gated_experts(
        (2, 4) if rehearse else (2, 256), on_device, interpret=rehearse,
        experts=cfg.experts_held, width=cfg.hidden_size,
        inner=cfg.expert_width, kinds=("routed",))
    for row in report["expert_matmul gated"]:
        print("joyai expert_matmul " + json.dumps(row), flush=True)
    report["expert_plans"] = {
        rows * cfg.top_k: em.plan_summary(
            rows, cfg.top_k, cfg.hidden_size, cfg.expert_width,
            cfg.experts_held, True, 2)
        for rows in (S, buckets[-1])}
    print("joyai expert_plans " + json.dumps(report["expert_plans"]),
          flush=True)
    rng = np.random.default_rng(SEED)
    report["prompt attention"] = []
    qk, dv = cfg.qk_dim, cfg.v_dim
    for P in buckets:
        q, k = (jnp.asarray(rng.standard_normal((1, P, heads, qk)) * 0.5,
                            jnp.bfloat16) for _ in range(2))
        v = jnp.asarray(rng.standard_normal((1, P, heads, dv)) * 0.5,
                        jnp.bfloat16)

        def attend(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=rehearse)

        @jax.jit
        def chain(q, k, v):
            for _ in range(_TIMED_CALLS):
                q = q.at[..., :dv].add(attend(q, k, v) * 0.01)
            return q

        chain(q, k, v).block_until_ready()
        row = {"shape": [1, P, heads, qk], "values": dv, "form": "flash_mla",
               "visited_flops": bench_family.attend_flops(
                   {"extra": extra}, P, visited=True) / cfg.layers,
               "needed_flops": bench_family.attend_flops(
                   {"extra": extra}, P) / cfg.layers}
        if on_device:
            row["us_a_layer"] = _busy_us(lambda: chain(q, k, v))
            row["share_of_197"] = round(
                row["visited_flops"] / 197e12 * 1e6 / row["us_a_layer"], 3)
        report["prompt attention"].append(row)
        print("joyai prompt_attention " + json.dumps(row), flush=True)
        del q, k, v

    # -- the programs against the reference -----------------------------------
    t0 = time.monotonic()
    tree = bench_family.init_tree(config["weights"]["seed"], config,
                                  {"extra": extra})
    print(f"joyai: {cfg.layers} layers drawn in "
          f"{time.monotonic() - t0:.0f} s", flush=True)
    sv = decoder.make_servable(
        "joyai", ModelConfig(name="joyai", dtype=dtype, batch_buckets=(1,),
                             seq_buckets=buckets, extra=extra), fam, tree)
    del tree
    params, meta = sv.params, sv.meta["continuous"]
    kernels = build_gen_kernels(types.SimpleNamespace(servable=sv))
    V = cfg.vocab_size
    stats = jax.local_devices()[0].memory_stats() or {}
    print("joyai: cache leaves " + json.dumps(
        [[list(shape), str(np.dtype(dt))]
         for shape, dt in meta["cache_leaves"]])
        + f", read_block {meta['read_block']}, prompt forms "
        + json.dumps({b: meta["prompt_form"](1, b) for b in buckets})
        + f", bytes in use with the weights alone "
        f"{int(stats.get('bytes_in_use', 0))}", flush=True)

    # An 8,192 and a 2,300 prompt, then the cell's reference prompts, as
    # benchmark/run.py draws them.
    rng = np.random.default_rng(config["weights"]["seed"] + 1)
    cell = [traffic.token_ids(rng, max(2, round(n * scale)), V)
            for n in config["reference_prompts"]]
    new = min(16, extra["max_new_tokens"])
    # (The longest is decoded for twice ``new``: at the rehearsal's widths a
    # slot has room for ``new`` alone past its longest bucket.)
    own = [traffic.token_ids(rng, n, V) for n in (
        buckets[-1] - (new if rehearse else 0), max(2, round(2300 * scale)))]
    t0 = time.monotonic()
    runs, got = _serve_prompts_alone(kernels, params, meta, buckets,
                                     own + cell, new)
    stats = jax.local_devices()[0].memory_stats() or {}
    report["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    print(f"joyai: {len(runs)} prompts of "
          f"{sorted({len(r['ids']) for r in runs})} tokens prefilled alone "
          f"into a slot and decoded in {time.monotonic() - t0:.0f} s "
          f"(compiles included); peak {report['memory_peak_bytes']} bytes",
          flush=True)

    keys = bench_family.published({"extra": extra})
    report["logit_std"] = float(np.std(got[0][0]))
    refs = {}

    def forward(run, control):
        at = (id(run), control)  # a pass a run a control, however often read
        if at not in refs:
            refs[at] = reference.forward(
                params, run["ids"] + run["tokens"][:-1], keys, control,
                len(run["tokens"]))
        return refs[at]

    # The two long prompts by the logits alone, against the sound reference
    # and the int8 control.
    for control in (None, "int8"):
        report[f"own prompts {control or 'float32'}"] = _against_reference(
            forward, bench_family.judge, config, runs[:2], got[:2], control,
            new)
        print(f"joyai: 8,192 and 2,300 prompts, reference "
              f"{control or 'float32'}: "
              + json.dumps(report[f"own prompts {control or 'float32'}"]),
              flush=True)
    runs, got = runs[2:], got[2:]
    for control in (None,) + reference.CONTROLS:
        # Judged over the 16 tokens a prompt the cell asks for.
        report[control or "float32"] = _against_reference(
            forward, bench_family.judge, config, runs, got, control, new)
        print(f"joyai: reference {control or 'float32'}: "
              + json.dumps(report[control or "float32"]), flush=True)
    # What the cell's limits are set between: the share of served tokens
    # that lie far under the reference's best, by how far "far" is.
    report["far_share_by_tolerance"] = {
        str(tol): {control or "float32": _against_reference(
            forward, bench_family.judge, {**config,
                                          "reference_tolerance": tol},
            runs, got, control, new)["far_share"]
            for control in (None,) + reference.CONTROLS}
        for tol in (0.01, 0.02, 0.03, 0.05)}
    print("joyai: far share by tolerance "
          + json.dumps(report["far_share_by_tolerance"]), flush=True)
    print("joyai " + json.dumps(report))
    limit = 1e-3 if rehearse else JOYAI_RMS_TOL
    assert report["float32"]["rms_logit_diff"] <= limit, report["float32"]
    assert report["own prompts float32"]["rms_logit_diff"] <= limit, report
    assert report["float32"]["ok"], report["float32"]
    # Each control must fail, by the logits and (on the chip) by the cell's
    # own comparison of the served tokens.
    for control in reference.CONTROLS:
        assert report[control]["rms_logit_diff"] > limit, report[control]
        assert rehearse or not report[control]["ok"], report[control]
    print(json.dumps(report))


# What a burst admits on the int8 lane (16 slots, buckets 512 and 768), and
# two of GPT-2 XL's admission batches that take the kernel: (batch, bucket).
BURST_INT8 = [(8, 512), (16, 512), (4, 768), (8, 768), (16, 768)]
BURST_XL = [(4, 768), (8, 512)]
BURST_LOGIT_TOL = 0.05   # the benchmark's reference tolerance, in logits
# K and V rows of the two forms, over the root mean square of the rows:
# their mean and their largest difference.
BURST_ROWS_MEAN_TOL, BURST_ROWS_MAX_TOL = 0.02, 0.25


def _burst_child(rehearse: bool) -> None:
    """A burst's prefills through the servable's own programs, in both forms
    of the prompt attention, on one device.

    ``gpt2-large-int8`` and ``gpt2-xl`` at their published widths and all
    their layers, built by the model's own builder as the benchmark's
    configurations build them; the jitted ``segment`` and the pool are
    ``build_gen_kernels``'s, the prefill is the servable's own.  Each
    (batch, bucket) is prefilled into a fresh slot pool and decoded
    for two segments, once with the picker held to the ``jax.numpy`` form
    (a patch here, in this process: the product has no switch) and once as
    built; a third prefill, the ``jax.numpy`` form with the attention alone
    in float32, is the arbiter between them.  The two sides must agree:
    the K and V rows up to each length within bfloat16 rounding through
    the layers, the kernel's no farther from the arbiter's than the other
    form's are (nor its first logits), first tokens and the 16 decoded
    tokens a slot equal up to a near-tie, and nothing non-finite in the
    rows or the pool.  ``choose`` is watched, not replaced."""
    import functools
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_zappa_serverless_tpu.config import ModelConfig
    from pytorch_zappa_serverless_tpu.models import decoder, gpt2
    from pytorch_zappa_serverless_tpu.ops import flash_attention as fa
    from pytorch_zappa_serverless_tpu.serving.generation import (
        build_gen_kernels)

    if rehearse:
        # The CPU picks the jax.numpy form: steer "as built" to the kernel,
        # under the interpreter, so that the phase's control flow runs.
        arch = {"d_model": 128, "layers": 2, "heads": 2, "ffn_dim": 256,
                "vocab_size": 300, "max_positions": 64, "eos_id": 300}
        lanes = [("gpt2-int8", arch, "int8", 4, [(4, 32)], (32,), 16)]
        def kernel_form(*shape):
            return "kernel"
        fa.prompt_attention = functools.partial(fa.prompt_attention,
                                                interpret=True)
    else:
        large = {"d_model": 1280, "layers": 36, "heads": 20, "ffn_dim": 5120,
                 "vocab_size": 50257, "max_positions": 1024, "eos_id": 50257}
        xl = {**large, "d_model": 1600, "layers": 48, "heads": 25,
              "ffn_dim": 6400}
        lanes = [("gpt2-large-int8", large, "int8", 16, BURST_INT8,
                  (256, 512, 768), 192),
                 ("gpt2-xl", xl, "bfloat16", 8, BURST_XL, (512, 768), 192)]
        kernel_form = fa.prompt_form

    seen = {}
    choose = decoder.choose

    def watched(logits, temperature, seeds, t, top_k=None, top_p=None):
        jax.debug.callback(
            lambda lg, tt: seen.update({int(tt[0]): np.asarray(lg)}),
            logits, t)
        return choose(logits, temperature, seeds, t, top_k, top_p)

    decoder.choose = watched
    masked = fa.masked_attention

    def in_float32(q, k, v, mask_bias, heads):
        with jax.default_matmul_precision("highest"):  # these einsums alone
            return masked(*(a.astype(jnp.float32) for a in (q, k, v)),
                          mask_bias, heads).astype(q.dtype)

    def einsum_form(*shape):
        return "einsum"

    rng = np.random.default_rng(SEED)
    report, failed = {}, []
    for name, arch, params_dtype, slots, shapes, buckets, max_new in lanes:
        t0 = time.monotonic()
        servable = gpt2.make_gpt2_servable("gpt2", ModelConfig(
            name="gpt2", seq_buckets=buckets, dtype="bfloat16", extra={
                "params_dtype": params_dtype, "max_new_tokens": max_new,
                "gen_slots": slots, "segment_tokens": 8, "arch": arch}))
        if params_dtype == "bfloat16":  # at rest as the engine keeps them
            from pytorch_zappa_serverless_tpu.models.vision_common import (
                cast_params_at_rest)
            servable.params = cast_params_at_rest(servable.params,
                                                  jnp.bfloat16)
        params = jax.device_put(servable.params)
        kernels = build_gen_kernels(types.SimpleNamespace(servable=servable),
                                    None)
        meta = kernels["meta"]
        print(f"burst: {name} built in {time.monotonic() - t0:.0f} s, "
              f"{slots} slots of {meta['total']} positions")

        @jax.jit
        def apart(xs, ys, lengths):
            """(largest, mean) difference of two sides' K and V rows up to
            each length, and the root mean square of the first side's."""
            xs, ys = ([a[:, :lengths.shape[0]] for a in side]
                      for side in (xs, ys))  # the slots that were written
            real = (jnp.arange(xs[0].shape[2])[None, :]
                    < lengths[:, None])[None, :, :, None]
            count = lengths.sum() * xs[0].shape[0] * xs[0].shape[3] * 2.0
            gaps = [jnp.where(real, jnp.abs(x.astype(jnp.float32)
                                            - y.astype(jnp.float32)), 0.0)
                    for x, y in zip(xs, ys)]
            squares = sum(jnp.where(real, x.astype(jnp.float32) ** 2,
                                    0.0).sum() for x in xs)
            return (jnp.maximum(gaps[0].max(), gaps[1].max()),
                    (gaps[0].sum() + gaps[1].sum()) / count,
                    jnp.sqrt(squares / count))

        def prefilled(rule, payload):
            """(first [B], first logits [B, V], the pool's (K, V)) of one
            prefill into slots 0 to B - 1 of a fresh pool, traced under
            ``rule``, every row finite."""
            fa.prompt_form = rule
            # A function of its own a side: jit keeps a function's trace,
            # and this one has to be made under the rule.
            batch, bucket = payload["input_ids"].shape
            at = np.arange(batch, dtype=np.int32)
            pool = kernels["alloc_cache"]()
            lowered = jax.jit(
                lambda p, cache, at, payload: meta["prefill"](
                    p, cache, at, payload), donate_argnums=(1,)).lower(
                        params, pool, at, payload)
            scores = (f"{batch}x{arch['heads']}x{bucket}x{bucket}xf32"
                      in lowered.as_text())
            form = rule(batch, arch["heads"], bucket, 64)
            assert scores == (form != "kernel"), (name, form, scores)
            seen.clear()
            first, k_rows, v_rows = lowered.compile()(params, pool, at,
                                                       payload)
            jax.effects_barrier()
            assert bool(jnp.isfinite(k_rows).all()
                        & jnp.isfinite(v_rows).all()), \
                "a prefill's rows hold a value that is not finite"
            return np.asarray(first), seen[0], (k_rows, v_rows)

        def decoded(first, rows, lengths):
            """(tokens [B, 17], logits by step) of two segments over the
            slot pool the prefill wrote (a copy: the segment donates what
            it is given, and ``rows`` is compared again): token ``t`` of a
            slot is the choice from logits ``t``, the prefill's being 0."""
            B = len(lengths)
            cache = tuple(jnp.copy(leaf) for leaf in rows)
            S = meta["slots"]
            tok = np.zeros((S,), np.int32)
            tok[:B] = first
            pos = np.zeros((S,), np.int32)
            pos[:B] = lengths
            st = np.zeros((S,), np.int32)
            fin = np.ones((S,), bool)
            fin[:B] = False
            zf, zi = np.zeros((S,), np.float32), np.zeros((S,), np.int32)
            emits = []
            seen.clear()
            for _ in range(2):
                packed, *cache = kernels["segment"](
                    params, tuple(cache), tok, pos, st, fin, zf, zi, zi,
                    zf + 1)
                packed = np.asarray(packed)
                emits.append(packed[:B, :8])
                tok, pos, st = (packed[:, 8 + i].copy() for i in range(3))
                fin = packed[:, 11].astype(bool)
            jax.effects_barrier()
            assert all(bool(jnp.isfinite(leaf).all()) for leaf in cache[:2]), \
                "the pool holds a value that is not finite"
            # A step emits the token decided before it: the 16 emitted are
            # the prefill's and 15 steps', and the 16th step's is the carry.
            seq = np.concatenate(emits + [tok[:B, None]], axis=1)
            assert (seq[:, 0] == first).all()
            return seq, dict(seen)

        for batch, bucket in shapes:
            t0 = time.monotonic()
            lengths = np.asarray(edge_lengths(batch, bucket), np.int32)
            tokens = rng.integers(0, arch["vocab_size"],
                                  (batch, bucket)).astype(np.int32)
            payload = {"input_ids": tokens, "length": lengths,
                       **{k: np.full((batch,), off, dt)
                          for k, dt, off in decoder.KNOBS}}
            assert kernel_form(batch, arch["heads"], bucket, 64) == "kernel"
            # The arbiter: the same program with the attention alone in
            # float32 (its inputs are the bfloat16 q, k and v of the other
            # two), prefill only.  Then the jax.numpy form, whose
            # temporaries are the large ones, then the program as built.
            fa.masked_attention = in_float32
            _, lg_i, rows_i = prefilled(einsum_form, payload)
            fa.masked_attention = masked
            f_e, lg_e0, rows_e = prefilled(einsum_form, payload)
            e_i = [float(x) for x in apart(rows_e, rows_i, lengths)]
            seq_e, lg_e = decoded(f_e, rows_e, lengths)
            f_k, lg_k0, rows_k = prefilled(kernel_form, payload)
            k_i = [float(x) for x in apart(rows_k, rows_i, lengths)]
            k_e = [float(x) for x in apart(rows_k, rows_e, lengths)]
            del rows_i, rows_e
            seq_k, lg_k = decoded(f_k, rows_k, lengths)
            del rows_k
            lg_e[0], lg_k[0] = lg_e0, lg_k0
            for seq, lg in ((seq_e, lg_e), (seq_k, lg_k)):  # greedy, aligned
                assert all((seq[:, t] == lg[t][:batch].argmax(-1)).all()
                           for t in range(seq.shape[1]))
            # Served tokens: equal, or parted at a near-tie.
            partings = []
            for row in range(batch):
                for t in range(seq_e.shape[1]):
                    e, k = int(seq_e[row, t]), int(seq_k[row, t])
                    if e != k:  # what follows has another prompt
                        partings.append({
                            "length": int(lengths[row]), "step": t,
                            "einsum_gap": round(float(
                                lg_e[t][row, e] - lg_e[t][row, k]), 5),
                            "kernel_gap": round(float(
                                lg_k[t][row, k] - lg_k[t][row, e]), 5),
                            "logits_apart": round(float(np.max(np.abs(
                                lg_e[t][row] - lg_k[t][row]))), 5)})
                        break
            first_apart = {
                pair: round(float(np.max(np.abs(x[:batch] - y[:batch]))), 5)
                for pair, x, y in (("kernel_ideal", lg_k0, lg_i),
                                   ("einsum_ideal", lg_e0, lg_i),
                                   ("kernel_einsum", lg_k0, lg_e0))}
            rms = e_i[2]
            line = {"rows_rms": round(rms, 4),
                    "rows_apart_max_mean": {
                        "kernel_ideal": [round(k_i[0], 5), round(k_i[1], 6)],
                        "einsum_ideal": [round(e_i[0], 5), round(e_i[1], 6)],
                        "kernel_einsum": [round(k_e[0], 5), round(k_e[1], 6)]},
                    "first_logits_apart": first_apart,
                    "logit_std": round(float(np.std(lg_k0)), 4),
                    "slots_parted": len(partings), "of": batch,
                    "partings": partings,
                    "seconds": round(time.monotonic() - t0, 1)}
            report[f"{name} [{batch}, {bucket}]"] = line
            print(f"burst {name} [{batch}, {bucket}] lengths "
                  f"{sorted(int(n) for n in lengths)} " + json.dumps(line),
                  flush=True)
            # bfloat16 keeps 8 bits: a rounding is 0.4% of a value, and a
            # row of the last layer has two a layer behind it; the largest
            # of 10^8 to 10^9 differences lies six deviations out.  The
            # kernel keeps float32 scores where the other form rounds them,
            # so it may not lie farther from the float32 attention than
            # that form does.  Two sets of logits ``x`` apart can order a
            # pair of tokens up to ``2 x`` apart differently.
            if (k_e[1] > BURST_ROWS_MEAN_TOL * rms
                    or k_e[0] > BURST_ROWS_MAX_TOL * rms
                    or k_i[1] > 1.25 * e_i[1]
                    or first_apart["kernel_ideal"] > max(
                        BURST_LOGIT_TOL, 1.25 * first_apart["einsum_ideal"])
                    or any(max(p["einsum_gap"], p["kernel_gap"])
                           > 2 * BURST_LOGIT_TOL for p in partings)):
                failed.append((name, batch, bucket, line))
        del params, kernels, servable
    fa.prompt_form = kernel_form
    assert not failed, failed
    print(json.dumps({"burst": report}))


def _multichip_child(rehearse: bool) -> None:
    """``mesh: {data: 2, model: 2}`` through ``build_engine`` against the
    same models on one device: where the shards sit, that the step holds a
    collective, and that both compute the same function."""
    import jax
    import numpy as np

    from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    if rehearse:
        resnet = ModelConfig(name="resnet50", batch_buckets=(8,),
                             extra={"image_size": 64, "resize_to": 72})
        gpt2 = ModelConfig(
            name="gpt2", batch_buckets=(4,), seq_buckets=(16,),
            extra={"max_new_tokens": 8, "params_dtype": "bfloat16",
                   "arch": TINY_GPT2})
        vocab, size = 512, 64
    else:
        resnet = ModelConfig(name="resnet50", batch_buckets=(8,))
        gpt2 = ModelConfig(name="gpt2", batch_buckets=(4,), seq_buckets=(64,),
                           extra={"max_new_tokens": 32,
                                  "params_dtype": "bfloat16"})
        vocab, size = 50257, 224

    def engine(mesh):
        return build_engine(ServeConfig(
            mesh=mesh, warmup_at_boot=True, models=[resnet, gpt2]))

    sharded, single = engine({"data": 2, "model": 2}), engine({})
    try:
        # Where the parameters sit.
        for name in ("resnet50", "gpt2"):
            leaves = jax.tree.leaves(sharded.model(name).servable.params)
            devices = set().union(*(leaf.sharding.device_set
                                    for leaf in leaves))
            assert len(devices) == 4, (name, devices)
            alone = set().union(*(leaf.sharding.device_set for leaf in
                                  jax.tree.leaves(
                                      single.model(name).servable.params)))
            assert len(alone) == 1, (name, alone)
        q = sharded.model("gpt2").servable.params["layer0"]["q"]["kernel"]
        shard_shapes = {s.data.shape for s in q.addressable_shards}
        assert shard_shapes == {(q.shape[0], q.shape[1] // 2)}, shard_shapes
        assert len({s.device for s in q.addressable_shards}) == 4
        print(f"params on 4 distinct devices; gpt2 layer0/q/kernel "
              f"{tuple(q.shape)} split over model into {shard_shapes}")

        # The compiled GPT-2 step holds a collective.
        cm = sharded.model("gpt2")
        spec = cm.servable.input_spec(cm.buckets[0])
        dummy = cm._place({k: np.zeros(s.shape, s.dtype)
                           for k, s in spec.items()})
        text = cm._jit.lower(cm.servable.params, dummy).compile().as_text()
        found = sorted(op for op in ("all-reduce", "all-gather",
                                     "reduce-scatter", "collective-permute",
                                     "all-to-all") if op in text)
        assert found, "no collective in the sharded GPT-2 step"
        print(f"sharded gpt2 step holds collectives: {found}")

        # Same function: resnet50 probabilities, GPT-2 prefill activations
        # (every layer's K and V), then the generated tokens.
        rng = np.random.default_rng(SEED)
        images = [{"image": rng.integers(0, 256, (size, size, 3), np.uint8)}
                  for _ in range(8)]
        got = sharded.runner.run_sync(sharded.model("resnet50"), images)
        want = single.runner.run_sync(single.model("resnet50"), images)
        gap = max(abs(g["prob"] - w["prob"]) for a, b in zip(got, want)
                  for g, w in zip(a["top_k"], b["top_k"]))
        assert gap <= 2e-2, f"resnet50 top-k probabilities differ by {gap}"
        print(f"resnet50 b8: sharded and unsharded top-k probabilities "
              f"within {gap:.2e}")

        prompts = [[int(t) for t in rng.integers(1, vocab - 2, n)]
                   for n in (12, 9, 14, 5)]
        payload = {
            "input_ids": np.asarray([p + [0] * (16 - len(p))
                                     for p in prompts], np.int32),
            "length": np.asarray([len(p) for p in prompts], np.int32),
            "temperature": np.zeros((4,), np.float32),
            "seed": np.zeros((4,), np.int32),
            "top_k": np.zeros((4,), np.int32),
            "top_p": np.ones((4,), np.float32)}

        def kv(eng):
            cm = eng.model("gpt2")
            meta = cm.servable.meta["continuous"]
            pool = tuple(np.zeros(shape, dt)
                         for shape, dt in meta["cache_leaves"])
            _, k, v = jax.jit(meta["prefill"])(
                cm.servable.params, pool, np.arange(4, dtype=np.int32),
                cm._place(payload))
            return [np.asarray(a, np.float32)[:, i, :len(p)]
                    for a in (k, v) for i, p in enumerate(prompts)]

        for a, b in zip(kv(sharded), kv(single)):
            np.testing.assert_allclose(a, b, rtol=3e-2,
                                       atol=3e-2 * float(np.abs(b).max()))
        print("gpt2 prefill: every layer's K and V agree within bf16 "
              "tolerance")
        samples = [{"input_ids": p} for p in prompts]
        toks = [[r["tokens"] for r in eng.runner.run_sync(
            eng.model("gpt2"),
            [eng.model("gpt2").servable.preprocess(s) for s in samples])]
            for eng in (sharded, single)]
        same = sum(a == b for s, u in zip(*toks) for a, b in zip(s, u))
        total = sum(len(u) for u in toks[1])
        print(f"gpt2 greedy tokens: {same}/{total} positions equal between "
              "sharded and unsharded (bf16 near-ties may diverge a row)")
        assert all(s[0] == u[0] for s, u in zip(*toks)) or same >= total // 2, \
            "sharded and unsharded GPT-2 disagree from the first token on"
    finally:
        sharded.shutdown()
        single.shutdown()
    print(json.dumps({"tokens_equal": same, "tokens_total": total}))


# -- retrieval: JAX's one number for a cached program, in its three parts -------

# The order a child makes its first uses in: a program is its process's first
# retrieval in one and its third in the other.
RETRIEVAL_ORDERS = (("xl_prefill", "xl_segment", "int8_prefill"),
                    ("int8_prefill", "xl_segment", "xl_prefill"))


def timed_retrieval(compile_):
    """Run ``compile_()`` (a lowered program's ``.compile()``) and say where
    its retrieval from the persistent cache went.  JAX books the retrieval as
    one duration round ``compilation_cache.get_executable_and_time``; a
    ``sys.setprofile`` hook, set for this call alone, times the Python
    functions inside it: the cache's ``get`` (the file's read),
    ``decompress_executable`` and ``extract_executable_and_time``.  What is
    left of the whole is ``backend.deserialize_executable``, a native method
    the hook cannot see: the executable's load onto the device.  The bytes
    are the lengths of what ``get`` and ``decompress_executable`` return."""
    parts = {("lru_cache.py", "get"): "file_read",
             ("compilation_cache.py", "decompress_executable"): "decompress",
             ("compilation_cache.py", "extract_executable_and_time"): "extract",
             ("compilation_cache.py", "get_executable_and_time"): "retrieval"}
    secs = dict.fromkeys(parts.values(), 0.0)
    sizes = {"file_read": 0, "decompress": 0}
    began = {}

    def hook(frame, event, arg):
        if event not in ("call", "return"):
            return
        code = frame.f_code
        part = parts.get((os.path.basename(code.co_filename), code.co_name))
        if part is None:
            return
        if event == "call":
            began[part] = time.perf_counter()
        elif part in began:
            secs[part] += time.perf_counter() - began.pop(part)
            if part in sizes and isinstance(arg, bytes):
                sizes[part] += len(arg)

    t0 = time.perf_counter()
    sys.setprofile(hook)
    try:
        compile_()
    finally:
        sys.setprofile(None)
    whole = time.perf_counter() - t0
    return {"compile_s": whole, "retrieval_s": secs["retrieval"],
            "file_read_s": secs["file_read"],
            "decompress_s": secs["decompress"],
            "deserialize_s": max(secs["retrieval"] - secs["file_read"]
                                 - secs["decompress"] - secs["extract"], 0.0),
            "compressed_bytes": sizes["file_read"],
            "decompressed_bytes": sizes["decompress"]}


def _retrieval_child(rehearse: bool, order: tuple) -> None:
    """Lower and compile the three programs in ``order``, each from shapes
    alone (nothing runs), against the compile cache the server uses; the
    last line is one JSON list, a row a program."""
    import jax
    import jax.numpy as jnp

    from pytorch_zappa_serverless_tpu.config import ModelConfig
    from pytorch_zappa_serverless_tpu.engine.cache import setup_compile_cache
    from pytorch_zappa_serverless_tpu.models import decoder, gpt2

    setup_compile_cache()
    if rehearse:
        xl = gpt2.GPT2Config(**TINY_GPT2)
        slots, total, shape = 4, 32, (4, 16)
        arch = {"d_model": 128, "layers": 2, "heads": 2, "ffn_dim": 256,
                "vocab_size": 300, "max_positions": 64, "eos_id": 300}
        buckets, int8_slots, new = (16,), 4, 16
    else:
        xl = gpt2.GPT2Config(d_model=1600, layers=48, heads=25, ffn_dim=6400)
        slots, total, shape = 8, 960, (4, 768)
        arch = {"d_model": 1280, "layers": 36, "heads": 20, "ffn_dim": 5120,
                "vocab_size": 50257, "max_positions": 1024, "eos_id": 50257}
        buckets, int8_slots, new = (256, 512, 768), 16, 192

    def int8_prefill():
        # The W8A16 tree has no shape maker of its own: the model's builder
        # makes it, as the benchmark's configuration does, and only its
        # shapes are used.
        servable = gpt2.make_gpt2_servable("gpt2", ModelConfig(
            name="gpt2", seq_buckets=buckets, dtype="bfloat16", extra={
                "params_dtype": "int8", "max_new_tokens": new,
                "gen_slots": int8_slots, "segment_tokens": 8, "arch": arch}))

        def sd(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        batch, bucket = shape
        payload = {"input_ids": jax.ShapeDtypeStruct(shape, jnp.int32),
                   "length": jax.ShapeDtypeStruct((batch,), jnp.int32),
                   **{k: jax.ShapeDtypeStruct((batch,), dt)
                      for k, dt, _ in decoder.KNOBS}}
        meta = servable.meta["continuous"]
        pool = tuple(jax.ShapeDtypeStruct(shape, dt)
                     for shape, dt in meta["cache_leaves"])
        return (jax.jit(meta["prefill"], donate_argnums=(1,)),
                (jax.tree.map(sd, servable.params), pool,
                 jax.ShapeDtypeStruct((batch,), jnp.int32), payload))

    programs = {"xl_prefill": lambda: prefill_program(xl, *shape, slots,
                                                      total),
                "xl_segment": lambda: segment_program(xl, slots, total),
                "int8_prefill": int8_prefill}
    heard = {}

    def listen(event, *a, **kw):
        heard[event] = heard.get(event, 0) + (a[0] if a else 1)

    jax.monitoring.register_event_listener(listen)
    jax.monitoring.register_event_duration_secs_listener(listen)
    rows = []
    for nth, name in enumerate(order, 1):
        fn, args = programs[name]()
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        lower_s = time.perf_counter() - t0
        heard.clear()
        row = {"program": name, "nth": nth, "trace_lower_s": lower_s,
               **timed_retrieval(lowered.compile)}
        row["outcome"] = ("hit" if heard.get(
            "/jax/compilation_cache/cache_hits") else "miss")
        row["jax_retrieval_s"] = heard.get(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.0)
        rows.append(row)
        print(f"retrieval: {name} as this process's retrieval {nth}: "
              f"{row['outcome']}; trace and lower {lower_s:.2f} s, compile "
              f"{row['compile_s']:.2f} s of which the retrieval "
              f"{row['retrieval_s']:.3f} s (JAX's own number "
              f"{row['jax_retrieval_s']:.3f}): file read "
              f"{row['file_read_s']:.3f}, decompress "
              f"{row['decompress_s']:.3f}, deserialize_executable "
              f"{row['deserialize_s']:.3f}; entry "
              f"{row['compressed_bytes']} bytes, "
              f"{row['decompressed_bytes']} decompressed", flush=True)
    print(json.dumps(rows))


def phase_retrieval(rehearse: bool) -> list[dict]:
    """Three children, one after the other: the first makes the entries if
    the cache lacks them (and says which it found), the second and third
    retrieve all three in the two orders of :data:`RETRIEVAL_ORDERS`."""
    OUT.mkdir(exist_ok=True)
    runs = []
    for i, order in enumerate((RETRIEVAL_ORDERS[0], *RETRIEVAL_ORDERS)):
        rows = run_child(f"import chip_smoke; "
                         f"chip_smoke._retrieval_child({rehearse}, {order})",
                         rehearse, f"retrieval{i}.log", timeout=1500.0)
        if i:
            cold = [r["program"] for r in rows if r["outcome"] != "hit"]
            check(not cold, f"retrieval: {cold} were compiled, not retrieved, "
                            "in a process that followed one that made them")
            runs += rows
    for name in RETRIEVAL_ORDERS[0]:
        early, late = sorted((r for r in runs if r["program"] == name),
                             key=lambda r: r["nth"])
        say(f"retrieval: {name}: as a process's retrieval {early['nth']} "
            f"{early['retrieval_s']:.3f} s (deserialize "
            f"{early['deserialize_s']:.3f}), as its retrieval {late['nth']} "
            f"{late['retrieval_s']:.3f} s (deserialize "
            f"{late['deserialize_s']:.3f})")
    return runs


# -- the run ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run the mesh phase (and only it) on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the same phases on the CPU at tiny widths")
    args = ap.parse_args(argv)
    if not PKG.is_dir():
        print(f"[smoke] FAIL: {PKG.name}/ is not next to chip_smoke.py — "
              "nothing to smoke", file=sys.stderr)
        return 2
    OUT.mkdir(exist_ok=True)
    t0 = time.monotonic()
    try:
        probe = probe_device(args.rehearse, args.chips)
        if args.chips == 4:
            run_child(f"import chip_smoke; "
                      f"chip_smoke._multichip_child({args.rehearse})",
                      args.rehearse, "multichip.log", devices=4,
                      timeout=1500.0)
            say("multichip: sharded and unsharded engines agree")
        else:
            cache_was_empty = not entries(Path(probe["cache_dir"]))
            probe["in_checkout_entries"] = entries(IN_CHECKOUT_CACHE)
            inputs = make_inputs(args.rehearse)
            serve_cfg, sd15_cfg = write_configs(args.rehearse)
            first = phase_serve(serve_cfg, inputs, probe, args.rehearse)
            phase_restart(serve_cfg, inputs, probe, first, cache_was_empty,
                          args.rehearse)
            run_child(f"import chip_smoke; "
                      f"chip_smoke._kernels_child({args.rehearse})",
                      args.rehearse, "kernels.log", timeout=1500.0)
            say("kernels: int8_matmul, flash_attention, prompt_attention and "
                "decode_attention match their references on the device")
            run_child(f"import chip_smoke; "
                      f"chip_smoke._burst_child({args.rehearse})",
                      args.rehearse, "burst.log", timeout=2400.0)
            say("burst: the int8 lane's and XL's large admission prefills "
                "agree in both forms of the prompt attention, through "
                "the pool and two segments")
            seg = run_child(f"import chip_smoke; "
                            f"chip_smoke._segment_child({args.rehearse})",
                            args.rehearse, "segment.log", timeout=900.0)
            check(args.rehearse or seg["decode_kernel"],
                  "the segment program compiled for the chip holds no "
                  "decode_attention kernel")
            say("segment: no copy, slice or transpose of a layer of the "
                "slot pool in the compiled decode segment"
                + (" (not asserted on the CPU)" if args.rehearse else ""))
            run_child(f"import chip_smoke; "
                      f"chip_smoke._evabyte_child({args.rehearse})",
                      args.rehearse, "evabyte.log", timeout=1200.0)
            say("evabyte: prefill and decode through the two-tier pool "
                "agree with the plain reference across a window's end")
            run_child(f"import chip_smoke; "
                      f"chip_smoke._nemotron_child({args.rehearse})",
                      args.rehearse, "nemotron.log", timeout=2400.0)
            say("nemotron: prefill batches into the pool and 256 decode steps "
                "over a full pool agree with the plain reference; the "
                "reference in the precision below does not")
            run_child(f"import chip_smoke; "
                      f"chip_smoke._lfm2_child({args.rehearse})",
                      args.rehearse, "lfm2.log", timeout=3000.0)
            say("lfm2: the grouped decode kernel, the gated expert matmul "
                "and the flash prompt form match their jax.numpy forms; the "
                "cell's reference prompts through prefill into the pool and "
                "segments agree with the plain reference; the reference in "
                "the precision below does not")
            run_child(f"import chip_smoke; "
                      f"chip_smoke._mellum_child({args.rehearse})",
                      args.rehearse, "mellum.log", timeout=3000.0)
            say("mellum: the decode kernel over a ring and over full rows, "
                "the band form of the prompt attention and the gated expert "
                "matmul at its widths match their jax.numpy forms; the "
                "cell's reference prompts through prefill into both leaf "
                "pairs and segments agree with the plain reference; its "
                "three controls do not")
            run_child(f"import chip_smoke; "
                      f"chip_smoke._joyai_child({args.rehearse})",
                      args.rehearse, "joyai.log", timeout=3000.0)
            say("joyai: the latent kernel over one pool operand, the prompt "
                "attention at keys of 192 and values of 128 and the gated "
                "expert matmul at its widths match their jax.numpy forms; "
                "an 8,192 and a 2,300 prompt and the cell's reference "
                "prompts through prefill into the one leaf and absorbed "
                "segments agree with the plain non-absorbed reference; its "
                "three controls do not")
            phase_sd15(sd15_cfg, probe, args.rehearse)
    except SmokeFailure as e:
        print(f"[smoke] FAIL after {time.monotonic() - t0:.0f}s: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        for proc in _CHILDREN:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    say(f"all phases passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": probe["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
