"""Multi-host (DCN) bootstrap: 2-process CPU simulation (VERDICT r2 #3).

SURVEY §4's named technique — simulate multi-host with ``jax.distributed``
CPU processes before touching real DCN.  Each worker process joins a
2-process world (1 CPU device each), builds the PRODUCTION engine over a
global ``{"data": 2}`` mesh that spans both processes, and serves a batch in
lockstep.  Asserts:

- both processes see 2 global devices / 1 local device (the DCN world);
- the mesh spans hosts and the engine serves through it;
- both processes return identical predictions, identical to a
  single-process single-device run of the same config (sharding across
  hosts changes nothing numerically).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]

WORKER = """\
import json, os, sys
pid = int(sys.argv[1]); port = sys.argv[2]; cache = sys.argv[3]
import jax
jax.config.update("jax_platforms", "cpu")
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine

cfg = ServeConfig(
    compile_cache_dir=cache,
    warmup_at_boot=True,
    mesh={"data": 2},
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=2,
    process_id=pid,
    models=[ModelConfig(
        name="bert_base", dtype="float32", batch_buckets=(2,),
        seq_buckets=(8,),
        extra={"arch": {"num_layers": 1, "num_heads": 2, "head_dim": 8,
                        "mlp_dim": 32, "vocab_size": 512,
                        "max_position": 64}})])
engine = build_engine(cfg)
cm = engine.model("bert_base")
samples = [cm.servable.preprocess({"input_ids": [5, 6, 7, 8]}),
           cm.servable.preprocess({"input_ids": [9, 10]})]
results, bucket = cm.run_batch(samples)
print(json.dumps({
    "pid": pid,
    "processes": jax.process_count(),
    "global_devices": len(jax.devices()),
    "local_devices": len(jax.local_devices()),
    "mesh_devices": int(engine.mesh.devices.size) if engine.mesh is not None else 1,
    "mesh_spans_processes": (engine.mesh is not None
                             and len({d.process_index
                                      for d in engine.mesh.devices.flat}) == 2),
    "bucket": list(bucket),
    "scores": [[s["prob"] for s in r["scores"]] for r in results],
}))
engine.shutdown()
"""


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return env


@pytest.mark.slow
def test_two_process_dcn_mesh_serves_identically(tmp_path):
    port = "29731"
    cache = str(tmp_path / "xla")
    procs = [subprocess.Popen(
        [sys.executable, "-c", WORKER, str(pid), port, cache],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=ROOT, env=_env()) for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=600)
            assert p.returncode == 0, f"worker failed:\n{stderr[-2000:]}"
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        # One worker failing must not orphan its sibling inside the
        # distributed barrier (it would hold the coordinator port and hang
        # reruns).
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    for o in outs:
        assert o["processes"] == 2
        assert o["global_devices"] == 2 and o["local_devices"] == 1
        assert o["mesh_devices"] == 2 and o["mesh_spans_processes"]
        assert o["bucket"] == [2, 8]
    # Lockstep SPMD: both processes computed the same full batch.
    np.testing.assert_allclose(outs[0]["scores"], outs[1]["scores"], rtol=0, atol=0)

    # Single-process single-device reference: sharding across hosts must not
    # change the numbers (same random-init seed, fp32).
    ref_code = WORKER.replace('mesh={"data": 2},', 'mesh={},') \
                     .replace('coordinator_address=f"127.0.0.1:{port}",',
                              'coordinator_address="",') \
                     .replace("num_processes=2,", "num_processes=1,")
    ref = subprocess.run(
        [sys.executable, "-c", ref_code, "0", port, cache],
        capture_output=True, text=True, cwd=ROOT, env=_env(), timeout=600)
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_out = json.loads(ref.stdout.strip().splitlines()[-1])
    np.testing.assert_allclose(outs[0]["scores"], ref_out["scores"],
                               rtol=1e-5, atol=1e-6)


LEADER = """\
import json, os, sys
pid = int(sys.argv[1]); port = sys.argv[2]; cache = sys.argv[3]
import jax
jax.config.update("jax_platforms", "cpu")
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine

cfg = ServeConfig(
    compile_cache_dir=cache,
    warmup_at_boot=True,
    mesh={"data": 2},
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=2,
    process_id=pid,
    models=[ModelConfig(
        name="bert_base", dtype="float32", batch_buckets=(1, 2),
        seq_buckets=(8,),
        extra={"arch": {"num_layers": 1, "num_heads": 2, "head_dim": 8,
                        "mlp_dim": 32, "vocab_size": 512,
                        "max_position": 64}})])
engine = build_engine(cfg)
cm = engine.model("bert_base")
if pid == 0:
    # The lead side: host 0 serves (run_batch broadcasts each dispatch to
    # the follower via engine.lockstep) across DIFFERENT buckets.  The
    # server calls enable_lockstep_lead() at startup; this test drives
    # run_batch directly, so it enables the topology itself.
    engine.enable_lockstep_lead()
    out = []
    for batch in ([{"input_ids": [5, 6, 7, 8]}, {"input_ids": [9, 10]}],
                  [{"input_ids": [1, 2, 3]}]):
        samples = [cm.servable.preprocess(p) for p in batch]
        results, bucket = cm.run_batch(samples)
        out.append({"bucket": list(bucket),
                    "scores": [[s["prob"] for s in r["scores"]]
                               for r in results]})
    print(json.dumps({"pid": 0, "runs": out}))
    engine.shutdown()   # leads the shutdown broadcast; follower returns
else:
    engine.lockstep.follow()   # mirrors both dispatches, then returns
    print(json.dumps({"pid": 1, "followed": True}))
    engine.runner.shutdown()
"""


@pytest.mark.slow
def test_follower_driver_mirrors_leader_dispatches(tmp_path):
    """parallel/lockstep.py: host 0 leads through run_batch, the follower's
    loop mirrors every dispatch (different buckets) and releases on
    shutdown — the one-HTTP-endpoint multi-host topology."""
    port = "29741"
    cache = str(tmp_path / "xla")
    procs = [subprocess.Popen(
        [sys.executable, "-c", LEADER, str(pid), port, cache],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=ROOT, env=_env()) for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=600)
            assert p.returncode == 0, f"worker failed:\n{stderr[-2000:]}"
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    lead, follow = outs
    assert follow == {"pid": 1, "followed": True}
    assert [r["bucket"] for r in lead["runs"]] == [[2, 8], [1, 8]]
    for r in lead["runs"]:
        for scores in r["scores"]:
            assert len(scores) > 0


GEN_WORKER = """\
import asyncio, json, os, sys
pid = int(sys.argv[1]); port = sys.argv[2]; cache = sys.argv[3]
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.serving.generation import GenerationScheduler

ARCH = {"vocab_size": 512, "d_model": 128, "layers": 2, "heads": 2,
        "ffn_dim": 256, "max_positions": 64, "eos_id": 511}
MC = ModelConfig(name="gpt2", dtype="float32", batch_buckets=(1,),
                 seq_buckets=(16,),
                 extra={"max_new_tokens": 8, "arch": ARCH,
                        "gen_slots": 2, "segment_tokens": 4})
mesh_spec = {"model": 2} if port != "none" else {}
cfg = ServeConfig(
    compile_cache_dir=cache, warmup_at_boot=False, mesh=mesh_spec,
    coordinator_address=(f"127.0.0.1:{port}" if port != "none" else ""),
    num_processes=(2 if port != "none" else 1), process_id=pid, models=[MC])
engine = build_engine(cfg)
cm = engine.model("gpt2")

if pid == 0:
    if engine.lockstep is not None:
        engine.enable_lockstep_lead()

    async def main():
        sched = GenerationScheduler(
            cm, engine.runner, MC, lockstep=engine.lockstep,
            mesh=engine.mesh if engine.lockstep is not None else None).start()
        a = sched.submit(cm.servable.preprocess({"input_ids": [5, 6, 7]}))
        b = sched.submit(cm.servable.preprocess({"input_ids": [9, 10, 11, 12]}))
        toks_a = await asyncio.wait_for(a.done, 300)
        toks_b = await asyncio.wait_for(b.done, 300)
        await sched.stop()
        return toks_a, toks_b

    toks_a, toks_b = asyncio.new_event_loop().run_until_complete(main())
    print(json.dumps({"pid": 0, "a": toks_a, "b": toks_b}))
    engine.shutdown()
else:
    engine.lockstep.follow()
    print(json.dumps({"pid": 1, "followed": True}))
    engine.runner.shutdown()
"""


WHISPER_GEN_WORKER = """\
import asyncio, json, os, sys
pid = int(sys.argv[1]); port = sys.argv[2]; cache = sys.argv[3]
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.serving.generation import GenerationScheduler

ARCH = {"d_model": 32, "encoder_layers": 2, "decoder_layers": 2, "heads": 2,
        "ffn_dim": 64, "vocab_size": 64, "source_positions": 1500,
        "target_positions": 96}
MC = ModelConfig(name="whisper_tiny", dtype="float32", batch_buckets=(1,),
                 extra={"max_new_tokens": 6, "arch": ARCH,
                        "gen_slots": 2, "segment_tokens": 3})
mesh_spec = {"model": 2} if port != "none" else {}
cfg = ServeConfig(
    compile_cache_dir=cache, warmup_at_boot=False, mesh=mesh_spec,
    coordinator_address=(f"127.0.0.1:{port}" if port != "none" else ""),
    num_processes=(2 if port != "none" else 1), process_id=pid, models=[MC])
engine = build_engine(cfg)
cm = engine.model("whisper_tiny")

def _sample(seed):
    t = np.arange(16000) / 16000.0
    wav = (0.4 * np.sin(2 * np.pi * (300 + 50 * seed) * t)).astype(np.float32)
    return cm.servable.preprocess({"array": wav.tolist()})

if pid == 0:
    if engine.lockstep is not None:
        engine.enable_lockstep_lead()

    async def main():
        sched = GenerationScheduler(
            cm, engine.runner, MC, lockstep=engine.lockstep,
            mesh=engine.mesh if engine.lockstep is not None else None).start()
        a = sched.submit(_sample(1))
        b = sched.submit(_sample(2))
        toks_a = await asyncio.wait_for(a.done, 300)
        toks_b = await asyncio.wait_for(b.done, 300)
        await sched.stop()
        return toks_a, toks_b

    toks_a, toks_b = asyncio.new_event_loop().run_until_complete(main())
    print(json.dumps({"pid": 0, "a": toks_a, "b": toks_b}))
    engine.shutdown()
else:
    engine.lockstep.follow()
    print(json.dumps({"pid": 1, "followed": True}))
    engine.runner.shutdown()
"""


KILL_WORKER = """\
import asyncio, json, os, sys
pid = int(sys.argv[1]); port = sys.argv[2]; cache = sys.argv[3]
import jax
jax.config.update("jax_platforms", "cpu")
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.serving.generation import GenerationScheduler

ARCH = {"vocab_size": 512, "d_model": 128, "layers": 2, "heads": 2,
        "ffn_dim": 256, "max_positions": 64, "eos_id": 511}
MC = ModelConfig(name="gpt2", dtype="float32", batch_buckets=(1,),
                 seq_buckets=(16,),
                 extra={"max_new_tokens": 16, "arch": ARCH,
                        "gen_slots": 2, "segment_tokens": 4})
cfg = ServeConfig(
    compile_cache_dir=cache, warmup_at_boot=False, mesh={"model": 2},
    coordinator_address=f"127.0.0.1:{port}", num_processes=2,
    process_id=pid, models=[MC])
engine = build_engine(cfg)
cm = engine.model("gpt2")

if pid == 0:
    engine.enable_lockstep_lead()

    async def main():
        sched = GenerationScheduler(
            cm, engine.runner, MC, lockstep=engine.lockstep,
            mesh=engine.mesh).start()
        # Exercise the heartbeat op on the live protocol first.
        await engine.runner.run_fn(engine.lockstep.lead_heartbeat)
        a = sched.submit(cm.servable.preprocess({"input_ids": [5, 6, 7]}))
        await asyncio.wait_for(a.events.get(), 300)  # stream is mid-flight

    asyncio.new_event_loop().run_until_complete(main())
    print(json.dumps({"pid": 0, "dying": True}), flush=True)
    os._exit(137)  # leader dies mid-stream, no shutdown broadcast
else:
    engine.lockstep.follow()   # must RETURN on leader loss, not hang
    print(json.dumps({"pid": 1, "exited_cleanly": True}))
    engine.runner.shutdown()
"""


@pytest.mark.slow
def test_leader_death_releases_follower_then_world_restarts(tmp_path):
    """Close the multi-host recovery loop (VERDICT r3 #7): kill the leader
    mid-stream; the follower's mirror loop must EXIT (so a process
    supervisor — the rendered warmpool.sh loop — can restart it) rather
    than hang in a collective; a restarted world on the same warm cache
    serves streams again."""
    cache = str(tmp_path / "xla")
    procs = [subprocess.Popen(
        [sys.executable, "-c", KILL_WORKER, str(pid), "29761", cache],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=ROOT, env=_env()) for pid in (0, 1)]
    try:
        lead_out, _ = procs[0].communicate(timeout=600)
        assert procs[0].returncode == 137, "leader did not die as scripted"
        assert json.loads(lead_out.strip().splitlines()[-1])["dying"]
        # The follower must terminate on its own — a hang here means a dead
        # leader strands followers forever and no supervisor can help.
        follow_out, follow_err = procs[1].communicate(timeout=300)
        if procs[1].returncode == 0:
            assert json.loads(
                follow_out.strip().splitlines()[-1])["exited_cleanly"]
        # A nonzero exit is acceptable too (the distributed runtime may
        # abort on coordinator loss) — the supervision loop restarts either
        # way; only hanging is a failure.
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    # World restart on a fresh coordinator port, same warm cache: the
    # GEN_WORKER pair must serve streams again.
    procs = [subprocess.Popen(
        [sys.executable, "-c", GEN_WORKER, str(pid), "29762", cache],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=ROOT, env=_env()) for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=600)
            assert p.returncode == 0, f"restarted worker failed:\n{stderr[-3000:]}"
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    lead, follow = outs
    assert follow == {"pid": 1, "followed": True}
    assert len(lead["a"]) >= 1 and len(lead["b"]) >= 1


@pytest.mark.slow
def test_streaming_generation_mirrors_on_multihost(tmp_path):
    """SSE/continuous-batching on a CROSS-HOST TP mesh: the leader's
    scheduler broadcasts every prefill and segment (OP_GEN_*), the
    follower mirrors them, and the streamed tokens equal a single-process
    run of the same scheduler."""
    port = "29751"
    cache = str(tmp_path / "xla")
    procs = [subprocess.Popen(
        [sys.executable, "-c", GEN_WORKER, str(pid), port, cache],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=ROOT, env=_env()) for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=600)
            assert p.returncode == 0, f"worker failed:\n{stderr[-3000:]}"
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    lead, follow = outs
    assert follow == {"pid": 1, "followed": True}
    assert len(lead["a"]) >= 1 and len(lead["b"]) >= 1

    # Single-process reference (no mesh, no lockstep): same token streams.
    ref = subprocess.run(
        [sys.executable, "-c", GEN_WORKER, "0", "none", cache],
        capture_output=True, text=True, cwd=ROOT, env=_env(), timeout=600)
    assert ref.returncode == 0, ref.stderr[-3000:]
    ref_out = json.loads(ref.stdout.strip().splitlines()[-1])
    assert lead["a"] == ref_out["a"] and lead["b"] == ref_out["b"]


@pytest.mark.slow
def test_whisper_streaming_mirrors_on_multihost(tmp_path):
    """Whisper's continuous lane under the REAL lockstep OP_GEN protocol
    (VERDICT r4 #5 asked for the continuous lane, not just the kernels):
    audio admission (OP_GEN_ADMIT carries the log-mel payload through the
    model-shaped admit spec), packed cross+self KV pool on a cross-host
    Megatron-TP mesh (WHISPER_TP_RULES), streamed tokens equal a
    single-process run."""
    port = "29753"
    cache = str(tmp_path / "xla")
    procs = [subprocess.Popen(
        [sys.executable, "-c", WHISPER_GEN_WORKER, str(pid), port, cache],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=ROOT, env=_env()) for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=600)
            assert p.returncode == 0, f"worker failed:\n{stderr[-3000:]}"
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    lead, follow = outs
    assert follow == {"pid": 1, "followed": True}
    assert len(lead["a"]) >= 1 and len(lead["b"]) >= 1

    ref = subprocess.run(
        [sys.executable, "-c", WHISPER_GEN_WORKER, "0", "none", cache],
        capture_output=True, text=True, cwd=ROOT, env=_env(), timeout=600)
    assert ref.returncode == 0, ref.stderr[-3000:]
    ref_out = json.loads(ref.stdout.strip().splitlines()[-1])
    assert lead["a"] == ref_out["a"] and lead["b"] == ref_out["b"]
