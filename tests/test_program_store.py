"""engine/cache.py's program store: a warm boot restores a lane's executables
by what they were built from, and traces and lowers nothing.

The store's pieces alone (the digest, an entry's file, a restored program's
call) and two boots of a lane over one cache directory, on the CPU: the
backend's executables round-trip through ``serialize_executable`` there as
they do on the chip.
"""

import asyncio
import dataclasses
import json
import os
import subprocess
import sys
import threading
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine import cache as C
from pytorch_zappa_serverless_tpu.serving.tracing import RoundTimeline

pytest_plugins = "aiohttp.pytest_plugin"

ROOT = Path(__file__).resolve().parents[1]


# -- a toy lane -----------------------------------------------------------------

def _toy(params, pool, rows, payload):
    """A prefill's shape: parameters, a donated pool, a small payload."""
    x = payload["x"].astype(jnp.float32) @ params["w"] + params["b"]
    return x.sum(axis=1), pool.at[rows].set(x)


def _args(n=2, dtype=np.int32):
    return ({"w": jnp.eye(4) * 2.0, "b": jnp.ones(4)}, jnp.zeros((8, 4)),
            np.arange(n, dtype=np.int32),
            {"x": np.arange(n * 4, dtype=dtype).reshape(n, 4)})


@pytest.fixture()
def lane(tmp_path):
    """``(program, timeline, clock)`` of a toy lane over a fresh store; the
    timeline's key is the launch phase's ``n``."""
    C.setup_compile_cache(tmp_path / "xla")

    def make(basis="b"):
        clock = C.CompileClock()
        tl = RoundTimeline("toy", clock=clock, program_of=lambda name, attrs: (
            ("toy", {"n": attrs["n"]}) if name == "prefill.launch" else None))
        program = C.StoredProgram("toy", _toy, basis=basis, model="toy",
                                  clock=clock, donate_argnums=(1,))
        return program, tl, clock

    return make


def _launch(program, tl, args, n=2):
    with tl.phase("prefill.launch", n=n):
        out = program(*args)
    with tl.phase("prefill.fetch"):
        return jax.tree.map(np.asarray, out)


def test_restored_program_matches_the_jitted_one_and_donates(lane):
    program, tl, clock = lane()
    want = jax.tree.map(np.asarray, jax.jit(_toy)(*_args()))
    first = _launch(program, tl, _args())  # a miss: compiled and stored
    assert clock.programs("toy")["program_store"] == {
        "hits": 0, "misses": 1, "failed_loads": 0, "fallbacks": 0}
    program, tl, clock = lane()  # a second lane over the same store
    args = _args()
    got = _launch(program, tl, args)
    assert args[1].is_deleted()  # the pool was donated
    for a, b, c in zip(want, first, got):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    (entry,) = clock.snapshot()
    assert (entry["outcome"], entry["restored"]) == ("hit", "program_store")
    assert entry["trace_s"] == entry["lower_s"] == 0.0
    assert entry["cache_read_s"] > 0 and entry["backend_s"] > 0
    assert entry["launch_s"] > 0 and entry["first_run_s"] is not None
    assert clock.programs("toy")["program_store"]["hits"] == 1
    # The next launch of the key calls the loaded executable: no first use.
    _launch(program, tl, _args())
    assert len(clock.snapshot()) == 1


def test_no_scope_or_no_basis_is_the_jitted_function(lane, tmp_path):
    program, tl, clock = lane()
    program(*_args())  # no launch phase open: nothing keyed, nothing stored
    bare = C.StoredProgram("toy", _toy, basis=None, clock=clock,
                           donate_argnums=(1,))
    _launch(bare, tl, _args())
    assert not (tmp_path / "xla" / "programs").exists()
    assert clock.programs("toy")["program_store"]["misses"] == 0


def test_a_type_error_from_a_restored_program_falls_back_and_is_counted(lane):
    program, tl, clock = lane()
    _launch(program, tl, _args(2))
    # The key says 2 rows and the arguments hold 3: the executable refuses
    # them, the launch is the jitted function's, and nothing is raised.
    got = _launch(program, tl, _args(3), n=2)
    want = jax.tree.map(np.asarray, jax.jit(_toy)(*_args(3)))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert clock.programs("toy")["program_store"]["fallbacks"] == 1
    _launch(program, tl, _args(3), n=2)
    assert clock.programs("toy")["program_store"]["fallbacks"] == 2


def test_a_truncated_entry_is_a_miss_and_is_rewritten(lane, tmp_path):
    program, tl, _ = lane()
    _launch(program, tl, _args())
    (entry,) = (tmp_path / "xla" / "programs").glob("*.program")
    whole = entry.read_bytes()
    entry.write_bytes(whole[:len(whole) // 2])
    program, tl, clock = lane()
    got = _launch(program, tl, _args())
    np.testing.assert_array_equal(got[0], np.asarray(jax.jit(_toy)(*_args())[0]))
    assert clock.programs("toy")["program_store"] == {
        "hits": 0, "misses": 1, "failed_loads": 1, "fallbacks": 0}
    assert len(entry.read_bytes()) > len(whole) // 2  # whole again
    program, tl, clock = lane()
    _launch(program, tl, _args())
    assert clock.programs("toy")["program_store"]["hits"] == 1


def test_two_lanes_storing_one_key_at_once_leave_one_whole_file(tmp_path):
    store = C.ProgramStore(tmp_path / "programs")
    args = _args()
    compiled = jax.jit(_toy).lower(*args).compile()
    barrier, faults = threading.Barrier(4), []

    def save():
        barrier.wait()
        try:
            for _ in range(5):
                store.save("k", compiled)
        except Exception as e:  # pragma: no cover - the failure mode
            faults.append(e)

    threads = [threading.Thread(target=save) for _ in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert not faults
    assert [p.name for p in (tmp_path / "programs").iterdir()] == ["k.program"]
    restored = store.load("k", args, {jax.devices()[0]})
    np.testing.assert_array_equal(np.asarray(restored(*args)[0]),
                                  np.asarray(jax.jit(_toy)(*_args())[0]))


# -- the digest -----------------------------------------------------------------

CONFIG = ModelConfig(name="m", extra={"arch": {"layers": 2}, "gen_slots": 4})


def _other(field: dataclasses.Field):
    """Another value of a configuration field's own type."""
    value = getattr(CONFIG, field.name)
    if isinstance(value, bool) or value is None and "bool" in str(field.type):
        return not value
    if isinstance(value, (int, float)):
        return value + 1
    if isinstance(value, tuple):
        return value + (7,)
    if isinstance(value, dict):
        return {**value, "arch": {"layers": 3}}
    return f"{value or ''}x"


@pytest.mark.parametrize("field", [f.name for f in dataclasses.fields(CONFIG)])
def test_digest_changes_with_any_field_of_the_configuration(field):
    (f,) = [f for f in dataclasses.fields(CONFIG) if f.name == field]
    other = dataclasses.replace(CONFIG, **{field: _other(f)})
    assert other != CONFIG
    a, b = (C.lane_basis(dataclasses.asdict(c), {"lane": "slot"}, source="s")
            for c in (CONFIG, other))
    assert a != b


def _digest(basis="b", name="toy", args=None, **jit_options):
    program = C.StoredProgram(name, _toy, basis=basis, **jit_options)
    return program._digest(args or _args())[0]


DIGEST_CASES = {
    "lane_option": lambda: _digest(C.lane_basis({}, {"spec_k": 4}, source="s"))
    != _digest(C.lane_basis({}, {"spec_k": 5}, source="s")),
    "source": lambda: _digest(C.lane_basis({}, {}, source="s"))
    != _digest(C.lane_basis({}, {}, source="t")),
    "program_name": lambda: _digest(name="prefill") != _digest(name="segment"),
    "donate_argnums": lambda: _digest(donate_argnums=(1,)) != _digest(),
    "compiler_options": lambda: _digest(compiler_options={"a": 1})
    != _digest(compiler_options={"a": 2}),
    "argument_shape": lambda: _digest(args=_args(2)) != _digest(args=_args(4)),
    "argument_dtype": lambda: _digest(args=_args(dtype=np.int32))
    != _digest(args=_args(dtype=np.int16)),
    "argument_weak_type": lambda: _digest(args=(1.0,)) != _digest(
        args=(np.float32(1.0),)),
    "argument_tree": lambda: _digest(args=({"x": np.zeros(2)},))
    != _digest(args=({"y": np.zeros(2)},)),
    "argument_sharding": lambda: _digest(args=(jax.device_put(
        np.zeros(2), jax.devices()[0]),)) != _digest(args=(jax.device_put(
            np.zeros(2), jax.devices()[1]),)),
    "same_twice": lambda: _digest(C.lane_basis({"a": 1}, {"b": 2}))
    == _digest(C.lane_basis({"a": 1}, {"b": 2})),
}


@pytest.mark.parametrize("case", sorted(DIGEST_CASES))
def test_digest(case):
    assert DIGEST_CASES[case]()


def test_digest_changes_with_a_source_files_bytes(tmp_path):
    (tmp_path / "pkg" / "ops").mkdir(parents=True)
    (tmp_path / "pkg" / "ops" / "kernel.py").write_text("x = 1\n")
    (tmp_path / "pkg" / "notes.txt").write_text("not source")
    C.source_digest.cache_clear()
    before = C.source_digest(tmp_path / "pkg")
    (tmp_path / "pkg" / "notes.txt").write_text("still not source")
    C.source_digest.cache_clear()
    assert C.source_digest(tmp_path / "pkg") == before
    (tmp_path / "pkg" / "ops" / "kernel.py").write_text("x = 2\n")
    C.source_digest.cache_clear()
    assert C.source_digest(tmp_path / "pkg") != before
    # The package's own: every .py file of it, once a process.
    assert C.source_digest() == C.source_digest() != before


_DIGEST_CHILD = """
import json, sys
import numpy as np
import jax
from pytorch_zappa_serverless_tpu.config import ModelConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.config import ServeConfig
from pytorch_zappa_serverless_tpu.serving.generation import build_gen_kernels
mc = ModelConfig(name="gpt2", dtype="float32", batch_buckets=(1,),
                 seq_buckets=(8,), extra={"max_new_tokens": 4, "arch": {
                     "d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 64,
                     "vocab_size": 300, "max_positions": 32},
                     "gen_slots": 2, "segment_tokens": 2})
eng = build_engine(ServeConfig(compile_cache_dir=sys.argv[1],
                               warmup_at_boot=False, models=[mc]))
cm = eng.model("gpt2")
k = build_gen_kernels(cm)
S = 2
state = [np.zeros(S, np.int32)] * 3 + [np.ones(S, bool), np.zeros(S, np.float32),
         np.zeros(S, np.int32), np.zeros(S, np.int32), np.ones(S, np.float32)]
print(json.dumps(k["segment"]._digest(
    (cm.servable.params, k["alloc_cache"](), *state))[0]))
eng.shutdown()
"""


def test_digest_does_not_change_between_two_boots_of_the_same_tree(tmp_path):
    def boot(hash_seed: str) -> str:
        env = {**os.environ, "PYTHONPATH": str(ROOT), "JAX_PLATFORMS": "cpu",
               "PYTHONHASHSEED": hash_seed}
        out = subprocess.run(
            [sys.executable, "-c", _DIGEST_CHILD, str(tmp_path / "xla")],
            env=env, cwd=str(ROOT), capture_output=True, text=True,
            timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    assert boot("1") == boot("2")


# -- two boots of a lane ----------------------------------------------------------

ARCH = {"d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 128,
        "vocab_size": 500, "max_positions": 64}


def _models(lane: str) -> list[ModelConfig]:
    extra = {"max_new_tokens": 12, "arch": ARCH, "gen_slots": 2,
             "segment_tokens": 3}
    base = dict(dtype="float32", batch_buckets=(1, 2), seq_buckets=(8, 16),
                coalesce_ms=1.0)
    if lane == "slot":
        return [ModelConfig(name="gpt2", extra=extra, **base)]
    paged = dict(kv_cache="paged", kv_block_size=4, prefill_chunk_tokens=8)
    if lane == "paged":
        return [ModelConfig(name="gpt2", extra=extra, **paged, **base)]
    return [ModelConfig(name="gpt2", extra=extra, spec_draft="draft",
                        spec_k=3, **paged, **base),
            ModelConfig(name="draft", builder="gpt2", extra={
                **extra, "arch": {**ARCH, "layers": 1}}, **paged, **base)]


async def _boot(lane: str, cache_dir) -> tuple[list, list[dict], dict]:
    """One boot of a lane: two requests' tokens, the ledger's entries of the
    generation programs, and the store's counts."""
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.generation import (
        DraftGate, GenerationScheduler, PagedGenerationScheduler)

    eng = build_engine(ServeConfig(compile_cache_dir=str(cache_dir),
                                   warmup_at_boot=False,
                                   models=_models(lane)))
    cm = eng.model("gpt2")
    if lane == "slot":
        sched = GenerationScheduler(cm, eng.runner, cm.cfg).start()
    else:
        draft = eng.model("draft") if lane == "speculative" else None
        sched = PagedGenerationScheduler(
            cm, eng.runner, cm.cfg,
            draft=draft and DraftGate("draft", lambda: draft)).start()
    try:
        tokens = []
        for ids in ([5, 6, 7], list(range(1, 12))):
            sample = cm.servable.preprocess({"input_ids": ids})
            tokens.append(await asyncio.wait_for(
                sched.submit(sample, max_new=9).done, 120))
        entries = [e for e in cm.clock.snapshot() if e["model"] == "gpt2"
                   and e["program"] != "predict"]
        counts = {m: cm.clock.programs(m)["program_store"]
                  for m in ("gpt2", "draft")}
    finally:
        await sched.stop()
        eng.shutdown()
    return tokens, entries, counts


@pytest.mark.parametrize("lane", ["slot", "paged", "speculative"])
async def test_a_second_boot_restores_every_generation_program(lane, tmp_path):
    tokens, cold, counts = await _boot(lane, tmp_path / "xla")
    assert cold and all(e["restored"] is None and e["trace_s"] > 0
                        for e in cold)
    stored = sum(c["misses"] for c in counts.values())
    assert stored >= len(cold) and not any(
        c["hits"] or c["fallbacks"] for c in counts.values())
    again, warm, counts = await _boot(lane, tmp_path / "xla")
    assert again == tokens
    assert [(e["program"], e["key"]) for e in warm] == [
        (e["program"], e["key"]) for e in cold]
    for e in warm:
        assert (e["outcome"], e["restored"]) == ("hit", "program_store"), e
        assert e["trace_s"] == e["lower_s"] == 0.0 and e["layer_traces"] == 0
        assert e["cache_read_s"] > 0 and e["launch_s"] > 0
    assert sum(c["hits"] for c in counts.values()) == stored
    assert not any(c["misses"] or c["failed_loads"] or c["fallbacks"]
                   for c in counts.values())


# -- what the digest cannot see -----------------------------------------------------

def _configs() -> list[str]:
    return sorted(p.stem for p in (ROOT / "benchmark" / "configs").glob(
        "*.json"))


@pytest.mark.parametrize("name", _configs())
def test_programs_close_over_nothing_made_from_the_weights(name, tmp_path):
    """The digest holds the configuration and the arguments' types, not the
    weights' values: a program that closed over an array made from them
    would be restored for another checkpoint's.  Every benchmark family's
    prefill and segment, built as the server builds them from two seeds'
    staged weights, lower to the same text."""
    from benchmark import families
    from pytorch_zappa_serverless_tpu.engine.weights import save_native
    from pytorch_zappa_serverless_tpu.models import decoder
    from pytorch_zappa_serverless_tpu.serving.generation import (
        build_gen_kernels)
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

    config = json.loads((ROOT / "benchmark" / "configs"
                         / f"{name}.json").read_text())
    serve = dict(config["serve"])
    reh = config["rehearse"]
    serve["seq_buckets"] = reh.get("seq_buckets", serve["seq_buckets"])
    serve["extra"] = {**serve["extra"], **reh["extra"]}
    serve["dtype"] = "float32"
    texts = []
    for seed in (0, 1):
        path = tmp_path / f"{seed}.tpu.safetensors"
        save_native(families.load(config).init_tree(seed, config, serve), path)
        sv = get_model_builder(serve["builder"])(ModelConfig(
            name=serve["model"], builder=serve["builder"],
            checkpoint=str(path), dtype=serve["dtype"],
            batch_buckets=tuple(serve["batch_buckets"]),
            seq_buckets=tuple(serve["seq_buckets"]), extra=serve["extra"]))
        k = build_gen_kernels(types.SimpleNamespace(servable=sv))
        meta, bucket = k["meta"], min(serve["seq_buckets"])
        pool = tuple(jax.ShapeDtypeStruct(shape, dt)
                     for shape, dt in meta["cache_leaves"])
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), sv.params)
        payload = {"input_ids": jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                   "length": jax.ShapeDtypeStruct((1,), jnp.int32),
                   **decoder.knob_spec(1)}
        S = meta["slots"]
        state = [jax.ShapeDtypeStruct((S,), dt) for dt in (
            jnp.int32, jnp.int32, jnp.int32, jnp.bool_, jnp.float32,
            jnp.int32, jnp.int32, jnp.float32)]
        texts.append((
            k["prefill"].jitted.lower(params, pool, jax.ShapeDtypeStruct(
                (1,), jnp.int32), payload).as_text(),
            k["segment"].jitted.lower(params, pool, *state).as_text()))
    assert texts[0][0] == texts[1][0]
    assert texts[0][1] == texts[1][1]
