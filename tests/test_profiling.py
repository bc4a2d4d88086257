"""jax.profiler integration (SURVEY §5 tracing, VERDICT r1 item 6; ISSUE 24).

POST /admin/profile captures an xplane/perfetto trace of live traffic and
reduces it: the host annotations (``tpuserve.*`` scheduler phases, the
dispatch/collate/h2d/device spans of engine/runner + engine/compiled) lie on
the host threads of that capture, and ``utils/xplane.attribute_idle`` books
every idle gap of the device to the phase that covers it.
"""

import asyncio
import io
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData
from PIL import Image

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.serving.server import Server
from pytorch_zappa_serverless_tpu.utils.xplane import attribute_idle

pytest_plugins = "aiohttp.pytest_plugin"


def _cfg(cache_dir, trace_dir):
    arch = {"d_model": 32, "layers": 1, "heads": 2, "ffn_dim": 64,
            "vocab_size": 512, "max_positions": 32}
    return ServeConfig(
        compile_cache_dir=str(cache_dir), trace_dir=str(trace_dir),
        warmup_at_boot=True,
        models=[ModelConfig(name="resnet18", batch_buckets=(1, 4), dtype="float32",
                            coalesce_ms=5.0,
                            extra={"image_size": 64, "resize_to": 72}),
                ModelConfig(name="gpt2", batch_buckets=(1,), seq_buckets=(8,),
                            dtype="float32", coalesce_ms=1.0,
                            extra={"max_new_tokens": 8, "arch": arch})],
    )


def _jpeg() -> bytes:
    arr = np.random.default_rng(0).integers(0, 255, (80, 100, 3)).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG")
    return buf.getvalue()


async def test_profile_captures_live_traffic(aiohttp_client, tmp_path):
    eng = build_engine(_cfg(tmp_path / "xla", tmp_path / "traces"))
    try:
        server = Server(_cfg(tmp_path / "xla", tmp_path / "traces"), engine=eng)
        client = await aiohttp_client(server.app)
        jpeg = _jpeg()
        r = await client.post("/v1/models/gpt2:generate",
                              json={"text": "warm", "stream": False})
        assert r.status == 200, await r.text()

        async def traffic():
            await asyncio.sleep(0.2)
            for _ in range(4):
                r = await client.post("/v1/models/resnet18:predict", data=jpeg,
                                      headers={"Content-Type": "image/jpeg"})
                assert r.status == 200
            r = await client.post("/v1/models/gpt2:generate",
                                  json={"text": "hello tpu", "stream": False})
            assert r.status == 200

        # 3 s: on a loaded machine the traffic below takes more than one.
        trace_req = client.post("/admin/profile", json={"seconds": 3.0})
        resp, _ = await asyncio.gather(trace_req, traffic())
        body = await resp.json()
        assert resp.status == 200, body
        # The capture wrote xplane protobuf files under trace_dir/<timestamp>.
        assert str(tmp_path / "traces") in body["dir"]
        captures = list(Path(body["dir"]).rglob("*.xplane.pb"))
        assert captures, body
        # The scheduler's own phases are in it, on the profiler's clock.
        names = {ev.name for pb in captures
                 for plane in ProfileData.from_file(str(pb)).planes
                 for line in plane.lines for ev in line.events
                 if ev.name.startswith("tpuserve.")}
        assert "tpuserve.segment.launch" in names, names
        assert {"tpuserve.round.lane_wait", "tpuserve.round.wakeup",
                "tpuserve.segment.fetch"} <= names, names
        # No device plane on the CPU: both reductions are there, and empty.
        assert body["idle"] == {} and body["programs"] == {}
        assert "ops" in body
    finally:
        eng.shutdown()


async def test_concurrent_profile_capture_rejected(aiohttp_client, tmp_path):
    eng = build_engine(_cfg(tmp_path / "xla", tmp_path / "traces"))
    try:
        server = Server(_cfg(tmp_path / "xla", tmp_path / "traces"), engine=eng)
        client = await aiohttp_client(server.app)
        first = asyncio.create_task(client.post("/admin/profile", json={"seconds": 1.0}))
        await asyncio.sleep(0.2)
        second = await client.post("/admin/profile", json={"seconds": 0.1})
        assert second.status == 409
        assert (await first).status == 200
        # The route it replaced is gone, not a second way in.
        assert (await client.post("/debug/trace", json={})).status == 404
    finally:
        eng.shutdown()


# -- attribute_idle on a capture made by hand -----------------------------------

def _line(line_id, name, events) -> str:
    """``events``: (name's index, start, duration, stats), times in us."""
    body = "".join(
        f"events {{ metadata_id: {meta} offset_ps: {start * 10**6} "
        f"duration_ps: {dur * 10**6}"
        + "".join(f" stats {{ metadata_id: {k} int64_value: {v} }}"
                  for k, v in stats.items()) + " }\n"
        for meta, start, dur, stats in events)
    return f'lines {{ id: {line_id} name: "{name}" timestamp_ns: 0\n{body}}}\n'


def _plane(plane_id, name, lines, event_names, stat_names=()) -> str:
    """``event_names``: a name, or ``(name, {stat's index: value})`` for an
    event whose metadata carries stats (an int or a string each)."""
    def stats_of(stats: dict) -> str:
        return "".join(
            f" stats {{ metadata_id: {k} "
            + (f'str_value: "{v}"' if isinstance(v, str)
               else f"uint64_value: {v}") + " }" for k, v in stats.items())

    named = [(n, {}) if isinstance(n, str) else n for n in event_names]
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}"'
                   f'{stats_of(stats)} }} }}\n'
                   for i, (n, stats) in enumerate(named, 1))
    stats = "".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                    for i, n in enumerate(stat_names, 1))
    return (f'planes {{ id: {plane_id} name: "{name}"\n' + "".join(lines)
            + meta + stats + "}\n")


@pytest.fixture
def synthetic_capture(tmp_path):
    """Two device runs 1000 us apart and the host's side of them, in us:

        device  prefill [1000, 3000)   gap [3000, 4000)   segment [4000, 9000)
                (its one operation ends at 2800: 200 us idle inside the run)
        host    prefill.launch [500, 1200)  prefill.fetch [1200, 3100)
                round.wakeup [3100, 3400)   (no phase over [3400, 3700))
                segment.launch [3700, 4100) segment.fetch [4100, 9050)
    """
    device = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Modules", [(1, 1000, 2000, {}), (1, 4000, 5000, {})]),
        _line(2, "XLA Ops", [(2, 1000, 1800, {}), (3, 4000, 5000, {}),
                             (4, 4000, 3000, {}), (2, 7000, 2000, {})]),
    ], ["jit__lambda_(7)", "%fusion.3 = f32[8] fusion(%p)",
        "%while.2 = (s32[]) while(%t)", "%copy.11 = f32[8] copy(%q)"])
    host = _plane(2, "/host:CPU", [
        _line(7, "python", [(1, 500, 700, {1: 1}), (2, 1200, 1900, {}),
                            (3, 3100, 300, {}), (4, 3700, 400, {1: 1}),
                            (5, 4100, 4950, {})]),
        _line(8, "python", [(6, 100, 300, {})]),
    ], ["tpuserve.prefill.launch", "tpuserve.prefill.fetch",
        "tpuserve.round.wakeup", "tpuserve.segment.launch",
        "tpuserve.segment.fetch", "tpuserve.round.admit_host"], ["programs"])
    out = tmp_path / "capture"
    out.mkdir()
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(device + host))
    return out


def test_attribute_idle_books_a_known_gap(synthetic_capture):
    got = attribute_idle(synthetic_capture)
    idle = got["idle"]
    # Window 1000-9000; operations cover 1000-2800 and 4000-9000.
    assert idle["window_ms"] == pytest.approx(8.0)
    assert idle["busy_ms"] == pytest.approx(6.8)
    assert idle["idle_ms"] == pytest.approx(1.2)
    # The gap between the runs: 100 us still in prefill.fetch, 300 in
    # round.wakeup, 300 in segment.launch, 300 that no phase covers; the
    # 200 us before the prefill run's end are idle inside a program.
    assert idle["by_phase"] == {
        "round.wakeup": pytest.approx(0.3),
        "segment.launch": pytest.approx(0.3),
        "in_program": pytest.approx(0.2),
        "prefill.fetch": pytest.approx(0.1)}
    assert idle["unattributed_ms"] == pytest.approx(0.3)
    assert sum(idle["by_phase"].values()) + idle["unattributed_ms"] \
        == pytest.approx(idle["idle_ms"])
    (gap,) = idle["gaps"]
    assert (gap["before"], gap["after"], gap["count"]) \
        == ("prefill", "segment", 1)
    assert gap["ms"] == pytest.approx(1.0)
    assert gap["phases"]["unattributed"] == pytest.approx(0.3)
    # segment.fetch ended 50 us after the run it waited for.
    assert idle["clock"] == {"segments": 1, "ok": 1,
                             "lag_ms": {"min": 0.05, "max": 0.05},
                             "device_early_ms": 0.0}
    # Both runs are jit__lambda_ to the profiler; the launches name them, and
    # the envelope (while) is not an operation of its own.
    # No scope in this capture: every operation is in no part.
    assert got["programs"] == {
        "prefill": {"runs": 1, "device_ms": pytest.approx(2.0),
                    "ops": {"fusion": pytest.approx(1.8)},
                    "parts": {}, "unnamed_ms": pytest.approx(1.8),
                    "unnamed_ops": {"fusion": pytest.approx(1.8)},
                    "part_ops": {}},
        "segment": {"runs": 1, "device_ms": pytest.approx(5.0),
                    "ops": {"copy": pytest.approx(3.0),
                            "fusion": pytest.approx(2.0)},
                    "parts": {}, "unnamed_ms": pytest.approx(5.0),
                    "unnamed_ops": {"copy": pytest.approx(3.0),
                                    "fusion": pytest.approx(2.0)},
                    "part_ops": {}}}


# -- device time by named part of the model (ISSUE 57) ---------------------------

@pytest.fixture
def parts_capture(tmp_path):
    """A prefill (program 7) and a segment (program 8), both
    ``jit__lambda`` to the profiler, whose operations carry the scope they
    were traced in as their event metadata's ``tf_op``, as a v5e's capture
    does (stat 1 ``program_id``, stat 2 ``tf_op``), in us:

        prefill [1000, 3000): fusion.3 400 (mlp), expert_matmul.9 600
            (experts.matmul inside mlp), convolution.4 300 (under a function
            and a primitive of a part's name: in none), reduce.1 100 (a mark
            on a name that is no part), copy-done.5 50 (no scope: the
            compiler's prefetch, which fusion.3 reads through a bitcast)
        segment [4000, 9000): a while's envelope, fusion.3 2000 (cache_write
            inside attend: the same name, another program), copy.11 500 (no
            part), fusion.3 again 1000
    """
    def op(name, program, scope=None):
        return (name, {1: program, **({2: scope + ":"} if scope else {})})

    here = "jit(_lambda)/jit(layer)/"
    device = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Modules", [(1, 1000, 2000, {}), (2, 4000, 5000, {})]),
        _line(2, "XLA Ops", [
            (3, 1000, 400, {}), (7, 1400, 600, {}), (6, 2000, 300, {}),
            (9, 2300, 100, {}), (10, 2400, 50, {}),
            (8, 4000, 5000, {}), (4, 4000, 2000, {}), (5, 6000, 500, {}),
            (4, 6500, 1000, {})]),
    ], ["jit__lambda(7)", "jit__lambda(8)",
        op("%fusion.3 = f32[8] fusion(f32[8] %bitcast.6)", 7,
           here + "part.mlp/dot_general"),
        op("%fusion.3 = f32[8] fusion(f32[8] %bitcast.6)", 8,
           "jit(_lambda)/while/body/" + "jit(layer)/part.attend/"
           "part.cache_write/scatter"),
        op("%copy.11 = f32[8] copy(%q)", 8, "jit(_lambda)/transpose"),
        op("%convolution.4 = f32[8] convolution(%a, %b)", 7,
           "jit(_lambda)/jit(norm)/norm/conv_general_dilated"),
        op("%expert_matmul.9 = bf16[8] custom-call(%x)", 7,
           here + "part.mlp/part.experts.matmul/expert_matmul/pallas_call"),
        op("%while.2 = (s32[]) while(%t)", 8),
        op("%reduce.1 = f32[] reduce(%x)", 7,
           "jit(_lambda)/part.bogus/reduce_sum"),
        op("%copy-done.5 = f32[8] copy-done((f32[8], u32[]) %copy-start.5)",
           7),
        op("%bitcast.6 = f32[8] bitcast(f32[8] %copy-done.5)", 7)],
        ["program_id", "tf_op"])
    host = _plane(2, "/host:CPU", [
        _line(7, "python", [(1, 500, 700, {1: 1}), (2, 3700, 400, {1: 1})]),
    ], ["tpuserve.prefill.launch", "tpuserve.segment.launch"], ["programs"])
    out = tmp_path / "capture"
    out.mkdir()
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(device + host))
    return out


@pytest.mark.parametrize("case", [
    "inside a part", "innermost wins", "no part",
    "one name in two programs", "a primitive's name is no part",
    "a prefetch takes its reader's part", "parts and unnamed sum to ops"])
def test_device_time_by_part(parts_capture, case):
    programs = attribute_idle(parts_capture)["programs"]
    pre, seg = programs["prefill"], programs["segment"]
    if case == "inside a part":
        assert pre["parts"]["mlp"] == pytest.approx(0.45)
        assert pre["part_ops"]["mlp"]["fusion"] == pytest.approx(0.4)
    elif case == "innermost wins":
        assert pre["parts"]["experts.matmul"] == pytest.approx(0.6)
        assert pre["part_ops"]["experts.matmul"] == {
            "expert_matmul": pytest.approx(0.6)}
        assert seg["parts"] == {"cache_write": pytest.approx(3.0)}
    elif case == "no part":
        # The copy; the while is an envelope and no operation of either sum.
        assert seg["unnamed_ms"] == pytest.approx(0.5)
        assert seg["unnamed_ops"] == {"copy": pytest.approx(0.5)}
        assert "while" not in seg["ops"]
    elif case == "one name in two programs":
        assert pre["part_ops"]["mlp"]["fusion"] == pytest.approx(0.4)
        assert seg["part_ops"]["cache_write"] == {
            "fusion": pytest.approx(3.0)}
    elif case == "a primitive's name is no part":
        assert set(pre["parts"]) == {"mlp", "experts.matmul"}
        assert pre["unnamed_ms"] == pytest.approx(0.4)  # convolution, reduce
    elif case == "a prefetch takes its reader's part":
        # Two steps away, through an operation that never ran; in the
        # segment the same fusion reads nothing that was fetched.
        assert pre["part_ops"]["mlp"] == {
            "fusion": pytest.approx(0.4), "copy-done": pytest.approx(0.05)}
        assert "copy-done" not in seg["ops"]
    else:
        for p in programs.values():
            assert sum(p["parts"].values()) + p["unnamed_ms"] \
                == pytest.approx(sum(p["ops"].values()))
            # The largest part first, and its largest families.
            assert list(p["parts"]) == list(p["part_ops"])


def test_one_read_of_a_capture_serves_both_reductions(synthetic_capture):
    """``/admin/profile`` reads a capture once (``read_capture``) and hands
    it to both reductions: each gives what it gives from the directory."""
    from pytorch_zappa_serverless_tpu.utils.xplane import (
        op_time_breakdown, read_capture)

    capture = read_capture(synthetic_capture)
    device, host = capture
    assert [len(p["ops"]) for p in device] == [4] and len(host) == 6
    # The while is an envelope, the jit_ event no operation at all.
    assert [(fam, is_op) for _, _, fam, is_op, _ in device[0]["ops"]] == [
        ("fusion", True), ("copy", True), ("while", True), ("fusion", True)]
    alone = op_time_breakdown(synthetic_capture)
    assert op_time_breakdown(synthetic_capture, capture) == alone
    compute, counts, overlap, envelope = alone
    assert dict(compute) == {"fusion": 3_800_000, "copy": 3_000_000}
    assert dict(counts) == {"fusion": 2, "copy": 1}
    assert dict(envelope) == {"while": 5_000_000} and not overlap
    assert attribute_idle(synthetic_capture, capture) \
        == attribute_idle(synthetic_capture)


def test_join_survives_a_capture_that_begins_mid_round(tmp_path):
    """The first run's launch is not in the capture, and the device plane's
    clock is 300 us early (the second prefill's run seems to start before
    its launch did): the join neither hands the first run to the first
    launch nor loses a prefill and shifts every later name by one, and the
    gaps are booked with the clock put right."""
    device = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Modules", [(1, 1500, 8000, {}), (1, 11000, 9000, {}),
                                 (1, 20200, 500, {}), (1, 21500, 500, {}),
                                 (1, 23000, 9000, {})]),
        _line(2, "XLA Ops", [(2, 1500, 8000, {}), (2, 11000, 9000, {}),
                             (2, 20200, 500, {}), (2, 21500, 500, {}),
                             (2, 23000, 9000, {})]),
    ], ["jit__lambda_(7)", "%fusion.3 = f32[8] fusion(%p)"])
    host = _plane(2, "/host:CPU", [
        # round.distribute of the round whose segment.launch came too early
        # to be recorded, then: three prefills of one round, its segment.
        _line(7, "python", [(5, 1000, 200, {})]),
        _line(8, "python", [(1, 10000, 1500, {1: 1}), (2, 11500, 8900, {}),
                            (1, 20500, 400, {1: 1}), (2, 20900, 200, {}),
                            (1, 21200, 700, {1: 1}), (2, 21900, 500, {}),
                            (3, 22500, 1000, {1: 1}), (4, 23500, 9000, {})]),
    ], ["tpuserve.prefill.launch", "tpuserve.prefill.fetch",
        "tpuserve.segment.launch", "tpuserve.segment.fetch",
        "tpuserve.round.distribute"], ["programs"])
    out = tmp_path / "capture"
    out.mkdir()
    (out / "a.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(device + host))
    got = attribute_idle(out)
    assert {k: v["runs"] for k, v in got["programs"].items()} \
        == {"jit__lambda_": 1, "prefill": 3, "segment": 1}
    # The fetch returned 500 us after the segment's run by the planes' own
    # clocks, 200 us with the device's put right.
    assert got["idle"]["clock"] == {
        "segments": 1, "ok": 1, "lag_ms": {"min": 0.2, "max": 0.2},
        "device_early_ms": 0.3}
    gaps = {(g["before"], g["after"]): g for g in got["idle"]["gaps"]}
    assert set(gaps) == {("jit__lambda_", "prefill"), ("prefill", "segment"),
                         ("prefill", "prefill")}
    # Device 20000-20200 is host 20300-20500: the fetch had returned at
    # 20400, the next prefill's launch began at 20500.  Device 20700-21500
    # is host 21000-21800: 100 us of the second fetch, 100 of nothing, 600
    # of the third launch.
    assert gaps[("prefill", "prefill")]["count"] == 2
    assert gaps[("prefill", "prefill")]["phases"] == {
        "prefill.launch": pytest.approx(0.6),
        "prefill.fetch": pytest.approx(0.2),
        "unattributed": pytest.approx(0.2)}


def test_attribute_idle_without_annotations_or_device(tmp_path):
    """A run nobody launched under an annotation keeps its module's name,
    and a capture with no device plane reduces to nothing, not an error."""
    device = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Modules", [(1, 0, 1000, {}), (1, 3000, 1000, {})]),
        _line(2, "XLA Ops", [(2, 0, 1000, {}), (2, 3000, 1000, {})]),
    ], ["jit__lambda_(7)", "%fusion.3 = f32[8] fusion(%p)"])
    out = tmp_path / "capture"
    out.mkdir()
    (out / "a.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(device))
    got = attribute_idle(out)
    assert set(got["programs"]) == {"jit__lambda_"}
    assert got["idle"]["unattributed_ms"] == pytest.approx(2.0)
    assert got["idle"]["clock"] == {"segments": 0, "ok": 0, "lag_ms": None,
                                    "device_early_ms": 0.0}
    empty = tmp_path / "empty"
    empty.mkdir()
    assert attribute_idle(empty) == {"idle": {}, "programs": {}}
