"""The trace reducer on captures whose times are known.

One capture is written here by hand, in the layout the v5e's captures have
(a ``/device:TPU:0`` plane with ``XLA Modules``, ``XLA Ops`` and ``Async XLA
Ops`` lines, programs named ``jit__lambda(<id>)`` and ``jit__insert_from``),
so every expected number can be checked against the events below.  The other,
``data/v5e_slice.xplane.pb``, is a slice cut from a capture recorded on the
chip during this benchmark's own traffic; its numbers are pinned.
"""

import json
from pathlib import Path

import pytest

from benchmark.trace_reduce import family, module_name, reduce_trace, union_ns

HERE = Path(__file__).resolve().parent
RULES = json.loads((HERE.parent / "configs" / "gpt2-xl.json")
                   .read_text())["programs"]

# (name, start us, duration us): two segments (each holds a while), one
# prefill, one insert; 10 us idle before the prefill, 5 us before the insert,
# 20 us before the second segment.
MODULES = [("jit__lambda(11)", 0, 100), ("jit__lambda(22)", 110, 40),
           ("jit__insert_from(33)", 155, 5), ("jit__lambda(11)", 180, 100)]
OPS = [("%while.1 = (s32[]) while(...)", 0, 100),
       ("%fusion.7 = bf16[8,1600] fusion(...)", 0, 60),
       ("%copy.3 = bf16[48,8,960,1600] copy(...)", 60, 30),
       # 90..100: the program runs, no operation does
       ("%fusion.9 = bf16[8,768,1600] fusion(...)", 110, 40),
       ("%dynamic-update-slice.2 = bf16[48,8,960,1600] dus(...)", 155, 5),
       ("%while.1 = (s32[]) while(...)", 180, 100),
       ("%fusion.7 = bf16[8,1600] fusion(...)", 180, 50),
       ("%copy.3 = bf16[48,8,960,1600] copy(...)", 230, 50)]
ASYNC = [("%copy-start.5 = (...) copy-start(...)", 20, 200)]


def text_proto(modules=MODULES, ops=OPS, overlapped=ASYNC, host=(),
               unit_ps=10**6):
    """``host`` is (annotation, start, duration, programs) on a host thread;
    times are in ``unit_ps`` picoseconds (microseconds unless said)."""
    meta, lines = {}, []
    for li, (lname, events) in enumerate(
            (("XLA Modules", modules), ("XLA Ops", ops),
             ("Async XLA Ops", overlapped)), 1):
        rows = "".join(
            f"events {{ metadata_id: {meta.setdefault(n, len(meta) + 1)} "
            f"offset_ps: {s * unit_ps} duration_ps: {d * unit_ps} }}\n"
            for n, s, d in events)
        lines.append(f'lines {{ id: {li} name: "{lname}" timestamp_ns: 5000 '
                     f'{rows} }}\n')
    md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} '
                 f'}}\n' for n, i in meta.items())
    hmeta = {}
    rows = "".join(
        f"events {{ metadata_id: {hmeta.setdefault(n, len(hmeta) + 1)} "
        f"offset_ps: {s * unit_ps} duration_ps: {d * unit_ps} "
        f"stats {{ metadata_id: 1 int64_value: {k} }} }}\n"
        for n, s, d, k in host)
    hmd = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
                  f'}} }}\n' for n, i in hmeta.items())
    return (f'planes {{ id: 1 name: "/device:TPU:0" {"".join(lines)}{md} }}\n'
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 name: '
            f'"python3" timestamp_ns: 5000 {rows} }} {hmd} stat_metadata {{ '
            f'key: 1 value {{ id: 1 name: "programs" }} }} }}')


def write_capture(tmp_path, proto):
    from jax.profiler import ProfileData

    path = tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(proto))
    return tmp_path


@pytest.fixture()
def capture(tmp_path):
    return write_capture(tmp_path, text_proto())


def test_known_busy_idle_and_program_times(capture):
    got = reduce_trace(capture, RULES)
    us = 1e-6
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(280 * us)
    # Busy: the union of the operations, 0..100 (the while spans its body)
    # + 110..150 + 155..160 + 180..280; the async line does not count.
    assert got["busy_s"] == pytest.approx(245 * us)
    assert got["programs"]["segment"] == {
        "runs": 2, "seconds": pytest.approx(200 * us),
        "ops": {"fusion": pytest.approx(110 * us),
                "copy": pytest.approx(80 * us)}}
    assert got["programs"]["prefill"] == {
        "runs": 1, "seconds": pytest.approx(40 * us),
        "ops": {"fusion": pytest.approx(40 * us)}}
    assert got["programs"]["insert"] == {
        "runs": 1, "seconds": pytest.approx(5 * us),
        "ops": {"dynamic-update-slice": pytest.approx(5 * us)}}
    ops = dict(got["device_ops"])
    assert "while" not in ops  # an envelope: its body's operations count
    assert ops["fusion"] == pytest.approx(150 * us)
    assert ops["copy"] == pytest.approx(80 * us)
    assert got["device_ops"][0][0] == "fusion"
    assert dict(got["idle_gaps"]) == {
        "segment-prefill": pytest.approx(10 * us),
        "prefill-insert": pytest.approx(5 * us),
        "insert-segment": pytest.approx(20 * us)}


def test_names_and_union():
    assert family("%convert_reduce_fusion.12.3 = f32[8] fusion(...)") \
        == "convert_reduce_fusion"
    assert module_name("jit__lambda(1234567)") == "jit__lambda"
    assert union_ns([(0, 10), (5, 12), (20, 25), (21, 22)]) == 17


def test_capture_without_a_device_plane_reads_as_nothing(tmp_path):
    from jax.profiler import ProfileData

    (tmp_path / "vm.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/host:CPU" }'))
    got = reduce_trace(tmp_path, RULES)
    assert got["busy_s"] == 0 and got["window_s"] == 0 and not got["programs"]


def test_recorded_v5e_slice():
    """126 ms of gpt2-large-int8 chat on the v5e (PR 23's chip run): a
    prefill, its insert, one 8-step segment of 16 slots, the next prefill.
    The module line of the capture reads 3.981, 0.536, 102.643 and 7.554 ms."""
    got = reduce_trace(HERE / "data", RULES)
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(0.126294143, rel=1e-6)
    assert got["busy_s"] == pytest.approx(0.114697692, rel=1e-6)
    seg, pre = got["programs"]["segment"], got["programs"]["prefill"]
    assert (seg["runs"], seg["seconds"]) \
        == (1, pytest.approx(0.102643397, rel=1e-6))
    assert (pre["runs"], pre["seconds"]) \
        == (2, pytest.approx(0.011535008, rel=1e-6))
    assert got["programs"]["insert"]["runs"] == 1
    assert [name for name, _ in got["device_ops"][:2]] == ["slice", "copy"]
    assert dict(got["idle_gaps"])["segment-prefill"] \
        == pytest.approx(0.007566795, rel=1e-6)


def test_pool_copy_slice_pct_counts_the_pool_and_not_the_prefetch():
    """The recorded segment run is the parent of PR 26: it slices every
    layer out of the pool and copies it (78.8% of its 102.6 ms).  Its
    ``copy-done`` (the compiler's weight prefetch) stays out of the count,
    ``slice-done`` (the pool's slice, made asynchronous) stays in."""
    from benchmark.readers import trace as reader

    got = reduce_trace(HERE / "data", RULES)
    ops = got["programs"]["segment"]["ops"]
    assert ops["slice"] == pytest.approx(0.048620892, rel=1e-6)
    assert ops["copy"] == pytest.approx(0.032111024, rel=1e-6)
    assert ops["copy-done"] > 0 and ops["slice-done"] > 0
    assert ops["slice_divide_fusion"] > 0  # a fusion, named for its first op
    want = 100 * (ops["slice"] + ops["copy"] + ops["slice-done"]
                  + ops.get("slice-start", 0.0)) / 0.102643397
    assert reader.read({"trace": got}, "pool_copy_slice_pct") \
        == pytest.approx(want, rel=1e-6) == pytest.approx(78.693, rel=1e-4)
    # XL after PR 26: the prefetch's waits are a quarter of the step, the
    # pool's own copy a fiftieth.
    xl = {"window_s": 1.0, "programs": {"segment": {
        "runs": 42, "seconds": 1.97,
        "ops": {"decode_attention": 0.549, "copy-done": 0.536,
                "copy-start": 0.004, "copy": 0.037}}}}
    assert reader.read({"trace": xl}, "pool_copy_slice_pct") \
        == pytest.approx(100 * 0.037 / 1.97)
    assert reader.read({"trace": {"window_s": 1.0, "programs": {}}},
                       "pool_copy_slice_pct") is None


# Milliseconds.  The first run was launched before the capture began; the
# last is a segment that the capture's end cuts, so its ``while`` is missing.
CUT_MODULES = [("jit__lambda(11)", 0, 10), ("jit__lambda(22)", 13, 2),
               ("jit__insert_from(33)", 16, 1), ("jit__lambda(11)", 20, 4)]
CUT_OPS = [("%while.1 = (s32[]) while(...)", 0, 10),
           ("%fusion.7 = bf16[8,1600] fusion(...)", 0, 10),
           ("%fusion.9 = bf16[8,768,1600] fusion(...)", 13, 2),
           ("%dynamic-update-slice.2 = bf16[48,8,960,1600] dus(...)", 16, 1),
           ("%fusion.7 = bf16[8,1600] fusion(...)", 20, 4)]
CUT_HOST = [("tpuserve.segment.fetch", 1, 10, 1),
            ("tpuserve.prefill.launch", 12, 1, 1),
            ("tpuserve.insert.launch", 15, 1, 1),
            ("tpuserve.segment.launch", 19, 1, 1)]


def test_the_run_the_capture_s_end_cuts_is_what_its_launch_says(tmp_path):
    full = write_capture(tmp_path / "a", text_proto(
        CUT_MODULES, CUT_OPS, (), CUT_HOST, unit_ps=10**9))
    got = reduce_trace(full, RULES)["programs"]
    assert {k: p["runs"] for k, p in got.items()} \
        == {"segment": 2, "prefill": 1, "insert": 1}
    assert got["segment"]["seconds"] == pytest.approx(0.014)
    assert got["segment"]["ops"] == {"fusion": pytest.approx(0.014)}
    # The module rules alone, as before: the cut run reads as a prefill.
    bare = write_capture(tmp_path / "b", text_proto(
        CUT_MODULES, CUT_OPS, (), (), unit_ps=10**9))
    assert {k: p["runs"] for k, p in reduce_trace(bare, RULES)[
        "programs"].items()} == {"segment": 1, "prefill": 2, "insert": 1}


def test_a_launch_names_as_many_runs_as_it_says():
    from benchmark.trace_reduce import launched

    ms = 1_000_000
    mods = [(0, 5 * ms, "a"), (10 * ms, 12 * ms, "b"), (13 * ms, 14 * ms, "b"),
            (30 * ms, 31 * ms, "a")]
    assert launched(mods, [(9 * ms, "prefill", 2)]) \
        == [None, "prefill", "prefill", None]
    # The device plane may read up to 2 ms early against the host's.
    assert launched(mods, [(11 * ms, "prefill", 1), (29 * ms, "segment", 5)]) \
        == [None, "prefill", None, "segment"]
