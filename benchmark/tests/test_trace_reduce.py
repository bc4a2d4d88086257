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


def text_proto():
    meta, lines = {}, []
    for li, (lname, events) in enumerate(
            (("XLA Modules", MODULES), ("XLA Ops", OPS),
             ("Async XLA Ops", ASYNC)), 1):
        rows = "".join(
            f"events {{ metadata_id: {meta.setdefault(n, len(meta) + 1)} "
            f"offset_ps: {s * 10**6} duration_ps: {d * 10**6} }}\n"
            for n, s, d in events)
        lines.append(f'lines {{ id: {li} name: "{lname}" timestamp_ns: 5000 '
                     f'{rows} }}\n')
    md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} '
                 f'}}\n' for n, i in meta.items())
    host = 'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python3" } }'
    return (f'planes {{ id: 1 name: "/device:TPU:0" {"".join(lines)}{md} }}\n'
            + host)


@pytest.fixture()
def capture(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text_proto()))
    return tmp_path


def test_known_busy_idle_and_program_times(capture):
    got = reduce_trace(capture, RULES)
    us = 1e-6
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(280 * us)
    # Busy: the union of the operations, 0..100 (the while spans its body)
    # + 110..150 + 155..160 + 180..280; the async line does not count.
    assert got["busy_s"] == pytest.approx(245 * us)
    assert got["programs"]["segment"] == {"runs": 2,
                                          "seconds": pytest.approx(200 * us)}
    assert got["programs"]["prefill"] == {"runs": 1,
                                          "seconds": pytest.approx(40 * us)}
    assert got["programs"]["insert"] == {"runs": 1,
                                         "seconds": pytest.approx(5 * us)}
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
    assert got["programs"]["segment"] == {
        "runs": 1, "seconds": pytest.approx(0.102643397, rel=1e-6)}
    assert got["programs"]["prefill"] == {
        "runs": 2, "seconds": pytest.approx(0.011535008, rel=1e-6)}
    assert got["programs"]["insert"]["runs"] == 1
    assert [name for name, _ in got["device_ops"][:2]] == ["slice", "copy"]
    assert dict(got["idle_gaps"])["segment-prefill"] \
        == pytest.approx(0.007566795, rel=1e-6)
