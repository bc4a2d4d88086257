"""``chip_smoke.pool_sized_moves``: which instructions of an optimised HLO
module count as moving a layer of the slot pool (the smoke's segment phase
runs it on the chip; here on recorded shapes of the two programs)."""

import chip_smoke

LAYER = 8 * 960 * 1600

# The parent's decode step, as the v5e compiler wrote it: the layer sliced
# out of the pool and copied to a heads-major layout, in the scan's body.
PARENT = """\
%fused_computation.9.clone (param_0.1: bf16[8,8,960,1600]) -> bf16[8,8,960,1600] {
  %param_0.1 = bf16[8,8,960,1600]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %scatter.1 = bf16[8,8,960,1600]{3,2,1,0:T(8,128)(2,1)} scatter(%param_0.1), to_apply=%r
}

%wide.region_0.80 (arg: (bf16[8,8,960,1600])) -> (bf16[8,8,960,1600]) {
  %fusion.7 = bf16[8,8,960,1600]{3,2,1,0:T(8,128)(2,1)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.9.clone
  %slice.331 = bf16[1,8,960,1600]{3,2,1,0:T(8,128)(2,1)S(1)} slice(%fusion.7), slice={[3:4], [0:8], [0:960], [0:1600]}
  %copy.12 = bf16[1,8,960,1600]{2,3,1,0:T(8,128)(2,1)S(1)} copy(%slice.331)
  %slice-start.2 = ((bf16[8,8,960,1600]), bf16[1,8,960,1600]{3,2,1,0}, s32[]) slice-start(%fusion.7)
  %slice-done.2 = bf16[1,8,960,1600]{3,2,1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.2)
  %small = bf16[8,1600]{1,0} copy(%x)
}

ENTRY %main.82 (p: bf16[50257,1600]) -> bf16[8,8] {
  %copy.43 = bf16[50257,1600]{1,0:T(8,128)(2,1)} copy(%p)
}
"""

# This PR's: the slice lives inside the attention fusion (it is the dot's
# addressing), and nothing of the pool's size is left outside one.
CHANGE = """\
%fused_computation.92.clone (param_0.3: bf16[8,8,960,1600]) -> bf16[8,960,1600] {
  %param_0.3 = bf16[8,8,960,1600]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %slice.9 = bf16[1,8,960,1600]{3,2,1,0:T(8,128)(2,1)} slice(%param_0.3), slice={[3:4], [0:8], [0:960], [0:1600]}
  ROOT %bitcast.4 = bf16[8,960,1600]{2,1,0:T(8,128)(2,1)} bitcast(%slice.9)
}

%fused_computation.99.clone (param_0.2: bf16[8,8,960,1600]) -> bf16[8,1600] {
  %param_0.2 = bf16[8,8,960,1600]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %slice_bitcast_fusion.98 = bf16[8,960,1600]{2,1,0:T(8,128)(2,1)} fusion(%param_0.2), kind=kLoop, calls=%fused_computation.92.clone
  ROOT %convolution.97 = bf16[8,1600]{1,0} convolution(%p, %slice_bitcast_fusion.98), dim_labels=0bf_0io->0bf
}

%wide.region_0.9 (arg: (bf16[8,8,960,1600])) -> (bf16[8,8,960,1600]) {
  %fusion.1016 = bf16[8,1600]{1,0:T(8,128)(2,1)S(1)} fusion(%gte.2), kind=kOutput, calls=%fused_computation.99.clone
  %custom-call.3 = bf16[8,1,1600]{2,1,0} custom-call(%w, %q, %gte.2, %gte.3), custom_call_target="tpu_custom_call"
}

ENTRY %main.94 (p: bf16[50257,1600]) -> bf16[8,8] {
  %copy.11 = bf16[50257,1600]{1,0:T(8,128)(2,1)} copy(%p)
}
"""


def test_parent_program_moves_a_layer_of_the_pool_every_step():
    moves = chip_smoke.pool_sized_moves(PARENT, LAYER)
    ops = sorted(desc.split(" = ")[0].split(": ")[1] for _, desc in moves)
    # The slice, the copy and the finished asynchronous slice (its start
    # returns a tuple and is the same move); not the in-place scatter
    # fusion, not the small copy.
    assert ops == ["copy.12", "copy.43", "slice-done.2", "slice.331"]
    assert moves[0][0] == 50257 * 1600  # largest first
    of_pool = [m for m in moves if m[0] % (960 * 1600) == 0]
    assert len(of_pool) == 3 and all("wide.region_0.80" in d
                                     for _, d in of_pool)


def test_change_program_moves_nothing_of_the_pool():
    moves = chip_smoke.pool_sized_moves(CHANGE, LAYER)
    assert [d.split(": ")[1].split(" = ")[0] for _, d in moves] == ["copy.11"]
    assert not [m for m in moves if m[0] % (960 * 1600) == 0]


def test_a_fusion_named_after_a_move_counts_outside_a_fusion():
    text = CHANGE.replace(
        "  %custom-call.3 =",
        "  %slice_bitcast_fusion.7 = bf16[8,960,1600]{2,1,0} fusion(%gte.2),"
        " kind=kLoop, calls=%fused_computation.92.clone\n  %custom-call.3 =")
    moves = chip_smoke.pool_sized_moves(text, LAYER)
    assert any("slice_bitcast_fusion.7" in d for _, d in moves)
