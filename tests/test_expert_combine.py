"""ops/expert_matmul.expert_combine: the ``tiles`` regime's un-sort in one pass.

Out of the second grouped call come rows sorted by expert, each group on a
multiple of the tile; a row's result is the weighted sum of its ``top_k``
rows among them.  The pass that weighs and sums them as it reads them
(interpreted here) against the form it replaced, a cast of every gathered row
to float32 and ``einsum("knd,kn->nd")``, on the three served families'
``top_k`` and widths (cut to an eighth), with sizes as a router draws them.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.ops import expert_matmul as E

# ``(top_k, K, E)``: LFM2 (K 2,048), Mellum 2 (2,304), Nemotron-H (1,024).
FAMILIES = {"lfm2": (4, 256, 16), "mellum": (8, 288, 16),
            "nemotron-h": (22, 128, 32)}
# ``(offset, held)`` of the ``E`` experts, or what else the case bends.
CASES = ("all held", "held elsewhere", "an expert no row reaches",
         "no row held")
TILE = 16


def _routed(rng, family: str, case: str, tokens: int = 200):
    """A router's draw for ``tokens`` rows: ``top_k`` distinct experts of
    ``E`` a row and their normalised weights, as :func:`E.route` leaves
    them: ``(weights [N, top_k], group [N, top_k], held)``."""
    top_k, _, experts = FAMILIES[family]
    chosen = np.argsort(rng.random((tokens, experts)), axis=1)[:, :top_k]
    offset, held = {"held elsewhere": (experts // 4, experts // 2),
                    "no row held": (experts, experts // 2)}.get(
                        case, (0, experts))
    if case == "an expert no row reaches":
        chosen = np.where(chosen == 3, (chosen + 1) % experts, chosen)
    w = rng.random((tokens, top_k)).astype(np.float32)
    local = chosen - offset
    group = np.where((local >= 0) & (local < held), local, held)
    return (jnp.asarray(w / w.sum(-1, keepdims=True)),
            jnp.asarray(group, jnp.int32), held)


def _gathered(rng, weights, group, held, K, junk=0.0):
    """What the un-sort hands the pass: rows as the second call writes them
    (each group on a multiple of ``TILE``; every row no group holds is
    ``junk``), gathered through the sorted order as
    ``_experts_laid_out`` gathers them → ``(rows [top_k, N, K] bfloat16,
    weights with 0 where the expert is not held)``."""
    N, top_k = group.shape
    sizes = E.group_sizes(group, held)
    order = jnp.argsort(group.reshape(-1), stable=True)
    _, back = E.sorted_places(order, sizes, top_k, TILE)
    here = back != E._NOWHERE
    y = jnp.full((E.laid_rows(N * top_k, held, TILE), K), junk,
                 jnp.bfloat16).at[back[here]].set(jnp.asarray(
                     rng.standard_normal((int(here.sum()), K)), jnp.bfloat16))
    rows = y[jnp.where(here, back, 0).T.reshape(-1)].reshape(top_k, N, K)
    return rows, jnp.where(here, weights, 0), sizes


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_pass_is_the_cast_and_the_einsum_it_replaces(family, case):
    rng = np.random.default_rng(54)
    weights, group, held = _routed(rng, family, case)
    rows, w, sizes = _gathered(rng, weights, group, held, FAMILIES[family][1])
    assert (int(sizes.sum()) == 0) == (case == "no row held")
    assert (case != "an expert no row reaches") or int(sizes[3]) == 0
    got = E.expert_combine(rows, w, interpret=True)
    with jax.default_matmul_precision("highest"):
        want = jnp.einsum("knd,kn->nd", rows.astype(jnp.float32), w.T)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    # float32 products of the same bfloat16 rows, summed in another order.
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    if case == "no row held":
        assert not np.asarray(got).any()
    else:
        assert np.abs(np.asarray(want)).max() > 0.1


@pytest.mark.parametrize("case", ["held elsewhere", "no row held"])
def test_a_weight_of_zero_gives_zero_whatever_lies_in_the_row(case):
    """The kernel of the ``tiles`` regime never writes a row that no group
    holds, and an assignment held elsewhere reads row 0: with NaN in every
    such row (row 0's neighbours past its group among them, and row 0
    itself where nothing is held), and in half of what an assignment held
    elsewhere reads, the sum is what it is over zeros."""
    rng = np.random.default_rng(7)
    weights, group, held = _routed(rng, "mellum", case)
    rows, w, sizes = _gathered(rng, weights, group, held, 288, junk=np.nan)
    read_junk = np.isnan(np.asarray(rows, np.float32)).any(-1)
    assert read_junk.any() == (case == "no row held")
    if case == "held elsewhere":
        rows = rows.at[:, :, 1::2].set(jnp.where(w.T[:, :, None] == 0,
                                                 jnp.nan, rows[:, :, 1::2]))
    got = np.asarray(E.expert_combine(rows, w, interpret=True))
    assert np.isfinite(got).all()
    clean = jnp.where(w.T[:, :, None] != 0, rows.astype(jnp.float32), 0)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jnp.einsum("knd,kn->nd", clean, w.T))
    assert np.abs(got - want).max() < 1e-5


@pytest.mark.parametrize("tokens", [130, 512])
def test_rows_past_the_last_whole_block_are_summed_too(tokens):
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((4, tokens, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.random((tokens, 4)), jnp.float32)
    assert tokens % E.pick_combine_rows(4, 64, 2) in (0, 130)
    got = E.expert_combine(rows, w, interpret=True)
    want = (rows.astype(jnp.float32) * w.T[:, :, None]).sum(0)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


@pytest.mark.parametrize("family, rows", [("lfm2", 128), ("mellum", 64),
                                          ("nemotron-h", 64)])
def test_a_block_of_the_pass_fits_the_vmem_a_kernel_is_given(family, rows):
    top_k, K, _ = FAMILIES[family]
    K *= 8
    assert E.pick_combine_rows(top_k, K, 2) == rows
    # Two buffers of the gathered rows and of the result, the float32 sum
    # and a product beside it: inside 16 MiB with room for the compiler's.
    assert (2 * top_k * rows * K * 2 + 4 * rows * K * 4) <= 12 << 20


def test_the_plan_says_where_the_pass_runs():
    # Mellum 2's segment (32 slots) and its 8,192 bucket.
    assert E.plan_summary(32, 8, 2304, 896, 64, True)["unsort"] == "einsum"
    assert E.plan_summary(8192, 8, 2304, 896, 64, True) == {
        "regime": "tiles", "tile": 128, "blocks": [896, 2304],
        "grid": E.plan(65536, 2304, 896, 64, 2).grid,
        "vmem": E.plan(65536, 2304, 896, 64, 2).vmem, "unsort": "combine"}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_experts_takes_the_pass_in_the_tiles_regime_alone(monkeypatch,
                                                          family):
    """:func:`experts` through the kernels (interpreted; the process steered
    to them) calls the pass once where the plan says ``tiles`` and not at
    all under it, and is the ``ragged_dot`` path's sum either way."""
    top_k, K, experts = FAMILIES[family]
    rng = np.random.default_rng(11)
    held, F, calls = experts // 2, 48, []
    w1, w3 = (jnp.asarray(rng.standard_normal((held, K, F)) * 0.1,
                          jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((held, F, K)) * 0.1, jnp.float32)

    the_pass = E.expert_combine

    def combine(y, weights):
        calls.append(y.shape)
        return the_pass(y, weights, interpret=True)

    for tokens, regime in ((8, "stream"), (-(-128 * held // top_k) + 3,
                                           "tiles")):
        weights, group, _ = _routed(rng, family, "held elsewhere", tokens)
        u = jnp.asarray(rng.standard_normal((tokens, K)), jnp.float32)
        assert E.plan(tokens * top_k, K, F, held, 2).regime == regime
        with monkeypatch.context() as m, \
                jax.default_matmul_precision("highest"):
            want, _ = E.experts(u, w1, w2, weights, group, w3=w3)
            m.setattr(E, "_use_kernel", lambda: True)
            m.setattr(E, "expert_matmul_kernel", functools.partial(
                E.expert_matmul_kernel, interpret=True))
            m.setattr(E, "expert_combine", combine)
            got, _ = E.experts(u, w1, w2, weights, group, w3=w3)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
        assert calls == ([] if regime == "stream" else [(top_k, tokens, K)])
