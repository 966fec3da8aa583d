"""The routed layer's forward kernel (``mxtpu_experts_swiglu``) in interpret
mode on the CPU at tiny sizes: ``routed_experts`` through the kernel against
the loop it replaces on the chip and against the plain form of the Granite
reference (``chipbench/reference_granite_hybrid.routed_plus_shared``: every
held expert over every token, weighted by the router's own top-k)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import moe
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.registry import get_op

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "benchmark", "chip"))

D, F, E = 128, 128, 16


def _inputs(n, dtype, skew=False, seed=0):
    """Rows, a router, the stacked matrices of all E experts. ``skew``: one
    expert takes every token, two share the second choice, the rest none."""
    rng = onp.random.RandomState(seed)
    x = rng.randn(n, D).astype("float32")
    router = rng.randn(E, D).astype("float32") * 0.2
    if skew:
        x[:, 0] = 4.0
        router[:, 0] = -4.0
        router[5, 0], router[6, 0], router[7, 0] = 4.0, 0.0, 0.0
    gate_up = rng.randn(E, D, 2 * F).astype("float32") * 0.1
    down = rng.randn(E, F, D).astype("float32") * 0.1
    return (jnp.asarray(x, dtype), jnp.asarray(router),
            jnp.asarray(gate_up, dtype), jnp.asarray(down, dtype))


def _routed(x, router, gate_up, down, held, top_k, tile, kernel, mp):
    """``routed_experts`` on the experts ``held`` with the kernel path on
    (interpreted) or off (the loop)."""
    lo, hi = held
    with mp.context() as m:
        if kernel:
            m.setenv("MXTPU_PALLAS_INTERPRET", "1")
        else:
            m.setattr(pk, "_use_pallas", lambda: False)
        w, e, _ = get_op("moe_router")._make_fn(top_k=top_k)(x, router)
        op = get_op("routed_experts")._make_fn(experts_held=held, tile=tile)
        return op(x, w, e, gate_up[lo:hi], down[lo:hi]), e


def _reference(x, router, gate_up, down, held, top_k):
    """The plain form in float32 on the same (rounded) inputs, the shared
    MLP zero."""
    from chipbench import reference_granite_hybrid as ref

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    lo, hi = held
    w = {"router.weight": router, "gate_up": f32(gate_up[lo:hi]),
         "down": f32(down[lo:hi]),
         "shared_in.weight": jnp.zeros((2, D), jnp.float32),
         "shared_out.weight": jnp.zeros((D, 1), jnp.float32)}
    y, _, _ = ref.routed_plus_shared(
        f32(x)[None], w, {"num_experts_per_tok": top_k}, held)
    return y[0]


CASES = {
    # name: (rows, dtype, held, top_k, tile, skew)
    "f32_32rows_4_pairs_an_expert": (32, jnp.float32, (4, 12), 2, 128, False),
    "bf16_32rows_4_pairs_an_expert": (32, jnp.bfloat16, (4, 12), 2, 128,
                                      False),
    "f32_256rows_several_tiles": (256, jnp.float32, (0, 4), 4, 32, False),
    "bf16_256rows_several_tiles": (256, jnp.bfloat16, (0, 4), 4, 32, False),
    "bf16_one_expert_takes_every_token": (64, jnp.bfloat16, (4, 12), 2, 32,
                                          True),
    "f32_one_expert_takes_every_token": (64, jnp.float32, (4, 12), 2, 32,
                                         True),
    "f32_rows_not_a_multiple_of_the_tile": (200, jnp.float32, (2, 10), 3,
                                            128, False),
    "bf16_rows_not_a_multiple_of_the_tile": (208, jnp.bfloat16, (2, 10), 3,
                                             64, False),
    "f32_rows_no_choice_is_held": (48, jnp.float32, (0, 3), 2, 128, False),
    "bf16_rows_no_choice_is_held": (48, jnp.bfloat16, (13, 16), 2, 128,
                                    False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_path_equals_loop_and_plain_reference(case, monkeypatch):
    n, dtype, held, top_k, tile, skew = CASES[case]
    x, router, gate_up, down = _inputs(n, dtype, skew)
    lo, hi = held
    tm = moe._tile_rows(n, tile)
    with monkeypatch.context() as m:
        m.setenv("MXTPU_PALLAS_INTERPRET", "1")
        assert pk.experts_kernel_serves(n, tm, D, F, dtype, dtype)
    y_k, chosen = _routed(x, router, gate_up, down, held, top_k, tile, True,
                          monkeypatch)
    y_l, _ = _routed(x, router, gate_up, down, held, top_k, tile, False,
                     monkeypatch)
    ref = _reference(x, router, gate_up, down, held, top_k)
    assert y_k.dtype == x.dtype and y_k.shape == x.shape
    f32 = lambda a: onp.asarray(a.astype(jnp.float32))  # noqa: E731
    scale = onp.abs(f32(ref)).max()
    err_k, err_l = f32(y_k) - f32(ref), f32(y_l) - f32(ref)
    # float32 rows and weights are rounded to bfloat16 at the matrix unit,
    # in the kernel as at the chip's default precision: 1e-2 holds both
    assert onp.abs(err_k).max() <= 1e-2 * scale
    if dtype == jnp.bfloat16:
        # float32 products and SwiGLU where the loop rounds: no worse
        assert onp.sqrt((err_k ** 2).mean()) \
            <= 1.02 * onp.sqrt((err_l ** 2).mean())
    chosen = onp.asarray(chosen)
    here = ((chosen >= lo) & (chosen < hi))
    none_held = ~here.any(axis=1)
    assert (f32(y_k)[none_held] == 0).all()
    assert (onp.abs(f32(y_k)[~none_held]).max(axis=1) > 0).all()
    counts = onp.bincount(chosen[here] - lo, minlength=hi - lo)
    if skew:
        assert counts.max() == n and (counts == 0).sum() >= (hi - lo) - 3
    if "several_tiles" in case:
        assert counts.min() > tm          # two tiles at least
    if "no_choice" in case:
        assert none_held.sum() >= 8 and (~none_held).sum() >= 8


def test_no_pair_held_at_all_gives_zeros(monkeypatch):
    """Held experts none of which any token chose: no tile is in use, the
    kernel's result holds anything, and the sum selects none of it."""
    x, router, gate_up, down = _inputs(64, jnp.float32, skew=True)
    y, chosen = _routed(x, router, gate_up, down, (8, 16), 2, 32, True,
                        monkeypatch)
    assert onp.asarray(chosen).max() < 8
    assert (onp.asarray(y) == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gradients_do_not_depend_on_the_forward_path(dtype, monkeypatch):
    """The backward pass recomputes its tiles in the loop whichever path the
    forward took: the gradients of a linear loss are the same numbers."""
    n, held, top_k = 96, (2, 10), 3
    x, router, gate_up, down = _inputs(n, dtype)
    lo, hi = held
    gu, dn = gate_up[lo:hi], down[lo:hi]
    w, e, _ = get_op("moe_router")._make_fn(top_k=top_k)(x, router)
    c = jnp.asarray(onp.random.RandomState(1).randn(n, D), jnp.float32)

    def grads(kernel):
        with monkeypatch.context() as m:
            if kernel:
                m.setenv("MXTPU_PALLAS_INTERPRET", "1")
            else:
                m.setattr(pk, "_use_pallas", lambda: False)
            op = get_op("routed_experts")._make_fn(experts_held=held, tile=32)
            return jax.grad(
                lambda x, w, gu, dn: jnp.sum(
                    op(x, w, e, gu, dn).astype(jnp.float32) * c),
                argnums=(0, 1, 2, 3))(x, w, gu, dn)

    for g_k, g_l in zip(grads(True), grads(False)):
        onp.testing.assert_array_equal(onp.asarray(g_k.astype(jnp.float32)),
                                       onp.asarray(g_l.astype(jnp.float32)))


@pytest.mark.parametrize("case", [
    "tile_of_8_rows_bfloat16", "lanes_not_whole",
    "an_expert_too_large_for_fast_memory",
    "the_tokens_sums_too_large_for_fast_memory"])
def test_shapes_the_kernel_does_not_take_go_to_the_loop(case, monkeypatch):
    """The kernel keeps an expert (twice) and every token's float32 sum in
    fast memory: the Granite cell's tick (32 rows) and prefills (128-1024
    rows of 4096) fit, the Qwen cell's 4096 rows of 2048 in float32 beside
    float32 experts do not and keep the loop."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    bf16, f32 = jnp.bfloat16, jnp.float32
    n, tm, d, f, dt = {
        "tile_of_8_rows_bfloat16": (8, 8, 128, 128, bf16),
        "lanes_not_whole": (32, 32, 96, 128, f32),
        "an_expert_too_large_for_fast_memory": (128, 128, 8192, 2048, bf16),
        "the_tokens_sums_too_large_for_fast_memory": (4096, 128, 2048, 512,
                                                      f32),
    }[case]
    assert not pk.experts_kernel_serves(n, tm, d, f, dt, dt)
    for rows in (32, 128, 256, 512, 1024):
        assert pk.experts_kernel_serves(rows, min(rows, 128), 4096, 768,
                                        bf16, bf16)


def test_plan_places_every_held_pair_in_its_experts_tile():
    """``_plan``: every pair held has one slot, in a tile of its expert,
    among the tile's first ``tile_rows`` slots; the other slots are padding
    with weight 0; the tiles in use are ``tile_hi[-1]``."""
    rng = onp.random.RandomState(2)
    n, k, lo, hi, tm = 40, 3, 2, 9, 8
    experts = jnp.asarray(onp.stack(
        [rng.permutation(12)[:k] for _ in range(n)]).astype("int32"))
    weights = jnp.asarray(rng.rand(n, k).astype("float32"))
    plan = moe._plan(weights, experts, lo, hi, tm)
    pair, tok = onp.asarray(plan.pair), onp.asarray(plan.tok)
    rows, te = onp.asarray(plan.tile_rows), onp.asarray(plan.tile_expert)
    e, w = onp.asarray(experts).reshape(-1), onp.asarray(weights).reshape(-1)
    here = (e >= lo) & (e < hi)
    used = pair < n * k
    assert sorted(pair[used]) == list(onp.flatnonzero(here))
    assert (e[pair[used]] - lo == onp.repeat(te, tm)[used]).all()
    assert (tok[used] == pair[used] // k).all() and (tok[~used] >= n).all()
    assert (onp.asarray(plan.w_slot)[used] == w[pair[used]]).all()
    assert (onp.asarray(plan.w_slot)[~used] == 0).all()
    assert (used.reshape(-1, tm)
            == (onp.arange(tm) < rows[:, None])).all()
    n_tiles = int(plan.tile_hi[-1])
    assert n_tiles == sum(-(-int((e == g).sum()) // tm)
                          for g in range(lo, hi))
    assert (rows[:n_tiles] > 0).all() and (rows[n_tiles:] == 0).all()
    assert (onp.diff(te) >= 0).all()
