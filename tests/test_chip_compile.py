"""The Pallas kernels of the GPT-2 medium path, compiled for a DESCRIBED
TPU v5e chip at the model's real widths (B=8, H=16, T=1024, D=64, bf16;
LayerNorm / softmax at d=1024).

No chip is needed: the TPU compiler is installed and compiles for a topology
that is described, not attached. What Mosaic refuses here it refuses on the
chip (the segment-id block layout was caught this way), which interpret-mode
tests cannot see. Nothing runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture — never at import —
so that under pytest-xdist only the worker that runs this file loads libtpu.
All of these tests stay in this one file for the same reason.
"""
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import pallas_kernels as pk

B, H, T, D = 8, 16, 1024, 64
BQ, BK = 256, 512            # the default flash blocks at T=1024
SCALE = 1.0 / D ** 0.5


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_kernels(one_chip):
    """Steer the kernel gates as they stand on a TPU (``default_backend()``
    still says cpu here) and keep these compiles out of the persistent
    cache: an entry compiled for the chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    mp = pytest.MonkeyPatch()
    mp.setattr(pk, "_use_pallas", lambda: True)
    mp.setattr(pk, "_interpret", lambda: False)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield one_chip
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    mp.undo()


def _compiled_kernels(fn, *args):
    """Compile ``fn`` for the described chip; the count of Mosaic kernels."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _qkv(chip, t=T, b=B, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((b, H, t, D), dtype, sharding=chip)


def _seg(chip, t=T, b=B):
    return jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=chip)


def _lse(chip):
    return jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32, sharding=chip)


def _seg_operands(chip, seg):
    """Two (B, T) id operands for the segment variant, none for the plain."""
    return (_seg(chip), _seg(chip)) if seg else ()


def _seg_kw(ids):
    return {"q_seg": ids[0], "k_seg": ids[1]} if ids else {}


@pytest.mark.parametrize("lse", [False, True], ids=["out", "out_and_lse"])
@pytest.mark.parametrize("seg", [False, True], ids=["plain", "segments"])
def test_flash_forward_compiles(chip_kernels, seg, lse):
    q = _qkv(chip_kernels)
    assert _compiled_kernels(
        lambda q, k, v, *ids: pk._flash_attention_tpu(
            q, k, v, SCALE, True, BQ, BK, return_lse=lse, **_seg_kw(ids)),
        q, q, q, *_seg_operands(chip_kernels, seg)) == 1


@pytest.mark.parametrize("which", ["dq", "dkv"])
@pytest.mark.parametrize("seg", [False, True], ids=["plain", "segments"])
def test_flash_backward_compiles(chip_kernels, seg, which):
    """dq and dk/dv are two kernels inside ``_flash_bwd_tpu``; returning
    only one side lets XLA drop the other, so each compiles alone."""
    q = _qkv(chip_kernels)
    pick = (lambda dq, dk, dv: dq) if which == "dq" \
        else (lambda dq, dk, dv: (dk, dv))
    assert _compiled_kernels(
        lambda q, k, v, o, l, g, *ids: pick(*pk._flash_bwd_tpu(
            q, k, v, o, l, g, SCALE, True, BQ, BK, **_seg_kw(ids))),
        q, q, q, q, _lse(chip_kernels), q,
        *_seg_operands(chip_kernels, seg)) == 1


def test_ragged_segments_take_the_padded_path(chip_kernels):
    """T=1000 does not tile: the public entry pads to the block and hides
    the tail behind sentinel segment ids — forward, dq and dk/dv kernels
    all compile, with no switch to the XLA reference."""
    q = _qkv(chip_kernels, t=1000, b=2)
    seg = _seg(chip_kernels, t=1000, b=2)

    def loss_and_grads(q, k, v, s):
        def f(q, k, v):
            return pk.flash_attention(q, k, v, None, True, s, s).astype(
                jnp.float32).sum()
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    assert _compiled_kernels(loss_and_grads, q, q, q, seg) == 3


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_fused_layer_norm_compiles(chip_kernels, dtype):
    x = jax.ShapeDtypeStruct((B * T, 1024), dtype, sharding=chip_kernels)
    g = jax.ShapeDtypeStruct((1024,), jnp.float32, sharding=chip_kernels)
    assert _compiled_kernels(
        lambda x, g, b: pk._fused_ln(x, g, b, 1e-5, 128), x, g, g) == 1


def test_fused_softmax_compiles(chip_kernels):
    x = jax.ShapeDtypeStruct((B * T, 1024), jnp.bfloat16,
                             sharding=chip_kernels)
    assert _compiled_kernels(
        lambda x: pk._fused_softmax_impl(x, 128), x) == 1
