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
import math

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


# -- the paged KV pool is updated in place, in the layout the chip keeps it --
@pytest.fixture(scope="module")
def serve_programs(chip_kernels):
    """The serving graphs of a 2-layer model at GPT-2 medium's widths
    (16 heads x 64, pages of 128 positions). The trace runs the forward
    eagerly on the CPU, so the kernels are steered back for its length,
    and the per-op jits forget what they traced then: the compiles below
    take the kernels, as on the chip. The pool is too large (128 MiB) for
    the compiler to stage it in fast memory, as it is in any deployment:
    a staged pool shows as a copy."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.serve.decode import DecodePrograms

    mp = pytest.MonkeyPatch()
    mp.setattr(pk, "_use_pallas", lambda: False)
    try:
        mx.random.seed(3)
        net = GPTModel(vocab_size=512, num_layers=2, units=1024,
                       num_heads=16, max_length=512, dropout=0.0)
        net.initialize()
        progs = DecodePrograms(net, num_slots=5, max_len=512,
                               prefill_batch=2, max_prompt_len=128,
                               min_prompt_bucket=128, page_tokens=128,
                               kv_pages=128, speculate_k=2,
                               prefix_cache=True)
        # beside the draft's tick (K = 2) the plain one, as an engine
        # without speculation traces it
        with mx.autograd.pause():
            progs._cops["decode:1"] = progs._trace(
                "decode", 1, progs._collect_params())
        progs._graph_params["decode:1"] = progs._graph_params["decode:2"]
        return progs
    finally:
        mp.undo()
        jax.clear_caches()


@pytest.mark.parametrize("family", ["decode", "decode_k2", "prefill",
                                    "prefill_ext"])
def test_pool_updates_compile_in_place(chip_kernels, serve_programs, family):
    """The pool is ``f32[pages, layers, 16, 64, 128]``, a page's positions
    as its fastest axis: the layout the chip chose for the older
    ``[.., 128, 64]`` shape, now the declared one, so that the decode
    kernel takes the pool as it stands. An update of single positions
    made the TPU compiler lay the whole pool out anew and back (two
    copies a pool, 27 ms a tick at the benchmark's size); updates of
    whole pages compile in place. Guard: nothing of the pool's shape but
    the updates. The tick besides holds one paged kernel a layer and no
    array of the gathered view's size (slots x max_len x units): the
    view, its re-lay and the dense attention over it are gone. The PLAIN
    tick (K = 1) holds no update either: its kernels take the pools and
    hand them back, written where they lie."""
    import re

    progs = serve_programs
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        tuple(shape), dt, sharding=chip_kernels)
    pool = sds(progs.cache_shape, jnp.float32)
    S, Wt = progs.num_slots, progs.table_width
    tick = family.startswith("decode")
    if tick:
        K = 2 if family == "decode_k2" else 1
        family, gkey = "decode", f"decode:{K}"
        data = [sds((S, K), jnp.int32), sds((S,), jnp.int32),
                sds((S, Wt), jnp.int32)]
    else:
        gkey = f"{family}:128"
        ext = family == "prefill_ext"
        data = [sds((2, 128), jnp.int32), sds((2,), jnp.int32)] \
            + ([sds((2,), jnp.int32)] if ext else []) \
            + [sds((2, Wt), jnp.int32)]
    args = data + [pool, pool] + [
        sds(progs._params[n].shape, progs._params[n].dtype)
        for n in progs._graph_params[gkey]]
    text = progs._cops[gkey].lower(
        *args, donate=progs._donate(family)).compile().as_text()
    shape = "f32[%s]" % ",".join(str(d) for d in progs.cache_shape)
    ops = re.findall(r"= %s\S* ([\w\-]+)\(" % re.escape(shape), text)
    inside = {"parameter", "get-tuple-element", "bitcast"}
    if gkey != "decode:1":
        inside |= {"scatter", "dynamic-update-slice", "fusion", "while"}
    assert ops and set(ops) <= inside, sorted(set(ops) - inside)
    # every pool-shaped fusion is an update fused with what feeds it
    for name in re.findall(r"= %s\S* fusion\(.*calls=%%([\w.\-]+)"
                           % re.escape(shape), text):
        body = text.split("%" + name + " (", 1)[1].split("\n}", 1)[0]
        assert re.search(r" (scatter|dynamic-update-slice)\(", body), name
    if not tick:
        return
    calls = re.findall(r"%mxtpu_paged_decode[.\d]* = (.+?) custom-call\(",
                       text)
    assert len(calls) == 2
    # the plain tick's kernels hand both pools back beside the output
    assert all(c.count(shape) == (2 if K == 1 else 0) for c in calls), calls
    view = S * (Wt - 1) * 128 * 16 * 64
    sized = [ln.strip()[:120] for ln in text.splitlines()
             for m in [re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]",
                                ln)]
             if m and math.prod(map(int, m.group(1).split(","))) == view]
    assert not sized, sized


# -- the Qwen3-Next cell's shapes: one row of 4096, float32 ------------------
QT = 4096          # qwen3-next-80b-a3b.train-4k: 1 row x 4096 tokens
QH, QD = 16, 256   # 16 query heads (2 KV heads, repeated) of 256


def _q4k(chip):
    return jax.ShapeDtypeStruct((1, QH, QT, QD), jnp.float32, sharding=chip)


def test_flash_forward_compiles_at_head_256_and_4096_positions(chip_kernels):
    """One head's K and V are 4 MiB each in float32: double-buffered they
    pass the chip's default 16 MiB of scoped VMEM, and the kernels ask for
    the room they need (``_vmem_params``) instead of falling to the dense
    branch, which would hold 16 x 4096 x 4096 scores."""
    q = _q4k(chip_kernels)
    assert _compiled_kernels(
        lambda q, k, v: pk._flash_attention_tpu(
            q, k, v, QD ** -0.5, True, BQ, BK, return_lse=True),
        q, q, q) == 1


@pytest.mark.parametrize("which", ["dq", "dkv"])
def test_flash_backward_compiles_at_head_256_and_4096_positions(
        chip_kernels, which):
    q = _q4k(chip_kernels)
    lse = jax.ShapeDtypeStruct((1, QH, QT, 1), jnp.float32,
                               sharding=chip_kernels)
    pick = (lambda dq, dk, dv: dq) if which == "dq" \
        else (lambda dq, dk, dv: (dk, dv))
    assert _compiled_kernels(
        lambda q, k, v, o, l, g: pick(*pk._flash_bwd_tpu(
            q, k, v, o, l, g, QD ** -0.5, True, BQ, BK)),
        q, q, q, q, lse, q) == 1


def test_short_sequences_keep_the_default_vmem_limit():
    """The programs of the cells that were there do not change."""
    assert pk._vmem_params(4 * 1024 * 64 * 4) == {}
    assert "compiler_params" in pk._vmem_params(4 * QT * QD * 4)


def test_gated_attention_step_holds_no_score_matrix(chip_kernels):
    """Forward and backward of the attention op as the model calls it
    (16 query heads on 2 KV heads, causal): three kernels, and no array of
    16 x 4096 x 4096 anywhere in the compiled text."""
    from mxnet_tpu.ops.registry import get_op

    attn = get_op("multihead_attention")._make_fn(
        num_heads=QH, num_kv_heads=2, causal=True, scale=QD ** -0.5)
    q = jax.ShapeDtypeStruct((1, QT, QH * QD), jnp.float32,
                             sharding=chip_kernels)
    kv = jax.ShapeDtypeStruct((1, QT, 2 * QD), jnp.float32,
                              sharding=chip_kernels)
    text = jax.jit(jax.value_and_grad(
        lambda q, k, v: attn(q, k, v).sum(), argnums=(0, 1, 2))).lower(
            q, kv, kv).compile().as_text()
    import re

    assert text.count("tpu_custom_call") >= 3
    # (1, 4096, 16 x 256) is the layer's own input; scores would be
    # heads x positions x positions
    assert not re.search(r"\[(1,)?(16|2),4096,4096\]", text)


def test_chunked_delta_rule_compiles_at_the_cells_shapes(chip_kernels):
    """16 key heads and 32 value heads of 128 over 4096 positions in chunks
    of 64, forward and backward (XLA: no kernel), within the chip's memory."""
    from mxnet_tpu.ops.registry import get_op

    rule = get_op("gated_delta_rule")._make_fn(chunk=64)
    sds = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=chip_kernels)
    qk, v, gb = sds(1, QT, 16, 128), sds(1, QT, 32, 128), sds(1, QT, 32)
    compiled = jax.jit(jax.value_and_grad(
        lambda *a: rule(*a).sum(), argnums=(0, 1, 2, 3, 4))).lower(
            qk, qk, v, gb, gb).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 3 * 2**30


def _routed_args(chip, rows, units, held, width, dtype):
    """Operands of ``routed_experts``: ``rows`` tokens of ``units``, top-10,
    ``held`` experts of ``width``."""
    sds = lambda shape, dt=dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    return (sds((rows, units)), sds((rows, 10), jnp.float32),
            sds((rows, 10), jnp.int32), sds((held, units, 2 * width)),
            sds((held, width, units)))


def _experts_kernels(text):
    import re

    # under value_and_grad the instruction is ``jvp_mxtpu_experts_swiglu_``
    return len(re.findall(r"%(jvp_)?mxtpu_experts_swiglu[_.\d]* = ", text))


def _loops(text):
    return [line for line in text.splitlines() if " while(" in line]


def test_routed_experts_compile_at_the_cells_shapes(chip_kernels):
    """4096 tokens, top-10 of 512, experts 0-31 of width 512 held, float32,
    forward and backward: the dropless grouped products as loops over the
    tiles in use (a ``while`` each), and no buffer of tokens x top-k rows.
    The forward kernel does not take this shape (4096 float32 sums of 2048
    beside float32 experts are more than it may keep in fast memory, and
    the layout's 45056 slots cost XLA's gather more than the experts'
    bytes: PERF.md section 6, PR 35), so the forward is the loop too."""
    from mxnet_tpu.ops.registry import get_op

    op = get_op("routed_experts")._make_fn(experts_held=(0, 32))
    text = jax.jit(jax.value_and_grad(
        lambda x, w, i, gu, dn: op(x, w, i, gu, dn).sum(),
        argnums=(0, 1, 3, 4))).lower(*_routed_args(
            chip_kernels, QT, 2048, 32, 512, jnp.float32)).compile().as_text()
    assert _experts_kernels(text) == 0
    assert len(_loops(text)) == 4
    assert sum("transpose(" in w for w in _loops(text)) == 2   # backward
    assert "f32[40960,2048]" not in text and "f32[45056,2048]" not in text


def test_experts_kernel_compiles_in_float32_under_value_and_grad(
        chip_kernels):
    """Qwen3-Next's widths (hidden 2048, 32 experts of 512, float32 rows and
    weights, rounded at the matrix unit inside the kernel) at the 1024 rows
    the kernel does take: one kernel in the forward, the backward's two
    loops (experts, and tiles inside) as they were, and no loop forward."""
    from mxnet_tpu.ops.registry import get_op

    op = get_op("routed_experts")._make_fn(experts_held=(0, 32))
    text = jax.jit(jax.value_and_grad(
        lambda x, w, i, gu, dn: op(x, w, i, gu, dn).sum(),
        argnums=(0, 1, 3, 4))).lower(*_routed_args(
            chip_kernels, 1024, 2048, 32, 512, jnp.float32)) \
        .compile().as_text()
    assert _experts_kernels(text) == 1
    assert len(_loops(text)) == 2
    assert all("transpose(" in w for w in _loops(text))


@pytest.mark.parametrize("rows", [32, 1024], ids=["tick", "prefill_1024"])
def test_experts_kernel_compiles_at_the_granite_cells_shapes(chip_kernels,
                                                             rows):
    """32 slots' rows (tiles of 32) and a 1024-token prefill's (tiles of 128)
    on 36 experts of 768 at hidden 4096 in bfloat16: one kernel with an
    expert's two matrices as its blocks (18.9 MB, double-buffered: a limit
    above Mosaic's default), no loop, no copy of any matrix, and no buffer
    of the layout's results (the tokens' sums stay in the kernel)."""
    import re

    from mxnet_tpu.ops.registry import get_op

    op = get_op("routed_experts")._make_fn(experts_held=(0, 36))
    text = jax.jit(op).lower(*_routed_args(
        chip_kernels, rows, 4096, 36, 768, jnp.bfloat16)).compile().as_text()
    assert _experts_kernels(text) == 1
    assert " while(" not in text
    assert not re.search(r"bf16\[(\d+,)?4096,1536\]\S* (copy|fusion)\(",
                         text)
    assert not re.search(r"f32\[\d{4,},4096\]", text.replace(
        f"f32[{rows},4096]", ""))
    assert pk._vmem_params(pk._experts_resident(
        rows, min(rows, 128), 4096, 768, jnp.bfloat16, jnp.bfloat16))


# -- the Granite-4.0-H serving cell's shapes: bfloat16, 32 slots -------------
@pytest.fixture(scope="module")
def granite_programs():
    """The serving programs of a Mamba-2 layer and an attention layer at the
    cell's widths (hidden 4096, 128 state heads of 64 x 128, 32 query / 8 KV
    heads of 128, 72-way top-10 router, experts of 768, shared 1536) in
    bfloat16, 32 slots x 2048, one 1024-token prefill bucket. Depth, the
    experts held (8 of 36) and the vocabulary (8192 rows) are what a CPU
    can trace in half a minute; no width is. Traced with the kernels
    steered off, compiled with them on (``serve_programs`` says why)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import model_zoo
    from mxnet_tpu.serve.decode import DecodePrograms

    mp = pytest.MonkeyPatch()
    mp.setattr(pk, "_use_pallas", lambda: False)
    try:
        mx.random.seed(3)
        net = model_zoo.granite_hybrid(
            layer_types=["mamba", "attention"], vocab_size=8192,
            dtype="bfloat16", experts_held=(0, 8))
        for p in net.collect_params().values():
            p.grad_req = "null"
        net.initialize()
        return DecodePrograms(net, num_slots=32, max_len=2048,
                              prefill_batch=1, max_prompt_len=1024,
                              min_prompt_bucket=1024, page_tokens=128,
                              speculate_k=1, prefix_cache=False)
    finally:
        mp.undo()
        jax.clear_caches()


@pytest.mark.parametrize("family", ["decode", "prefill"])
def test_granite_serving_programs_compile_at_the_cells_widths(
        chip_kernels, granite_programs, family):
    """The tick at 32 slots and the 1024-token prefill compile for the
    described v5e. The pools, the conv tails and the SSM states are updated
    in place (everything donated comes back aliased: the tick's temporaries
    are a few MB beside 135 MB of state a layer); the tick holds the paged
    kernel at 32 query heads on 8 KV heads of 128 over a bfloat16 pool, the
    prefill the flash kernel; neither holds a float32 copy of an expert
    matrix (the grouped products take the bfloat16 weights as they lie).
    The routed layers' products are one ``mxtpu_experts_swiglu`` kernel a
    layer, named under the scope ``experts``: no loop is left in that scope
    and nothing slices an expert's matrix out of the stacked arrays."""
    import re

    progs = granite_programs
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        tuple(shape), dt, sharding=chip_kernels)
    S, Wt = progs.num_slots, progs.table_width
    assert progs.cache_shape == (S * 16, 1, 8, 128, 128)
    assert progs.cache_dtype == "bfloat16"
    assert progs.state_shapes == [((32, 3, 8448), "bfloat16"),
                                  ((32, 128, 64, 128), "float32")]
    held = [sds(progs.cache_shape, jnp.bfloat16)] * 2 \
        + [sds(shape, dt) for shape, dt in progs.state_shapes] \
        + [sds((len(progs.counter_names),), jnp.int32)]
    if family == "decode":
        gkey = "decode:1"
        data = [sds((S, 1), jnp.int32), sds((S,), jnp.int32),
                sds((S, Wt), jnp.int32)]
    else:
        gkey = "prefill:1024"
        data = [sds((1, 1024), jnp.int32), sds((1,), jnp.int32),
                sds((1,), jnp.int32), sds((1, Wt), jnp.int32)]
    args = data + held + [
        sds(progs._params[n].shape, progs._params[n].dtype)
        for n in progs._graph_params[gkey]]
    compiled = progs._cops[gkey].lower(
        *args, donate=progs._donate(family)).compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    donated = sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
                  for a in held[:-1])
    assert ma.alias_size_in_bytes >= donated
    assert not re.search(r"f32\[(\d+,)?4096,1536\]|f32\[(\d+,)?768,4096\]",
                         text)
    assert not re.search(r"dynamic[-_]slice[.\d]* = "
                         r"bf16\[(1,)?(4096,1536|768,4096)\]", text)
    kernels = [line for line in text.splitlines()
               if re.search(r"%mxtpu_experts_swiglu[.\d]* = ", line)]
    assert len(kernels) == 2
    assert all(re.search(r'op_name="[^"]*/experts/[^"]*"', k)
               for k in kernels)
    assert not [line for line in text.splitlines() if " while(" in line
                and re.search(r'op_name="[^"]*/experts/', line)]
    if family == "decode":
        assert len(re.findall(r"%mxtpu_paged_decode[.\d]* = ", text)) == 1
        assert ma.temp_size_in_bytes < 64 * 2**20
    else:
        assert text.count("tpu_custom_call") >= 1
        assert ma.temp_size_in_bytes < 2**30


@pytest.mark.parametrize("stores", [False, True], ids=["reads", "stores"])
def test_paged_decode_compiles_at_32_query_on_8_kv_heads_bfloat16(
        chip_kernels, stores):
    """``mxtpu_paged_decode`` with ``group`` 4 and heads of 128 on a
    bfloat16 pool of 512 pages: one kernel, no float32 copy of the pool.
    Handed the slots' new rows it takes the donated pools as its outputs:
    no copy of a pool, no temporary."""
    import re

    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip_kernels)
    pool = sds((512, 1, 8, 128, 128), jnp.bfloat16)
    rows = [sds((32, 1, 8, 128), jnp.bfloat16)] * 2 if stores else []
    compiled = jax.jit(
        lambda q, k, v, lay, tab, pos, *rows: pk._paged_decode_tpu(
            q, k, v, lay, tab, pos, 0.0078125, *rows),
        donate_argnums=(1, 2) if stores else ()).lower(
            sds((32, 1, 32, 128), jnp.bfloat16), pool, pool,
            sds((), jnp.int32), sds((32, 17), jnp.int32),
            sds((32,), jnp.int32), *rows).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "f32[512,1,8,128,128]" not in text
    if stores:
        ma = compiled.memory_analysis()
        assert ma.alias_size_in_bytes == 2 * 512 * 8 * 128 * 128 * 2
        assert ma.temp_size_in_bytes < 2**20
        assert not re.search(r"= bf16\[512,\S* (copy|fusion)\(", text)


# -- the A.X-K1 serving cell's shapes: bfloat16, 64 slots ---------------------
def test_mla_decode_compiles_at_64_heads_on_one_latent_row(chip_kernels):
    """``mxtpu_mla_decode`` at the cell's shapes: 64 slots, 64 heads of 576
    against ONE pool of 576-wide rows (1280 pages of 128, six layers),
    values the first 512 columns: one kernel, the pool taken as it lies (no
    copy, no float32 widening, no gathered view)."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip_kernels)
    text = jax.jit(lambda q, pool, lay, tab, pos: pk.mla_decode_attention(
        q, pool, lay, tab, pos, 512, 0.130861)).lower(
            sds((64, 1, 64, 576), jnp.bfloat16),
            sds((1280, 6, 1, 576, 128), jnp.bfloat16), sds((), jnp.int32),
            sds((64, 21), jnp.int32), sds((64,), jnp.int32)) \
        .compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "%mxtpu_mla_decode" in text
    assert "[1280,6,1,576,128]{" in text
    assert "f32[1280,6,1,576,128]" not in text and " gather(" not in text


@pytest.mark.parametrize("rows", [64, 2048], ids=["tick", "prefill_2048"])
def test_blocked_experts_kernel_compiles_at_the_axk1_cells_shapes(
        chip_kernels, rows):
    """64 slots' rows (tiles of 64) and a 2048-token prefill's (tiles of
    128) on 12 experts of 2048 at hidden 7168 in bfloat16, 88 MB an expert:
    ``experts_kernel_blocks`` says blocks of 512, ONE kernel takes each
    expert in four of them (22 MB a step, double-buffered: a limit above
    Mosaic's default), no loop and no copy of any expert's matrix."""
    import re

    from mxnet_tpu.ops.registry import get_op

    tm = min(rows, 128)
    assert not pk.experts_kernel_serves(rows, tm, 7168, 2048, jnp.bfloat16,
                                        jnp.bfloat16)
    assert pk.experts_kernel_blocks(rows, tm, 7168, 2048, jnp.bfloat16,
                                    jnp.bfloat16) == 512
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip_kernels)
    op = get_op("routed_experts")._make_fn(experts_held=(0, 12))
    compiled = jax.jit(op).lower(
        sds((rows, 7168)), sds((rows, 8), jnp.float32),
        sds((rows, 8), jnp.int32), sds((12, 7168, 4096)),
        sds((12, 2048, 7168))).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%mxtpu_experts_swiglu_blocked[_.\d]* = ",
                          text)) == 1
    assert " while(" not in text
    assert not re.search(r"bf16\[(\d+,)?7168,4096\]\S* (copy|fusion)\(",
                         text)
    assert not re.search(r"bf16\[(\d+,)?2048,7168\]\S* (copy|fusion)\(",
                         text)
    assert pk._vmem_params(pk._experts_block_resident(
        tm, 7168, 512, jnp.bfloat16, jnp.bfloat16))
    # the layout's float32 results: tiles x tm x 7168, the static worst case
    tiles = -(-rows * 8 // tm) + 12
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2.2 * tiles * tm * 7168 * 4 + 2**26


@pytest.fixture(scope="module")
def axk1_programs():
    """The serving programs of A.X-K1's leading dense layer and one routed
    layer at the cell's widths (hidden 7168, 64 heads of 128 + 64 / 128,
    latents 1536 and 512, dense MLP 18432, a 192-way sigmoid top-8 router,
    experts of 2048) in bfloat16, 64 slots x 2560, one 256-token prefill
    bucket. Depth, the experts held (2 of 12) and the vocabulary (2048 rows)
    are what a CPU can trace in a minute; no width is. Traced with the
    kernels steered off, compiled with them on (``serve_programs`` says
    why)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import model_zoo
    from mxnet_tpu.serve.decode import DecodePrograms

    mp = pytest.MonkeyPatch()
    mp.setattr(pk, "_use_pallas", lambda: False)
    try:
        mx.random.seed(3)
        net = model_zoo.axk1(num_hidden_layers=2, vocab_size=2048,
                             dtype="bfloat16", experts_held=(0, 2))
        for p in net.collect_params().values():
            p.grad_req = "null"
        net.initialize()
        return DecodePrograms(net, num_slots=64, max_len=2560,
                              prefill_batch=1, max_prompt_len=256,
                              min_prompt_bucket=256, page_tokens=128,
                              speculate_k=1, prefix_cache=False)
    finally:
        mp.undo()
        jax.clear_caches()


@pytest.mark.parametrize("family", ["decode", "prefill"])
def test_axk1_serving_programs_compile_at_the_cells_widths(
        chip_kernels, axk1_programs, family):
    """The tick at 64 slots and a 256-token prefill compile for the
    described v5e over ONE latent pool (no V pool among the operands),
    updated in place (what is donated comes back aliased). The tick holds
    the absorbed decode kernel a layer under the scope ``attn`` and no
    gathered view of the pool; the prefill holds the flash kernel (expanded
    attention, ``v`` padded to the key's 192) and writes each layer's rows
    as whole pages; the routed layer's products are one blocked kernel under
    the scope ``experts``, with no loop and no copy of an expert."""
    import re

    progs = axk1_programs
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        tuple(shape), dt, sharding=chip_kernels)
    S, Wt = progs.num_slots, progs.table_width
    assert progs.cache_shape == (S * 20, 2, 1, 576, 128)
    assert progs.cache_dtype == "bfloat16" and progs.pools == 1
    assert progs.state_shapes == []
    held = [sds(progs.cache_shape, jnp.bfloat16),
            sds((len(progs.counter_names),), jnp.int32)]
    if family == "decode":
        gkey = "decode:1"
        data = [sds((S, 1), jnp.int32), sds((S,), jnp.int32),
                sds((S, Wt), jnp.int32)]
        assert progs._donate(family) == (3,)
    else:
        gkey = "prefill:256"
        data = [sds((1, 256), jnp.int32), sds((1,), jnp.int32),
                sds((1, Wt), jnp.int32)]
        assert progs._donate(family) == (3,)
    args = data + held + [
        sds(progs._params[n].shape, progs._params[n].dtype)
        for n in progs._graph_params[gkey]]
    compiled = progs._cops[gkey].lower(
        *args, donate=progs._donate(family)).compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    pool_bytes = math.prod(progs.cache_shape) * 2
    assert ma.alias_size_in_bytes >= pool_bytes
    assert ma.temp_size_in_bytes < pool_bytes          # no second pool
    assert "f32[1280,2,1,576,128]" not in text
    blocked = [line for line in text.splitlines() if re.search(
        r"%mxtpu_experts_swiglu_blocked[.\d]* = ", line)]
    assert len(blocked) == 1
    assert re.search(r'op_name="[^"]*/experts/[^"]*"', blocked[0])
    assert not [line for line in text.splitlines() if " while(" in line]
    assert not re.search(r"bf16\[(1,)?(7168,4096|2048,7168)\]\S* "
                         r"(copy|dynamic-slice)\(", text)
    decode = [line for line in text.splitlines() if re.search(
        r"%mxtpu_mla_decode[.\d]* = ", line)]
    if family == "decode":
        assert len(decode) == 2
        assert all(re.search(r'op_name="[^"]*/attn/[^"]*"', k)
                   for k in decode)
        assert "mxtpu_flash_fwd" not in text
        # no gathered view: nothing of slots x pages x a page's rows
        assert not re.search(r"bf16\[64,20,576,128\]|bf16\[1280,576,128\]",
                             text)
    else:
        assert not decode
        assert len(re.findall(r"%mxtpu_flash_fwd[.\d]* = ", text)) == 2
