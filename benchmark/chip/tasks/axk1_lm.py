"""Serving the zoo's A.X-K1 as one chip's share of an expert-parallel
deployment: the configuration says which layers, which experts and which rows
of the vocabulary are held, the traffic draws its ids from those rows, and
logits are over them.

A task says how one family of configurations is built and compared with the
plain reference; runners know it only through these functions.
"""
import contextlib

from chipbench import axk1_cost, reference_axk1
from chipbench import traffic as gen

# the configuration file's keys the model is built from, as published
MODEL_KEYS = (
    "hidden_size", "intermediate_size", "first_k_dense_replace",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
    "routed_scaling_factor", "norm_topk_prob", "scoring_func",
    "rms_norm_eps", "rope_theta", "rope_scaling", "max_position_embeddings")

FAULTS = ("other_slots_rows", "rope_position_zero", "values_all_576_columns",
          "scaling_dropped", "mscale_dropped")


def model_config(config):
    """The model's arguments from the configuration file:
    ``num_hidden_layers`` is what ``layers_held`` says, ``n_routed_experts``
    in the file counts the experts HELD and the router keeps the published
    width (``router_num_experts``)."""
    cfg = {k: config[k] for k in MODEL_KEYS}
    lo, hi = config["layers_held"]
    if lo != 0:
        raise ValueError("axk1_lm: the layers held start at layer 0 (the "
                         "leading dense layer)")
    cfg.update(num_hidden_layers=hi - lo, vocab_size=config["vocab_size"],
               n_routed_experts=config["router_num_experts"])
    return cfg


def build_net(config, seed):
    """The zoo model at the sizes the configuration states, in its dtype,
    with weights from ``seed`` drawn on the device in that dtype; no
    gradient buffers (this task serves); checks what was built against the
    file."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import model_zoo

    mx.random.seed(gen.seed31(seed, 0))
    cfg = model_config(config)
    net = model_zoo.axk1(cfg, dtype=config["dtype"],
                         experts_held=config["experts_held"])
    params = net.collect_params()
    for p in params.values():
        p.grad_req = "null"
    net.initialize()
    lo, hi = config["experts_held"]
    routed = f"layers.{cfg['first_k_dense_replace']}."
    spec = net.cache_spec()
    built = {
        "num_hidden_layers": len(net.layers),
        "vocab_size": params["embed.weight"].shape[0],
        "head_rows": params["head.weight"].shape[0],
        "hidden_size": params["embed.weight"].shape[1],
        "dense_width": params["layers.0.mlp.down.weight"].shape[1],
        "n_routed_experts": params[routed + "moe.gate_up"].shape[0],
        "router_num_experts": params[routed + "moe.router.weight"].shape[0],
        "moe_intermediate_size": params[routed + "moe.down"].shape[1],
        "latent_row": list(spec["latent"]),
        "dtype": str(params["embed.weight"].data().dtype),
    }
    stated = {
        "num_hidden_layers": config["num_hidden_layers"],
        "vocab_size": config["n_vocab"],
        "head_rows": config["n_vocab"],
        "hidden_size": config["hidden_size"],
        "dense_width": config["intermediate_size"],
        "n_routed_experts": hi - lo,
        "router_num_experts": config["router_num_experts"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "latent_row": [config["kv_lora_rank"] + config["qk_rope_head_dim"],
                       config["kv_lora_rank"]],
        "dtype": config["dtype"],
    }
    if built != stated or config["n_routed_experts"] != hi - lo:
        raise ValueError(f"axk1 built {built}, the configuration states "
                         f"{stated} and n_routed_experts "
                         f"{config['n_routed_experts']}")
    # the readers of this configuration's own metrics get only the
    # runner's observations: what the cost model needs is noted here
    axk1_cost.note_run(cfg, config["experts_held"],
                       params["embed.weight"].data()._data.dtype.itemsize,
                       net)
    return net


def routed_layers(net):
    return [layer for layer in net.layers if not layer.dense]


@contextlib.contextmanager
def _noting_choices(net):
    """``calls``: a routed layer, the experts its router chose at every call
    made inside the block, in order (forward hooks, taken off at the end)."""
    calls, hooks = [[] for _ in routed_layers(net)], []
    for into, layer in zip(calls, routed_layers(net)):
        router = layer.moe.router
        hooks.append((router, router.register_forward_hook(
            lambda block, inputs, out, into=into: into.append(out[1]._data))))
    try:
        yield calls
    finally:
        for router, hook in hooks:
            router._forward_hooks.remove(hook)


def system_logits(net, x):
    """Inference-mode logits of the system's PLAIN forward (no cache) on
    one batch, and the experts each router chose, routed layer by layer."""
    import mxnet_tpu as mx

    with _noting_choices(net) as calls:
        logits = net(mx.np.array(x))._data
    return logits, [c[0] for c in calls]


def layer_weights(net, dtype="float32", round_to=None):
    """``i -> {name below layers.<i>.: array}``: the system's own values
    widened (or, for a control, narrowed) to ``dtype``, a layer at a time;
    the expert arrays and the dense layer's wide MLP as they are stored (the
    reference widens an expert, or a block of the width, at a time).
    ``round_to``: a control's weights, every matrix rounded to that type
    first."""
    import jax.numpy as jnp

    params = net.collect_params()
    as_stored = reference_axk1.EXPERT_ARRAYS + reference_axk1.BLOCKED

    def of(i):
        prefix = f"layers.{i}."
        out = {}
        for name, p in params.items():
            if not name.startswith(prefix):
                continue
            value, short = p.data()._data, name[len(prefix):]
            if round_to is not None and value.ndim >= 2:
                value = value.astype(round_to).astype(p.data()._data.dtype)
            out[short] = value if short in as_stored \
                else jnp.asarray(value, dtype)
        return out

    return of


def reference_forward(net, config, x, routing=None, dtype="float32",
                      round_to=None):
    """The reference on the net's own weights (``dtype`` and ``round_to``
    other than the defaults: a lower-precision control)."""
    params = net.collect_params()
    return reference_axk1.forward(
        params["embed.weight"].data()._data,
        params["head.weight"].data()._data,
        params["norm_f.weight"].data()._data,
        layer_weights(net, dtype, round_to), model_config(config), x,
        experts_held=tuple(config["experts_held"]), routing=routing,
        tie_ratio=config["routing_margin"], dtype=dtype)


def check_spans(config, x):
    """Which parts of a checked row go through the cache views: one
    ``(prefix, ticks)`` a slot, two slots. The row's real tokens end at its
    last non-zero id (the runner pads with zeros); the first slot's ticks
    end there, the second takes three sevenths of the first's prefix, so
    the two stand at different positions, read different rows, and neither
    prefix fills its bucket."""
    import numpy as onp

    check = config["views_check"]
    ticks = int(check["ticks"])
    real = int(onp.flatnonzero(onp.asarray(x)[0])[-1]) + 1
    first = max(min(real - ticks, int(check["max_prefix"])), 8)
    return [(first, ticks), (max(first * 3 // 7, 4), ticks)]


@contextlib.contextmanager
def _planted(net, fault):
    """A fault of the MODEL's arithmetic, for as long as the block lasts."""
    undo = []
    if fault == "scaling_dropped":
        for layer in routed_layers(net):
            router = layer.moe.router
            undo.append((router, "_scaling", router._scaling))
            router._scaling = 1.0
    elif fault == "mscale_dropped":
        cfg = net.config
        plain = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
        for layer in net.layers:
            undo.append((layer.attn, "scale", layer.attn.scale))
            layer.attn.scale = plain
    try:
        yield
    finally:
        for obj, name, value in undo:
            setattr(obj, name, value)


def _faulty_tick_view(kv, fault):
    """``TickView`` with a fault of the CACHE's side planted."""
    from mxnet_tpu import np, numpy_extension as npx

    class Faulty(kv.TickView):
        def positions(self, limit):
            pos = super().positions(limit)
            return pos * 0 if fault == "rope_position_zero" else pos

        def attend_latent(self, layer, row, q, k=None, v=None, scale=None,
                          heads=1):
            if fault != "values_all_576_columns":
                return super().attend_latent(layer, row, q, scale=scale,
                                             heads=heads)
            # the sums taken over the WHOLE row: the rotary columns' part
            # lands on the first value columns
            width, value = self.latent
            self.latent = (width, width)
            try:
                u = super().attend_latent(layer, row, q, scale=scale,
                                          heads=heads)
            finally:
                self.latent = (width, value)
            u = np.reshape(u, (self.S, self.K, -1, width))
            spill = np.pad(npx.slice_axis(u, axis=-1, begin=value, end=None),
                           ((0, 0),) * 3 + ((0, 2 * value - width),))
            return np.reshape(
                npx.slice_axis(u, axis=-1, begin=0, end=value) + spill,
                (self.S, self.K, -1))

    return Faulty


def views_forward(net, config, x, spans, fault=None):
    """What the serving programs compute, eagerly: for each ``(prefix,
    ticks)`` of ``spans`` one slot of a private cache; every slot's prefix
    goes through ``PrefillView`` alone, right-padded to its bucket as the
    engine pads it, then all slots advance together through ``ticks``
    ``TickView`` steps, fed the row's own next tokens. Returns ``{"logits":
    (slots, ticks + 1, V) at positions prefix - 1 .. prefix + ticks - 1,
    "rows": a layer, a slot: (prefix + ticks, R), the latent rows the cache
    is left holding, "chosen": a routed layer: (ticks, slots, k), the
    experts each TICK's router chose}``. ``fault``: one of ``FAULTS``,
    planted in the ticks."""
    import jax.numpy as jnp
    import numpy as onp
    from mxnet_tpu import np
    from mxnet_tpu.serve.decode import cache as kv

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r} (known: {FAULTS})")
    check = config["views_check"]
    P = int(check["page_tokens"])
    row = onp.asarray(x)[0]
    ticks = spans[0][1]
    S = len(spans)
    W = max(-(-(p + k) // P) for p, k in spans)
    spec = net.cache_spec()
    layout = kv.view_layout(spec)
    operands = kv.empty_pools(spec, S * W, P) \
        + tuple(kv.empty_state(spec, S))
    table = onp.full((S, W + 1), S * W, "int32")      # sentinel: unmapped
    table[:, :W] = onp.arange(S * W).reshape(S, W)
    logits = [[] for _ in spans]
    # every router call in order: a prefill a slot, then the ticks
    with _noting_choices(net) as calls:
        for s, (p, _) in enumerate(spans):
            bucket = max(int(check["min_bucket"]), 1 << (p - 1).bit_length())
            tokens = onp.zeros((1, bucket), "int32")
            tokens[0, :p] = row[:p]
            tokens = np.array(tokens)
            view = kv.PrefillView(
                tokens, np.array(onp.asarray([p], "int32")),
                np.array(table[s:s + 1]), *operands,
                slots=np.array(onp.asarray([s], "int32")), **layout)
            logits[s].append(net(tokens, cache=view)[0, p - 1]._data)
            operands = view.state()
        tick_view = kv.TickView if fault is None \
            else _faulty_tick_view(kv, fault)
        # one slot attending (and writing) the other's pages
        tick_table = table[::-1].copy() if fault == "other_slots_rows" \
            else table
        with _planted(net, fault):
            for k in range(ticks):
                at = onp.asarray([p + k for p, _ in spans], "int32")
                last = np.array(row[at].reshape(S, 1))
                view = tick_view(last, np.array(at), np.array(tick_table),
                                 *operands, **layout)
                out = net(last, cache=view)
                for s in range(S):
                    logits[s].append(out[s, 0]._data)
                operands = view.state()
    pool = operands[0]._data
    rows = []
    for layer in range(spec["layers"]):
        held = pool[table[:, :W].reshape(-1), layer, 0]     # (S*W, R, P)
        held = jnp.swapaxes(held.reshape(S, W, -1, P), 2, 3) \
            .reshape(S, W * P, -1)
        rows.append([held[s, :p + k] for s, (p, k) in enumerate(spans)])
    return {"logits": jnp.stack([jnp.stack(l) for l in logits]),
            "rows": rows,
            "chosen": [jnp.stack(c[S:]) for c in calls],
            "chosen_last": [jnp.stack([c[s][p - 1]
                                       for s, (p, _) in enumerate(spans)])
                            for c in calls]}


def routing_with_ticks(chosen, views, spans):
    """The plain forward's choice of experts, a routed layer (T, k), with
    the views' OWN choice put in at the positions whose logits they are
    held at (slot s: its prefill's last position prefix_s - 1 and its ticks'
    prefix_s + j; no two of them share a position). A top-k choice is discontinuous and the tick's router sees
    its token through other arithmetic than the plain forward's (absorbed
    attention, another batch): at a near-tie the two may choose otherwise,
    one flipped expert of the twelve held moves that position's logits by
    half a spread, and that is no fault. The reference takes a given choice
    only where its own is a near-tie (``routing_margin``)."""
    import jax.numpy as jnp

    merged = []
    for plain, ticks, last in zip(chosen, views["chosen"],
                                  views["chosen_last"]):
        plain = jnp.asarray(plain)
        for s, (p, k) in enumerate(spans):
            plain = plain.at[p - 1:p + k].set(
                jnp.concatenate([last[s:s + 1], ticks[:k, s]]))
        merged.append(plain)
    return merged


def tick_positions(spans):
    """The positions whose logits the views are held at."""
    return [p + j for p, k in spans for j in range(-1, k)]


def views_of(out, spans):
    """A full forward's result (``reference_forward``) cut to what
    ``views_forward`` returns."""
    import jax.numpy as jnp

    return {"logits": jnp.stack([out["logits"][0, p - 1:p + k]
                                 for p, k in spans]),
            "rows": [[r[0, :p + k] for p, k in spans] for r in out["rows"]]}


def _relative(got, want):
    """Root of the summed squares of ``got - want`` over that of ``want``,
    lists of arrays taken together, in float32."""
    import jax.numpy as jnp

    pairs = [(jnp.asarray(g, jnp.float32), jnp.asarray(w, jnp.float32))
             for g, w in zip(got, want)]
    return float(jnp.sqrt(sum(jnp.sum((g - w) ** 2) for g, w in pairs)
                          / sum(jnp.sum(w * w) for _, w in pairs)))


def compare(config, system, out, views=None, spans=None, say=print):
    """Holds ``system`` (plain-forward logits) and ``views`` (what
    ``views_forward`` returned for ``spans``) to the reference's result
    ``out`` by the limits of the configuration file: the routing margin,
    the largest and the rms error of the logits (one limit each for both
    paths; where ``views`` are compared the reference was routed by THEIR
    choice at the ticks' positions, ``routing_with_ticks``, so the plain
    forward is held at every position but those), and the relative error of
    the latent rows the cache holds at the spans' ends, all of them and
    those the ticks wrote. Returns ``(ok, numbers)``."""
    import jax.numpy as jnp

    ref = out["logits"].astype(jnp.float32)
    std = jnp.std(ref)

    def errors(got, want):
        diff = jnp.asarray(got, jnp.float32) - want
        return (float(jnp.max(jnp.abs(diff)) / std),
                float(jnp.sqrt(jnp.mean(diff * diff)) / std))

    numbers = {"routing_margin": float(out["routing_margin"])}
    limits = {"routing_margin": config["routing_margin"]}
    if system is not None:
        system = jnp.asarray(system, jnp.float32)
        if views is not None:
            import numpy as onp

            keep = onp.ones(ref.shape[1], bool)
            keep[tick_positions(spans)] = False
            system, ref_plain = system[:, keep], ref[:, keep]
        else:
            ref_plain = ref
        numbers["logits_error"], numbers["logits_rms_error"] = \
            errors(system, ref_plain)
        limits.update(logits_error=config["logits_tolerance"],
                      logits_rms_error=config["logits_rms_tolerance"])
    if views is not None:
        want = views_of(out, spans)
        numbers["views_logits_error"], numbers["views_logits_rms_error"] = \
            errors(views["logits"], want["logits"].astype(jnp.float32))
        numbers["rows_error"] = _relative(
            [r for layer in views["rows"] for r in layer],
            [r for layer in want["rows"] for r in layer])
        numbers["tick_rows_error"] = _relative(
            [r[p:] for layer in views["rows"]
             for r, (p, _) in zip(layer, spans)],
            [r[p:] for layer in want["rows"]
             for r, (p, _) in zip(layer, spans)])
        limits.update(views_logits_error=config["logits_tolerance"],
                      views_logits_rms_error=config["logits_rms_tolerance"],
                      rows_error=config["rows_tolerance"],
                      tick_rows_error=config["rows_tolerance"])
    ok = all(numbers[k] >= limits[k] if k == "routing_margin"
             else numbers[k] <= limits[k] for k in numbers)
    say("reference: the system's plain forward"
        + ("" if views is None else f" and its cache views over {spans}")
        + " against the reference, logits in units of the reference "
        "logits' std, latent rows relative: "
        + ", ".join(f"{k} {numbers[k]:.5f} (limit {limits[k]})"
                    for k in numbers) + ("" if ok else " -- FAILED"))
    return ok, numbers


def reference_logits(net, config, x):
    """(B, T, V_held) logits of the plain reference on the net's own
    weights, for one batch of token ids.

    Besides what the runner does with them (the engine's tokens against the
    reference's maximum), the system's own forward runs on the row here,
    twice, and is held to the reference by the limits of the configuration
    file (``compare``): PLAIN over the whole row, and THROUGH THE CACHE
    VIEWS as the serving programs run it (``views_forward``: two slots at
    different positions, a padded prefill each, expanded; then ticks,
    absorbed), logits at every position the views computed and the latent
    rows they are left holding. A top-k choice is discontinuous, so the
    reference chooses its experts itself except for the tokens whose
    (k+1)-th score BY THE REFERENCE reaches ``routing_margin`` of its k-th:
    there it takes the system's choice (the plain forward's; at the
    positions the views' ticks computed, theirs: ``routing_with_ticks``),
    and each expert so taken must itself reach that share of the k-th.

    Where a limit fails the run must fail. The runner takes the ``max`` of
    the gaps it computes from what is returned here, and a NaN gap is
    DROPPED by ``max``: so the answer on failure is finite and wrong, the
    reference's logits rolled by one along the vocabulary, under which a
    chosen token reads whole spreads below the maximum."""
    import jax.numpy as jnp

    spans = check_spans(config, x)
    system, chosen = system_logits(net, x)
    views = views_forward(net, config, x, spans)
    out = reference_forward(
        net, config, x, routing=routing_with_ticks(chosen, views, spans))
    ok, _ = compare(config, system, out, views, spans)
    ref = out["logits"]
    return ref if ok else jnp.roll(ref, 1, axis=-1)
