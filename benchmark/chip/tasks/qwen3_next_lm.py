"""Next-token language modelling on the zoo's Qwen3-Next, as one chip's share
of an expert-parallel job: the configuration says which experts and which
rows of the vocabulary are held, the traffic draws its ids from those rows,
and logits and loss are over them.

A task says how one family of configurations is built, fed and compared
with the plain reference; runners know it only through these functions.
"""
import numpy as onp

from chipbench import qwen3_next_cost, reference, reference_qwen3_next
from chipbench import traffic as gen

# the configuration file's keys the model is built from, as published
MODEL_KEYS = (
    "hidden_size", "full_attention_interval", "num_attention_heads",
    "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts_per_tok",
    "norm_topk_prob", "moe_intermediate_size",
    "shared_expert_intermediate_size", "rms_norm_eps")


def model_config(config):
    """The model's arguments from the configuration file: ``num_experts`` in
    the file counts the experts HELD; the router keeps the published width
    (``router_num_experts``)."""
    cfg = {k: config[k] for k in MODEL_KEYS}
    cfg.update(num_hidden_layers=config["num_hidden_layers"],
               vocab_size=config["vocab_size"],
               num_experts=config["router_num_experts"])
    return cfg


def build_net(config, seed):
    """The zoo model at the sizes the configuration states, with weights
    from ``seed``; checks what was built against the file."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import model_zoo

    mx.random.seed(gen.seed31(seed, 0))
    net = model_zoo.qwen3_next(model_config(config),
                               experts_held=config["experts_held"])
    net.initialize()
    # This chip does not update the router. It is replicated over the chips
    # that share a layer and its gradient is summed over them before the
    # optimizer's step; one chip alone has its own experts' part of that
    # gradient only, and Adam applies it at its full rate: the routing
    # drifts to the experts held (measured: 10 thousand pairs here at step
    # 0, 65 thousand at step 140, the step 30% slower, the rate 2% apart
    # from seed to seed). Nothing here stands in for the other chips'
    # parts; gradients still flow THROUGH the routing weights into the
    # layers below. The model itself trains its router by default.
    for layer in net.layers:
        layer.moe.router.weight.grad_req = "null"
    params = net.collect_params()
    lo, hi = config["experts_held"]
    first = "layers.0."
    built = {
        "num_hidden_layers": len(net.layers),
        "vocab_size": params["embed.weight"].shape[0],
        "head_rows": params["lm_head.weight"].shape[0],
        "hidden_size": params["embed.weight"].shape[1],
        "num_experts": params[first + "moe.gate_up"].shape[0],
        "router_num_experts": params[first + "moe.router.weight"].shape[0],
        "moe_intermediate_size": params[first + "moe.down"].shape[1],
        "gdn_layers": sum(not layer.full_attention for layer in net.layers),
    }
    interval = config["full_attention_interval"]
    stated = {
        "num_hidden_layers": config["num_hidden_layers"],
        "vocab_size": config["vocab_size"],
        "head_rows": config["vocab_size"],
        "hidden_size": config["hidden_size"],
        "num_experts": hi - lo,
        "router_num_experts": config["router_num_experts"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "gdn_layers": config["num_hidden_layers"]
        - config["num_hidden_layers"] // interval,
    }
    if built != stated or config["num_experts"] != hi - lo:
        raise ValueError(f"qwen3_next built {built}, the configuration "
                         f"states {stated} and num_experts "
                         f"{config['num_experts']}")
    return net


# what the system's last ``system_logits`` gave: the experts each router
# chose, layer by layer, and the logits
_SYSTEM = {}


def reference_logits(net, config, x):
    """(B, T, V_held) logits of the plain reference on the net's own
    weights, for one batch of token ids; NaN where one of the two limits
    the runner does not know fails.

    A top-k choice is discontinuous: where a token's k-th and next
    probabilities nearly tie, rounding flips the choice, and one flipped
    expert moves that token's logits by more than all other rounding
    together (measured: weights rounded to bf16 alone, in exact float32,
    move the logits by 2.5 of their spread at worst with the routed experts
    and 0.2 without). So the reference chooses for itself, except for the
    tokens whose (k+1)-th probability BY THE REFERENCE reaches
    ``routing_margin`` of its k-th: there it takes the experts the SYSTEM
    chose (``system_logits`` notes them through a forward hook on each
    router), and each of them must reach ``routing_margin`` of the
    reference's own k-th largest. Everything else is the reference's own,
    the routing weights included. Three limits decide ``correct``: the
    runner's ``logits_tolerance`` on the largest error, and here
    ``routing_margin`` and ``logits_rms_tolerance`` on the root mean square
    error, which one unlucky logit in 78 million does not move."""
    system = _SYSTEM.pop(id(net), {})     # 311 MB of logits: not kept
    out = reference_qwen3_next.forward(
        reference.system_weights(net), model_config(config), x,
        experts_held=tuple(config["experts_held"]),
        routing=system.get("chosen"), tie_ratio=config["routing_margin"])
    return held_to(config, out, system.get("logits"))


def held_to(config, out, system):
    """The reference's logits, or NaN where the routing margin or the rms
    error of the ``system`` logits is past its limit; prints both."""
    import jax.numpy as jnp

    ref = out["logits"]
    margin, floor = float(out["routing_margin"]), config["routing_margin"]
    print(f"reference: the system's chosen experts reach {margin:.4f} of the "
          f"reference's k-th largest probability at worst (limit {floor})",
          flush=True)
    ok = margin >= floor
    if system is not None:
        diff = jnp.asarray(system, jnp.float32) - ref
        rms = float(jnp.sqrt(jnp.mean(diff * diff)) / jnp.std(ref))
        limit = config["logits_rms_tolerance"]
        print(f"reference: rms (system - reference) logit = {rms:.5f} of the "
              f"reference's std (tolerance {limit})", flush=True)
        ok = ok and rms <= limit
    return ref if ok else ref * float("nan")


def system_logits(net, x):
    """Inference-mode logits of the system on one batch; notes them and the
    experts each router chose."""
    import mxnet_tpu as mx

    chosen, hooks = [], []
    for layer in net.layers:
        router = layer.moe.router
        hooks.append((router, router.register_forward_hook(
            lambda block, inputs, out: chosen.append(out[1]._data))))
    try:
        logits = net(mx.np.array(x))._data
    finally:
        for router, hook in hooks:
            router._forward_hooks.remove(hook)
    _SYSTEM.clear()
    _SYSTEM[id(net)] = {"chosen": chosen, "logits": logits}
    return logits


class Train:
    def __init__(self, config, mix, seed):
        from mxnet_tpu import gluon

        self.config, self.mix, self.seed = config, mix, seed
        self.net = build_net(config, seed)
        self.model = self.net
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        self.rows, self.length = int(mix["rows"]), int(mix["length"])
        self.tokens_per_step = self.rows * self.length
        # the readers of this configuration's own metrics get only the
        # runner's observations: what the cost model needs is noted here
        # (and keeps the net alive for them: the runner lets go of it before
        # the readers run, and the expert counters are read from its layers)
        qwen3_next_cost.note_run(model_config(config),
                                 tuple(config["experts_held"]), self.rows,
                                 self.length, self.net)

    def batches(self, n):
        """n host batches (inputs, labels): next-token pairs over full rows
        of Zipf-like tokens of the vocabulary held."""
        toks = gen.token_rows(self.mix, self.seed, self.config["vocab_size"],
                              n * self.rows, self.length + 1)
        toks = toks.reshape(n, self.rows, self.length + 1)
        return [(onp.ascontiguousarray(t[:, :-1]),
                 onp.ascontiguousarray(t[:, 1:])) for t in toks]


def train(config, mix, seed):
    return Train(config, mix, seed)
