"""Next-token language modelling on a GPT-style model of the zoo.

A task says how one family of configurations is built, fed and compared
with the plain reference; runners know it only through these functions.
"""
import numpy as onp

from chipbench import reference, traffic as gen

FAMILY = "gpt2"


def build_net(config, seed):
    """The zoo model the configuration names, at the sizes it states, with
    weights from ``seed``."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import model_zoo

    mx.random.seed(gen.seed31(seed, 0))
    net = getattr(model_zoo, config["factory"])(
        **config.get("factory_kwargs", {}))
    net.initialize()
    built = {"n_vocab": net.vocab_size, "n_ctx": net.max_length,
             "n_embd": net._units, "n_head": net._num_heads,
             "n_layer": net._num_layers}
    stated = {k: config[k] for k in built}
    if built != stated:
        raise ValueError(f"{config['factory']} built {built}, the "
                         f"configuration states {stated}")
    return net


def partition_rules():
    """The layout of a sharded training cell: megatron over ``tp``, FSDP over
    ``dp``."""
    from mxnet_tpu.gluon.model_zoo.gpt import gpt_tp_rules

    return gpt_tp_rules("train")


def reference_logits(net, config, x):
    """(B, T, V) logits of the plain reference on the net's own weights,
    for one batch of token ids."""
    out = reference.forward(reference.system_weights(net), FAMILY,
                            config["n_head"], config["n_layer"], x)
    return out["logits"]


def system_logits(net, x):
    """Inference-mode logits of the system on one packed batch."""
    import mxnet_tpu as mx

    return net(mx.np.array(x))._data


class Train:
    def __init__(self, config, mix, seed):
        from mxnet_tpu import gluon

        self.config, self.mix, self.seed = config, mix, seed
        self.net = build_net(config, seed)
        self.model = self.net
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        self.rows, self.length = int(mix["rows"]), int(mix["length"])
        self.tokens_per_step = self.rows * self.length

    def batches(self, n):
        """n host batches (inputs, labels): next-token pairs over full rows
        of Zipf-like tokens."""
        toks = gen.token_rows(self.mix, self.seed, self.config["n_vocab"],
                              n * self.rows, self.length + 1)
        toks = toks.reshape(n, self.rows, self.length + 1)
        return [(onp.ascontiguousarray(t[:, :-1]),
                 onp.ascontiguousarray(t[:, 1:])) for t in toks]


def train(config, mix, seed):
    return Train(config, mix, seed)
