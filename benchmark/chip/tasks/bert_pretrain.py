"""BERT pretraining (masked LM + next-sentence) on the zoo's BERT.

``compile_step`` takes one input and one label array, so a batch rides
packed: inputs (B, 2, T) = token ids and token types, labels (B, T + 1) =
the original id at each masked position (-1 elsewhere) and the
next-sentence label in the last column.
"""
import numpy as onp

from chipbench import reference, traffic as gen

FAMILY = "bert"


def build_net(config, seed):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import bert

    mx.random.seed(gen.seed31(seed, 0))
    body = getattr(bert, config["factory"])(
        **config.get("factory_kwargs", {}))
    net = bert.BERTForPretraining(body, vocab_size=config["vocab_size"])
    net.initialize()
    layer = body.encoder.layers[0]
    built = {
        "vocab_size": body.word_embed.weight.shape[0],
        "hidden_size": body.units,
        "num_hidden_layers": len(body.encoder.layers),
        "num_attention_heads": layer._num_heads,
        "intermediate_size": layer.ffn_1.weight.shape[0],
        "max_position_embeddings": body.position_embed.shape[0],
        "type_vocab_size": body.token_type_embed.weight.shape[0],
    }
    stated = {k: config[k] for k in built}
    if built != stated:
        raise ValueError(f"{config['factory']} built {built}, the "
                         f"configuration states {stated}")
    return net


def reference_logits(net, config, x):
    """Masked-LM logits of the plain reference on one packed batch."""
    x = onp.asarray(x)
    out = reference.forward(reference.system_weights(net), FAMILY,
                            config["num_attention_heads"],
                            config["num_hidden_layers"], x[:, 0], x[:, 1])
    return out["logits"]


def system_logits(net, x):
    """Inference-mode masked-LM logits on one packed batch (B, 2, T)."""
    import mxnet_tpu as mx

    x = onp.asarray(x)
    mlm, _ = net(mx.np.array(x[:, 0]), mx.np.array(x[:, 1]))
    return mlm._data


def _packed_net(model):
    from mxnet_tpu import gluon, numpy_extension as npx

    class Packed(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.model = model

        def forward(self, x):
            T = x.shape[2]
            tokens = npx.slice_axis(x, axis=1, begin=0, end=1)
            types = npx.slice_axis(x, axis=1, begin=1, end=2)
            return self.model(tokens.reshape((-1, T)),
                              types.reshape((-1, T)))

    return Packed()


def _loss(length, n_masked):
    from mxnet_tpu import gluon, np, numpy_extension as npx

    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(out, y):
        """Per row: mean masked-LM cross entropy over the row's masked
        positions (every row has ``n_masked``) plus the next-sentence
        cross entropy."""
        mlm, nsp = out
        labels = npx.slice_axis(y, axis=1, begin=0, end=length)
        nsp_label = npx.slice_axis(y, axis=1, begin=length,
                                   end=length + 1).reshape((-1,))
        weight = (labels >= 0).astype("float32")
        mlm_loss = sce(mlm, np.maximum(labels, 0), weight) \
            * (float(length) / n_masked)
        return mlm_loss + sce(nsp, nsp_label)

    return loss_fn


class Train:
    def __init__(self, config, mix, seed):
        self.config, self.mix, self.seed = config, mix, seed
        self.model = build_net(config, seed)
        self.net = _packed_net(self.model)
        self.rows, self.length = int(mix["rows"]), int(mix["length"])
        self.n_masked = max(1, round(self.length
                                     * float(mix["mask_share"])))
        self.loss_fn = _loss(self.length, self.n_masked)
        self.tokens_per_step = self.rows * self.length

    def batches(self, n):
        """n host batches. Two segments a row (token types 0 then 1, the
        cut drawn per row), no padding; ``n_masked`` positions a row are
        chosen and replaced as published: 80% [MASK], 10% a random token,
        10% left as they are."""
        vocab, T, B = self.config["vocab_size"], self.length, self.rows
        rs = gen.rng(self.seed, 4)
        toks = gen.token_rows(self.mix, self.seed, vocab, n * B, T)
        toks = toks.reshape(n, B, T)
        mask_id = int(self.mix["mask_token_id"])
        out = []
        for b in range(n):
            ids = toks[b].copy()
            cut = rs.integers(T // 4, 3 * T // 4, size=B)
            types = (onp.arange(T)[None, :] >= cut[:, None]).astype("int32")
            labels = onp.full((B, T + 1), -1, dtype="int32")
            labels[:, T] = rs.integers(0, 2, size=B)
            for r in range(B):
                pos = rs.choice(T, size=self.n_masked, replace=False)
                labels[r, pos] = ids[r, pos]
                how = rs.random(self.n_masked)
                ids[r, pos[how < 0.8]] = mask_id
                rnd = pos[(how >= 0.8) & (how < 0.9)]
                ids[r, rnd] = rs.integers(0, vocab, size=len(rnd))
            x = onp.stack([ids, types], axis=1).astype("int32")
            out.append((onp.ascontiguousarray(x), labels))
        return out


def train(config, mix, seed):
    return Train(config, mix, seed)
