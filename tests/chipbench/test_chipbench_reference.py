"""The plain references against the system at tiny sizes on the CPU, and the
serving check shown to fail when what the cache holds is wrong."""
import json
import os

import numpy as onp
import pytest

from chipbench_paths import BENCH, FIXTURE
from chipbench import harness, reference, traffic as gen

# float32 on the CPU, both sides: the only differences are the order of
# summation and fused kernels, some 1e-6 of the logits' spread. 1e-3 fails
# anything structural (a dropped layer, a wrong mask, the tanh GELU: 1e-2
# and more) by a wide margin.
TOL = 1e-3


def _config(name):
    with open(os.path.join(FIXTURE, "configs", name + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def gpt():
    config = _config("gpt-tiny")
    task = harness.load_module(BENCH, "tasks", "causal_lm")
    return task, config, task.build_net(config, 7)


@pytest.fixture(scope="module")
def bert():
    config = _config("bert-tiny")
    task = harness.load_module(BENCH, "tasks", "bert_pretrain")
    return task, config, task.build_net(config, 7)


def _tokens(vocab, rows, length, seed=3):
    return gen.zipf_tokens(gen.rng(seed, 9), vocab, 1.0, (rows, length))


def test_gpt_reference_matches_the_system(gpt):
    task, config, net = gpt
    x = _tokens(config["n_vocab"], 3, 96)
    ref = task.reference_logits(net, config, x)
    assert ref.shape == (3, 96, config["n_vocab"])
    assert reference.logits_error(task.system_logits(net, x), ref) < TOL


def test_bert_reference_matches_the_system(bert):
    task, config, net = bert
    ids = _tokens(config["vocab_size"], 3, 48)
    types = (onp.arange(48)[None, :] >= 20).astype("int32").repeat(3, 0)
    x = onp.stack([ids, types], axis=1)
    ref = task.reference_logits(net, config, x)
    assert ref.shape == (3, 48, config["vocab_size"])
    assert reference.logits_error(task.system_logits(net, x), ref) < TOL


def test_bert_next_sentence_head_matches(bert):
    import mxnet_tpu as mx

    task, config, net = bert
    ids = _tokens(config["vocab_size"], 2, 32)
    types = onp.zeros_like(ids)
    out = reference.forward(reference.system_weights(net), "bert",
                            config["num_attention_heads"],
                            config["num_hidden_layers"], ids, types)
    _, nsp = net(mx.np.array(ids), mx.np.array(types))
    assert onp.allclose(onp.asarray(nsp._data), onp.asarray(out["nsp"]),
                        atol=1e-5)


@pytest.mark.parametrize("what", ["weight", "causal_mask", "token_types"])
def test_the_comparison_sees_a_structural_difference(gpt, bert, what):
    if what == "token_types":
        task, config, net = bert
        ids = _tokens(config["vocab_size"], 2, 32)
        x = onp.stack([ids, onp.ones_like(ids)], axis=1)
        wrong = task.reference_logits(
            net, config, onp.stack([ids, onp.zeros_like(ids)], axis=1))
        assert reference.logits_error(task.system_logits(net, x),
                                      wrong) > 10 * TOL
        return
    task, config, net = gpt
    x = _tokens(config["n_vocab"], 2, 64)
    system = task.system_logits(net, x)
    weights = reference.system_weights(net)
    if what == "weight":
        # (a constant added to a bias would vanish in the next LayerNorm)
        weights["blocks.1.ffn_2.weight"] = \
            weights["blocks.1.ffn_2.weight"] * 1.2
        wrong = reference.forward(weights, "gpt2", 2, 2, x)["logits"]
    else:
        spec = dict(reference.SPECS["gpt2"], causal=False)
        reference.SPECS["not_causal"] = spec
        try:
            wrong = reference.forward(weights, "not_causal", 2, 2,
                                      x)["logits"]
        finally:
            del reference.SPECS["not_causal"]
    assert reference.logits_error(system, wrong) > 10 * TOL


def test_chosen_token_gaps():
    logits = onp.array([[0.0, 1.0, 3.0, 2.0], [5.0, 1.0, 0.0, 4.0]])
    gaps = reference.chosen_token_gaps(logits, [2, 3])
    assert gaps[0] == 0.0
    assert gaps[1] == pytest.approx(1.0 / logits[1].std())


@pytest.fixture(scope="module")
def serving(gpt):
    """An engine of the tiny model, and what it answers to four prompts."""
    task, config, net = gpt
    with open(os.path.join(FIXTURE, "traffic", "decode-tiny.json")) as fh:
        mix = json.load(fh)
    runner = harness.load_module(BENCH, "runners", "serve_decode")
    prompts = [([int(t) for t in _tokens(config["n_vocab"], 1, n, seed=n)[0]],
                12) for n in (9, 17, 30, 41)]

    def answers(corrupt):
        eng = runner.build_engine(net, mix)
        try:
            if corrupt:
                run = eng.programs.run

                def bad_run(key, datas):
                    outs = list(run(key, datas))
                    if key[0] == "prefill":
                        # what the prefill cached is not what decode reads
                        outs[1] = outs[1] * -30.0 + 1.0
                        outs[2] = outs[2][::-1] * -20.0
                    return outs

                eng.programs.run = bad_run
            done = []
            for prompt, n in prompts:
                req = runner.Request(prompt, n)
                req.tokens = eng.submit(prompt, max_new_tokens=n).result(
                    timeout=120)
                done.append(req)
            return done
        finally:
            eng.close()

    return task, config, net, mix, runner, answers


def test_serving_check_passes_on_a_sound_cache(serving):
    task, config, net, mix, runner, answers = serving
    worst = runner.check_against_reference(
        task, net, config, answers(corrupt=False), mix["check_pad_to"])
    assert worst <= mix["chosen_logit_tolerance"]


def test_serving_check_fails_on_a_corrupted_cache(serving):
    task, config, net, mix, runner, answers = serving
    worst = runner.check_against_reference(
        task, net, config, answers(corrupt=True), mix["check_pad_to"])
    assert worst > 2 * mix["chosen_logit_tolerance"]
