"""Look-ups in ``BENCHMARK.json`` by name that the tests of several cells
share: a test pins its own cell and metrics and asks nothing of what a later
PR appends."""

GPT2_TRAIN = "gpt2-medium.train-1k"
BERT = "bert-base.pretrain-512"
QWEN = "qwen3-next-80b-a3b.train-4k"
GPT2_SERVE = "gpt2-medium.decode-saturated"
GRANITE = "granite-4.0-h-small.decode-saturated-32"
AXK1 = "A.X-K1.decode-saturated-64"


def cells_by_kind(manifest):
    """``{"train": cells, "serve": cells}``: a cell's kind is the rate it
    reports (``train_tokens_per_s`` or ``serve_tokens_per_s``), read from the
    manifest alone, so a cell appended with files of its own counts too."""
    rates = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    return {kind: set(rates[f"{kind}_tokens_per_s"])
            for kind in ("train", "serve")}
