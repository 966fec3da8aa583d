"""Device time of the operations traced under the scope ``attn`` (the gated
attention mixers: projections, QK-norm, RoPE, the flash kernels, the gate),
forward and backward, over device busy time."""
from chipbench import scope_time


def read(obs):
    return scope_time.share(obs, ("attn",))
