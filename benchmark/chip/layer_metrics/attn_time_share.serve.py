"""Device time of the operations traced under the scope ``attn`` (the
attention mixers: their projections, norms, rotary positions, the attention
itself and the output projection; latent attention's down- and
up-projections in the A.X-K1 cell), in every serving program, over device
busy time; by self time, a module at a time
(``chipbench/scope_time_serve.py``). None where the program names no such
scope."""
from chipbench import scope_time_serve


def read(obs):
    return scope_time_serve.share(obs, ("attn",))
