"""Expert-load counters of the routed layers (``parallel.moe.RoutedExperts``).

Every routed layer's router keeps (``router.expert_tokens``), as an auxiliary
array the compiled step writes back like BatchNorm's moving statistics, how
many tokens chose each expert of the router's full width in the LAST step. The array stays on the device: the
training loop never reads it. ``report()`` reads the live layers' arrays (one
small device-to-host copy a layer, only when asked) and derives:

``moe.pairs_total``         token-expert pairs the routers chose (N x top_k a
                            layer), summed over the layers
``moe.pairs_here``          of them, those whose expert is held here: the
                            pairs this chip computed
``moe.expert_tokens_max``   most tokens one held expert received (any layer)
``moe.expert_tokens_mean``  mean tokens a held expert received

and sets the gauges of the same names in the registry.
"""
from __future__ import annotations

import weakref

_LAYERS = []   # weak references, in order of construction


def register(layer):
    _LAYERS.append(weakref.ref(layer))


def live_layers():
    layers = [ref() for ref in _LAYERS]
    _LAYERS[:] = [ref for ref, layer in zip(_LAYERS, layers)
                  if layer is not None]
    return [layer for layer in layers if layer is not None]


def report(registry=None):
    """``{"layers": [...], "moe.pairs_here": ..}``; ``layers`` has one row a
    live routed layer: its ``experts_held``, the tokens of each held expert
    in the last step (``expert_tokens``), ``pairs_here``, ``pairs_total``,
    ``max`` and ``mean``. None where no routed layer is alive."""
    rows = []
    for layer in live_layers():
        counts = layer.router.expert_tokens.data().asnumpy()
        lo, hi = layer.experts_held
        held = counts[lo:hi]
        rows.append({"experts_held": (lo, hi),
                     "expert_tokens": [float(c) for c in held],
                     "pairs_here": float(held.sum()),
                     "pairs_total": float(counts.sum()),
                     "max": float(held.max()), "mean": float(held.mean())})
    if not rows:
        return None
    n_held = sum(len(r["expert_tokens"]) for r in rows)
    out = {"layers": rows,
           "moe.pairs_here": sum(r["pairs_here"] for r in rows),
           "moe.pairs_total": sum(r["pairs_total"] for r in rows),
           "moe.expert_tokens_max": max(r["max"] for r in rows)}
    out["moe.expert_tokens_mean"] = out["moe.pairs_here"] / n_held
    if registry is not None:
        for name, value in out.items():
            if name != "layers":
                registry.gauge(name).set(value)
    return out
