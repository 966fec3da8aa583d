"""Runtime feature detection (reference: python/mxnet/runtime.py:52-95 over
src/libinfo.cc:39-161). Features reflect the TPU-native build."""
from __future__ import annotations

__all__ = ["Features", "Feature", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    from .context import default_backend

    feats = {
        "TPU": default_backend() == "tpu",
        "XLA": True,
        "PJRT": True,
        "PALLAS": True,
        "BF16": True,
        "INT64_TENSOR_SIZE": False,
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,
        "ONEDNN": False,
        "OPENCV": False,
        "DIST_KVSTORE": True,
        "ICI_COLLECTIVES": True,
        "SIGNAL_HANDLER": True,
        "CPU_FALLBACK": True,
    }
    return {k: Feature(k, v) for k, v in feats.items()}


class Features(dict):
    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            cls.instance.update(_detect())
        return cls.instance

    def is_enabled(self, name):
        return self[name.upper()].enabled


def feature_list():
    return list(Features().values())
