"""Runtime feature detection.

Reference parity: src/libinfo.cc + python/mxnet/runtime.py —
``feature_list()`` / ``Features`` reporting what this build supports
(``mx.runtime.Features()['TPU'].enabled``).
"""

from __future__ import annotations


class Feature:
    __slots__ = ("name", "enabled")

    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _has_native_jpeg():
    try:
        from . import _native

        return _native.has_jpeg()
    except Exception:
        return False


def _detect():
    import jax

    backends = set()
    try:
        backends = {d.platform for d in jax.devices()}
    except Exception:
        pass
    tpu = "tpu" in backends
    feats = {
        # accelerator backends (reference: CUDA/CUDNN/TENSORRT slots)
        "TPU": tpu,
        "XLA": True,
        "PALLAS": True,
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,
        "TENSORRT": False,
        "MKLDNN": False,
        # numeric
        "F16C": True,          # fp16 supported via XLA
        "BF16": True,
        "INT64_TENSOR_SIZE": True,
        # IO / formats
        "OPENCV": False,       # PIL-based codecs instead
        # native threaded libjpeg decode+augment (src/image_decode.cc);
        # honest: probed from the built library, False when unbuilt
        "JPEG_TURBO": _has_native_jpeg(),
        "RECORDIO": True,
        # distributed
        "DIST_KVSTORE": True,  # jax.distributed + collectives
        "PS_LITE": False,      # parameter server dropped on TPU (SURVEY §2.6)
        "ICI_COLLECTIVES": True,
        # language/runtime
        "SIGNAL_HANDLER": False,
        "DEBUG": False,
        "PROFILER": True,
    }
    return {name: Feature(name, on) for name, on in feats.items()}


class Features(dict):
    """Dict of Feature (reference: mx.runtime.Features)."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            dict.__init__(cls.instance, _detect())
        return cls.instance

    def __init__(self):
        pass

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError(f"Feature '{feature_name}' is unknown, "
                               "known features are: "
                               f"{list(self.keys())}")
        return self[feature_name].enabled


def feature_list():
    return list(Features().values())
