"""paddle.inference parity: Config + Predictor over saved artifacts.

Reference capability: paddle/fluid/inference/api/analysis_predictor.h:100
(AnalysisPredictor) and python/paddle/inference/wrapper.py — the deploy
surface: load a serialized program + weights in a fresh process, bind
named inputs, run, read named outputs. TPU-native redesign: the artifact
is the hermetic StableHLO program written by paddle.jit.save (or
static.save_inference_model); "analysis passes" are XLA's compile
pipeline, so Config's IR-optimization knobs are accepted for parity and
delegated. No separate C++ predictor runtime is needed — XLA's runtime is
the native engine under the same API shape.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from ..core import enforce as E

__all__ = ["Config", "Predictor", "create_predictor", "Tensor",
           "PrecisionType", "PlaceType", "get_version",
           "EngineOverloaded",
           "PageAllocator", "PagedKVCache", "Request", "RequestCost",
           "RequestOutput", "RequestRejected", "ServingEngine"]

_SERVING = {"PageAllocator": "paged", "PagedKVCache": "paged",
            "EngineOverloaded": "engine",
            "Request": "engine", "RequestCost": "accounting",
            "RequestOutput": "engine", "RequestRejected": "engine",
            "ServingEngine": "engine"}


def __getattr__(name):
    # Lazy: the serving stack pulls in the model families; the static
    # Predictor surface must stay importable without them (and without
    # a circular import during package init).
    if name in _SERVING:
        import importlib
        mod = importlib.import_module(f".{_SERVING[name]}", __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def get_version() -> str:
    from .. import __version__
    return __version__


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class PlaceType:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"
    CUSTOM = "custom"


class Config:
    """reference: inference/api/paddle_analysis_config.h (AnalysisConfig).
    Points at a saved artifact prefix; device/optimization toggles are
    accepted and recorded (XLA owns them)."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        # paddle passes either (model_dir) or (prog_file, params_file);
        # artifacts here are a single prefix (prefix.pdmodel + ...)
        if prog_file is not None and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self._prefix = prog_file
        self._params_file = params_file
        self._device = "tpu"
        self._device_id = 0
        self._ir_optim = True
        self._glog_info = False
        self._memory_optim = True

    def set_prog_file(self, path: str):
        self._prefix = path[:-len(".pdmodel")] if path.endswith(".pdmodel") \
            else path

    def prog_file(self):
        return self._prefix

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._device, self._device_id = "gpu", device_id

    def enable_custom_device(self, device_type, device_id=0):
        self._device, self._device_id = device_type, device_id

    def disable_gpu(self):
        self._device = "cpu"

    def use_gpu(self):
        return self._device == "gpu"

    def switch_ir_optim(self, flag=True):
        self._ir_optim = bool(flag)

    def ir_optim(self):
        return self._ir_optim

    def enable_memory_optim(self, flag=True):
        self._memory_optim = bool(flag)

    def disable_glog_info(self):
        self._glog_info = False

    def set_cpu_math_library_num_threads(self, n):
        pass

    def summary(self) -> str:
        return (f"Config(prefix={self._prefix}, device={self._device}, "
                f"ir_optim={self._ir_optim})")


class Tensor:
    """Named IO handle (reference: inference/api/paddle_tensor.h
    ZeroCopyTensor) — copy_from_cpu / copy_to_cpu semantics."""

    def __init__(self, name: str, owner: "Predictor", is_input: bool):
        self.name = name
        self._owner = owner
        self._is_input = is_input

    def copy_from_cpu(self, data: np.ndarray):
        if not self._is_input:
            raise E.PreconditionNotMetError("copy_from_cpu on an output handle")
        self._owner._inputs[self.name] = jnp.asarray(np.asarray(data))

    def copy_to_cpu(self) -> np.ndarray:
        if self._is_input:
            raise E.PreconditionNotMetError("copy_to_cpu on an input handle")
        out = self._owner._outputs.get(self.name)
        if out is None:
            raise E.PreconditionNotMetError("run() the predictor before reading outputs")
        return np.asarray(out)

    def shape(self):
        if self._is_input:
            arr = self._owner._inputs.get(self.name)
        else:
            arr = self._owner._outputs.get(self.name)
        return list(arr.shape) if arr is not None else None

    def reshape(self, shape):
        pass  # shapes are taken from the fed arrays


class Predictor:
    """reference: analysis_predictor.h:100. Wraps a jit.save /
    save_inference_model artifact; run() executes the compiled program."""

    def __init__(self, config: Config):
        self.config = config
        prefix = config.prog_file()
        if prefix is None or not os.path.exists(prefix + ".pdmodel"):
            raise FileNotFoundError(
                f"no saved program at {prefix}.pdmodel")
        import pickle
        with open(prefix + ".pdmodel", "rb") as f:
            self._exported = jax.export.deserialize(f.read())
        meta_path = prefix + ".pdmeta"
        self._meta = {}
        if os.path.exists(meta_path):
            with open(meta_path, "rb") as f:
                self._meta = pickle.load(f)
        # two artifact flavors: jit.save (params in .pdiparams, inputs are
        # positional) or static.save_inference_model (named feeds)
        self._kind = "static" if "feed_names" in self._meta else "jit"
        if self._kind == "static":
            self._input_names = list(self._meta["feed_names"])
            self._output_names = list(self._meta["fetch_names"])
            self._params = None
            self._buffers = None
            self._out_tree = None
        else:
            from ..framework.io import load as fload
            blob = fload(prefix + ".pdiparams")
            from ..core.tensor import Tensor as PTensor
            self._params = {n: (p._data if isinstance(p, PTensor)
                                else jnp.asarray(np.asarray(p)))
                            for n, p in blob["params"].items()}
            self._buffers = {n: (b._data if isinstance(b, PTensor)
                                 else jnp.asarray(np.asarray(b)))
                             for n, b in blob["buffers"].items()}
            n_in = int(self._meta.get("n_inputs", 1))
            self._input_names = [f"x{i}" for i in range(n_in)]
            self._output_names = None   # known after first run
        self._inputs: Dict[str, jax.Array] = {}
        self._outputs: Dict[str, jax.Array] = {}

    # -- IO surface --------------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_output_names(self) -> List[str]:
        if self._output_names is None:
            return [f"out{i}" for i in range(len(self._outputs) or 1)]
        return list(self._output_names)

    def get_input_handle(self, name: str) -> Tensor:
        if name not in self._input_names:
            raise KeyError(f"unknown input {name!r}; "
                           f"inputs are {self._input_names}")
        return Tensor(name, self, is_input=True)

    def get_output_handle(self, name: str) -> Tensor:
        return Tensor(name, self, is_input=False)

    # -- execution ---------------------------------------------------------
    def run(self, inputs: Optional[List[np.ndarray]] = None):
        """Either bind handles then run(), or pass arrays positionally
        (both reference calling conventions)."""
        if inputs is not None:
            for n, a in zip(self._input_names, inputs):
                self._inputs[n] = jnp.asarray(np.asarray(a))
        missing = [n for n in self._input_names if n not in self._inputs]
        if missing:
            raise E.PreconditionNotMetError(f"inputs not set: {missing}")
        args = [self._inputs[n] for n in self._input_names]
        if self._kind == "static":
            flat = self._exported.call(*args)
        else:
            flat = self._exported.call(self._params, self._buffers, *args)
        flat = list(flat) if isinstance(flat, (tuple, list)) else [flat]
        if self._output_names is None:
            self._output_names = [f"out{i}" for i in range(len(flat))]
        self._outputs = dict(zip(self._output_names, flat))
        if inputs is not None:
            return [np.asarray(o) for o in flat]
        return True

    def clear_intermediate_tensor(self):
        self._outputs.clear()


def create_predictor(config: Config) -> Predictor:
    """reference: paddle_inference_api.h CreatePredictor."""
    return Predictor(config)
