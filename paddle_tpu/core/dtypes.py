"""Dtype taxonomy for paddle_tpu.

The reference keeps a proto-level VarType enum (framework.proto:104 in the
reference repo) plus numpy/C++ mappings. Here the single source of truth is the
numpy/JAX dtype; we keep string names compatible with the fluid API surface
("float32", "int64", ...) so user code reads the same.

TPU note: bf16 is first-class (MXU-native); fp64 is supported by XLA:CPU for
tests but discouraged on TPU.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

_BF16 = jnp.bfloat16

# canonical name -> numpy dtype object
_NAME_TO_NP = {
    "bool": np.dtype(np.bool_),
    "int8": np.dtype(np.int8),
    "uint8": np.dtype(np.uint8),
    "int16": np.dtype(np.int16),
    "int32": np.dtype(np.int32),
    "int64": np.dtype(np.int64),
    "float16": np.dtype(np.float16),
    "float32": np.dtype(np.float32),
    "float64": np.dtype(np.float64),
}

FLOAT_DTYPES = ("float16", "bfloat16", "float32", "float64")
INT_DTYPES = ("bool", "int8", "uint8", "int16", "int32", "int64")


def convert_dtype(dtype) -> str:
    """Normalize any dtype spec (str, np.dtype, jnp dtype) to a canonical name."""
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        name = dtype
    else:
        name = np.dtype(dtype).name if dtype != _BF16 else "bfloat16"
    if name == "bfloat16":
        return name
    if name not in _NAME_TO_NP:
        # np.dtype handles e.g. np.float32 class objects
        name = np.dtype(dtype).name
    if name not in _NAME_TO_NP:
        raise ValueError(f"unsupported dtype: {dtype!r}")
    return name


def to_numpy_dtype(dtype):
    name = convert_dtype(dtype)
    if name == "bfloat16":
        return _BF16
    return _NAME_TO_NP[name]


def is_float(dtype) -> bool:
    return convert_dtype(dtype) in FLOAT_DTYPES


def is_integer(dtype) -> bool:
    return convert_dtype(dtype) in INT_DTYPES

