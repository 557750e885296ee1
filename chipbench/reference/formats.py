"""Rounding of fp32 values to a format by name, in numpy, for the plain
references and their controls.  Independent of the program: the types
come from ``chipbench/formats.json`` and ``ml_dtypes``."""
from __future__ import annotations

import ml_dtypes
import numpy as np

from chipbench import counts

_NP = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16,
       "float8_e4m3fn": ml_dtypes.float8_e4m3fn}

#: per-tile symmetric integer formats: largest magnitude of the integers
INT_QMAX = {"int8_pt": 127, "int4_pt": 7}


def cast(x: np.ndarray, dtype: str) -> np.ndarray:
    """Round ``x`` to ``dtype`` (round to nearest even) and back to fp64."""
    return np.asarray(x, np.float32).astype(_NP[dtype]).astype(np.float64)


def storage(name: str) -> str:
    return counts.formats()[name]["storage"]


def compute(name: str) -> str:
    return counts.formats()[name]["compute"]


def one_below(name: str) -> str:
    """The next lower format a control computes in."""
    return {"fp32": "bf16", "bf16": "fp8_e4m3",
            "fp8_e4m3": "int4_pt", "int8_pt": "int4_pt"}[name]
