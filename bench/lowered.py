"""Rounding one precision below the configuration's, for the controls."""
import numpy as np


def bf16_round(x):
    """float32 -> the nearest bfloat16, held as float32."""
    import ml_dtypes
    return np.float32(ml_dtypes.bfloat16(np.float32(x)))
