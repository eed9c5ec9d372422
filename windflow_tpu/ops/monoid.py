"""One source of truth for the built-in monoid reductions: identities and
reducer tables shared by the host path (numpy, ops/functions.py), the XLA
device path (ops/device.py, ops/resident.py) and the mesh layer
(parallel/mesh.py).

Semantics of the identity (what an *empty* window produces, matching the
reference's behaviour of leaving the result default-initialised): sum and
count give 0, prod gives 1, min/max give the dtype extremes — ``±inf`` for
floats, ``iinfo`` bounds for integers.
"""

from __future__ import annotations

import numpy as np

OPS = ("sum", "count", "mean", "min", "max", "prod")


def identity(op: str, dtype):
    """Monoid identity of `op` in `dtype` (accepts numpy or jax dtypes)."""
    dt = np.dtype(dtype)
    if op in ("sum", "count", "mean"):
        return dt.type(0)
    if op == "prod":
        return dt.type(1)
    if op not in ("min", "max"):
        raise ValueError(f"unknown op {op!r}")
    if dt.kind == "f":
        return dt.type(np.inf if op == "min" else -np.inf)
    info = np.iinfo(dt)
    return dt.type(info.max if op == "min" else info.min)


#: numpy ufuncs for the host fold (count has no ufunc: it counts rows)
NP_UFUNCS = {
    "sum": np.add,
    "min": np.minimum,
    "max": np.maximum,
    "prod": np.multiply,
}


def jnp_ufunc(op: str):
    """The jax.numpy pairwise combiner for `op` (count combines like sum —
    partial counts add)."""
    import jax.numpy as jnp
    return {"sum": jnp.add, "count": jnp.add, "mean": jnp.add,
            "min": jnp.minimum, "max": jnp.maximum,
            "prod": jnp.multiply}[op]


def jnp_reducer(op: str):
    """The jax.numpy whole-axis reducer for `op` (mean/count handled by the
    callers from masks)."""
    import jax.numpy as jnp
    return {"sum": jnp.sum, "mean": jnp.sum, "min": jnp.min,
            "max": jnp.max, "prod": jnp.prod}[op]
