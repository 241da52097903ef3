"""averylint fixture: host-sync positives inside traced code
(AV202/AV203)."""
import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def readback(x):
    return x * x.item()                  # AV202: .item() under tracing


@jax.jit
def concretise(x):
    return x * float(x[0])               # AV202: float() on a tracer


@jax.jit
def host_copy(x):
    return jnp.sum(np.asarray(x))        # AV202: np.asarray on a tracer


@jax.jit
def tracer_branch(x):
    if jnp.any(x > 0):                   # AV203: control flow on device
        return x
    return -x


def helper(x):
    return bool(x.sum())                 # AV202 via the traced closure


@jax.jit
def calls_helper(x):
    return helper(x)


def named_stage(name, fn):
    return fn


def named_helper(x):
    return int(x.max())                  # AV202 via a named stage


class NamedStages:
    def __init__(self):
        # a stage-naming wrapper leaves its body traced
        self._lam = jax.jit(named_stage(
            "lam", lambda x: x * x.item()))  # AV202 in the named lambda
        self._fn = jax.jit(named_stage("helper", named_helper))
