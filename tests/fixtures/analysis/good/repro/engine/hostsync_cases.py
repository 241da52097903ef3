"""averylint fixture: host-sync negatives — static-shape reads and
host-side sync are all fine."""
import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def shape_math(x):
    b, t, pp = x.shape
    s = int(round(pp ** 0.5))            # shape-derived: static, fine
    n = int(x.shape[0])
    return x.reshape(b, t * s, s // s)[:n]


@jax.jit
def device_branchless(x):
    return jnp.where(x > 0, x, -x)       # branchless: fine


def host_side(x):
    arr = np.asarray(x)                  # outside tracing: fine
    if float(arr[0]) > 0:
        return int(arr.sum())
    return arr.item()


def named_stage(name, fn):
    return fn


class NamedStages:
    def __init__(self):
        # the named body is traced, and reads only static shapes
        self._fn = jax.jit(named_stage(
            "reshape", lambda x: x.reshape(int(x.shape[0]), -1)))
