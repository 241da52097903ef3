"""The in-flight decoder picks each step's greedy token on the device
(``inflight.greedy_pick``) and copies back ids, not the ``(slots, V)``
logits: the pick equals ``np.argmax`` (first maximum on ties) over plain
and verify shapes, served tokens stay exact with the one-shot generate
path, and ``inflight_fetched_bytes`` counts a step's copy at its ids,
counters, and <SEG> states on a step where a row may finish."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.intent import Intent
from repro.engine import AveryEngine
from repro.engine.inflight import InflightDecoder, _greedy_pick_jit

from test_engine import LUT, _edge_requests

SLOTS, C, V = 8, 4, 1000


def _logits(shape, dtype, seed):
    """Random logits with two or three ties planted at each row's
    maximum, the first at a random index, so a pick that does not take
    the first maximum shows."""
    rng = np.random.RandomState(seed)
    x = rng.standard_normal(shape).astype(np.float32)
    rows = x.reshape(-1, shape[-1])
    for r, row in enumerate(rows):
        first = rng.randint(0, shape[-1] - 2)
        ties = np.sort(rng.choice(np.arange(first + 1, shape[-1]),
                                  size=1 + r % 2, replace=False))
        row[[first, *ties]] = row.max() + 1.0
    return jnp.asarray(rows.reshape(shape), dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", [(SLOTS, V), (SLOTS, C, V)],
                         ids=["decode", "verify"])
def test_pick_is_the_first_maximum(dtype, shape):
    logits = _logits(shape, dtype, seed=len(shape))
    ids = np.asarray(_greedy_pick_jit()(logits))
    want = np.argmax(np.asarray(logits), axis=-1)
    assert ids.dtype == np.int32 and ids.shape == shape[:-1]
    np.testing.assert_array_equal(ids, want)
    # every row has a tie at its maximum, so np.argmax's first index is
    # the planted one and not a later tie
    host = np.asarray(logits, np.float32)
    top = host.max(axis=-1, keepdims=True)
    assert ((host == top).sum(axis=-1) >= 2).all()


def test_decoders_share_one_jitted_pick(system):
    ex = _executor(system)
    a, b = InflightDecoder(ex, slots=2), InflightDecoder(ex, slots=4)
    assert a.greedy_pick is b.greedy_pick is _greedy_pick_jit()


# ---- a tiny in-flight serve: exact tokens, a few bytes a step ----


@pytest.fixture(scope="module")
def system():
    from repro.configs.lisa_mini import CONFIG as PCFG
    from repro.core import profile as prof
    params, bns, _ = prof.random_init_system(PCFG, lut=LUT)
    return PCFG, params, bns


def _executor(system):
    from repro.core import DualStreamExecutor
    pcfg, params, bns = system
    return DualStreamExecutor(pcfg=pcfg, params=params, bottlenecks=bns,
                              lut=LUT, max_new_tokens=4, flash_decode=False,
                              page_size=4)


class _Outputs:
    """The executor, noting the shapes and sizes the step stages return."""

    def __init__(self, inner):
        self._inner, self.logits, self.seg = inner, set(), set()

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _note(self, out):
        self.logits.add((out[0].shape, out[0].dtype.itemsize))
        self.seg.add(out[1].nbytes)
        return out

    def cloud_decode_rows(self, *args):
        return self._note(self._inner.cloud_decode_rows(*args))

    def cloud_verify_rows(self, *args):
        return self._note(self._inner.cloud_verify_rows(*args))


@pytest.mark.parametrize("mode", ["plain", "speculative"])
def test_served_tokens_exact_and_fetch_is_ids(system, mode):
    ex = _Outputs(_executor(system))
    slots = 4
    engine = AveryEngine(lut=LUT, executor=ex, batching="inflight",
                         max_batch=slots,
                         speculative=mode == "speculative")
    reqs = _edge_requests(ex._inner, 6, seed=31)
    futs = [engine.submit_packet(p, q, it, time_s=float(i))
            for i, (p, q, it) in enumerate(reqs)]
    engine.drain()
    for fut, (p, q, it) in zip(futs, reqs):
        out = ex._inner.cloud_generate_batch([p], [q])[0]
        assert np.array_equal(fut.result().tokens, out[-1])
        if it is Intent.INSIGHT:
            np.testing.assert_allclose(fut.result().mask_logits, out[0],
                                       atol=3e-4)
    stats = engine.stats
    steps, fetched = stats["inflight_steps"], stats["inflight_fetched_bytes"]
    assert steps > 0 and len(ex.logits) == 1 and len(ex.seg) == 1
    (shape, itemsize), = ex.logits
    assert len(shape) == (2 if mode == "plain" else 3)   # a verify chunk
    seg_bytes, = ex.seg
    ids_bytes = int(np.prod(shape[:-1])) * 4   # (slots,) or (slots, C)
    # every step copies its ids; a step on which a row may finish copies
    # the <SEG> states too (this trunk has no device counters), so a
    # step copies at most ids and seg
    seg_steps, rest = divmod(fetched - steps * ids_bytes, seg_bytes)
    assert rest == 0 and 1 <= seg_steps <= steps
    if mode == "plain":
        # there a row finishes on every step that copies seg
        assert seg_steps <= len(reqs)
    # the ids are a small share of the logits the copy used to carry
    assert ids_bytes * 16 <= slots * shape[-1] * itemsize
