"""A run with the timed path broken underneath comes out not correct under
the cells' committed limits, and so does the fp8 control. The harness's
look for a chip is skipped; everything else of a run is driven."""
import os

import jax.numpy as jnp
import pytest

from perfbench import check, drive, harness
from perfbench.tests import tiny

LIMITS = {name: harness.load_json(os.path.join(
    harness.HERE, "limits", f"{name}.json"))
    for name in ("phi4mini-context-closed", "qwen2vl2b-mixed-burst")}
# twice as many clients as decode slots, with answers long enough that
# the queue never empties, keep all eight slots live
FULL = dict(tiny.CLOSED, clients=16, answer_len=32, preroll_answers=16,
            sample={"context": 8})


class StaleState(drive.StageProxy):
    """The decode step hands back the pool it was given: no token's keys
    and values are ever written."""

    def cloud_decode_rows(self, pool, *args):
        logits, seg, _ = super().cloud_decode_rows(pool, *args)
        return logits, seg, pool


class HalfBatch(drive.StageProxy):
    """Only the first half of the batch is computed; the second half gets
    the first half's outputs."""

    def cloud_decode_rows(self, *args):
        logits, seg, pool = super().cloud_decode_rows(*args)
        h = logits.shape[0] // 2
        return (logits.at[h:].set(logits[:h]), seg.at[h:].set(seg[:h]),
                pool)


class TokenAltered(drive.StageProxy):
    """Every decode step's logits are shifted by one vocabulary entry."""

    def cloud_decode_rows(self, *args):
        logits, seg, pool = super().cloud_decode_rows(*args)
        return jnp.roll(logits, 1, axis=-1), seg, pool


class MaskAltered(drive.StageProxy):
    """The mask decode's output is scaled."""

    def cloud_mask(self, feats, seg):
        return 1.5 * super().cloud_mask(feats, seg)


@pytest.mark.parametrize("fault,mix,cell", [
    (StaleState, FULL, "phi4mini-context-closed"),
    (HalfBatch, FULL, "phi4mini-context-closed"),
    (TokenAltered, FULL, "phi4mini-context-closed"),
    (MaskAltered, tiny.OPEN, "qwen2vl2b-mixed-burst"),
])
def test_a_broken_timed_path_is_not_correct(fault, mix, cell):
    out = tiny.run(mix, LIMITS[cell], wrap=fault)
    assert out["result"]["correct"] is False, out["result"]["checks"]


@pytest.mark.parametrize("mix,cell", [
    (tiny.CLOSED, "phi4mini-context-closed"),
    (tiny.OPEN, "qwen2vl2b-mixed-burst"),
])
def test_sound_run_and_fp8_control_under_committed_limits(mix, cell):
    out = tiny.run(mix, LIMITS[cell], control=True)
    assert out["result"]["correct"] is True, out["result"]["checks"]
    ctrl = check.verdict(out["info"]["control"], LIMITS[cell])
    assert not check.passed(ctrl), ctrl
