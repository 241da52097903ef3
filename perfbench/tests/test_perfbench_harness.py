"""The harness end to end on the CPU at a tiny size: the engine's served
answers agree with the float32 reference, and the run reports what the
contract asks of it."""
import pytest

from perfbench.tests import tiny

LOOSE = {"token_gap": 1.0, "first_logits_err": 0.1, "mask_err": 0.1}


@pytest.fixture(scope="module")
def closed():
    return tiny.run(tiny.CLOSED, LOOSE, control=True)


@pytest.fixture(scope="module")
def open_loop():
    return tiny.run(tiny.OPEN, LOOSE, control=True)


def test_closed_loop_agrees_with_the_reference(closed):
    res, info = closed["result"], closed["info"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert info["sampled"] == 4 and info["compiles_in_window"] == 0
    # bf16 serving against float32: close, and never exact
    assert 0.0 < res["checks"]["first_logits_err"]["value"] < 0.05
    assert list(res)[-1] == "checks"


def test_open_loop_serves_both_intents_and_masks(open_loop):
    res, info = open_loop["result"], open_loop["info"]
    assert res["correct"], res["checks"]
    counts = info["requests_by_intent"]
    assert counts["context"]["completed"] > 0
    assert counts["insight"]["completed"] > 0
    assert 0.0 < res["checks"]["mask_err"]["value"] < 0.05
    assert info["backlog_at_close"] >= 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_control_reads_far_above_the_program(closed, open_loop):
    """fp8 in the program's place reads several times what bf16 serving
    does on the same sample."""
    for out in (closed, open_loop):
        prog, ctrl = out["info"]["program"], out["info"]["control"]
        assert ctrl["first_logits_err"] > 3 * prog["first_logits_err"]
    assert open_loop["info"]["control"]["mask_err"] > \
        3 * open_loop["info"]["program"]["mask_err"]
