"""A four-chip cell on four CPU devices: weights made sharded by the
program's rules, served through ``AveryEngine(mesh=...)`` and checked
against the reference (``sharded_tiny.py``, in a process of its own,
since the device count is fixed when JAX starts)."""
import json
import os
import subprocess
import sys

import pytest

from perfbench import harness


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " --xla_force_"
                          "host_platform_device_count=4").strip())
    p = subprocess.run([sys.executable, "-m", "perfbench.tests.sharded_tiny"],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_every_sharded_leaf_holds_a_quarter_on_each_device(four):
    sharded = [x for x in four["leaves"] if x["model_axes"]]
    names = {x["path"] for x in sharded}
    for leaf in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert f"[0]['llm']['groups'][0]['attn']['{leaf}']" in names or \
            f"[0]['llm']['groups'][0]['mlp']['{leaf}']" in names, leaf
    assert "[0]['llm']['embed']" in names
    assert "[0]['llm']['answer_head']" in names
    for x in four["leaves"]:
        for axis, share in enumerate(x["share"]):
            assert share == (0.25 if axis in x["model_axes"] else 1.0), x
    assert four["trunk_bytes_on_device_0"] < 0.3 * four["trunk_bytes"]


def test_sharded_weights_are_the_one_device_weights(four):
    assert all(x["equal"] for x in four["leaves"])


def test_the_four_device_cell_is_correct_and_its_control_is_not(four):
    assert four["chips"] == 4 and four["model_shards"] == 4
    assert four["correct"] is True, four["checks"]
    assert four["attempted"] > 0 and four["compiles_in_window"] == 0
    limits = {k: c["limit"] for k, c in four["checks"].items()}
    assert any(four["control"][k] > limit for k, limit in limits.items())
