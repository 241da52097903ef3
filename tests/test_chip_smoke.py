"""CPU rehearsal of ``chip_smoke.py``: its serving phases run at
lisa_mini size through the same control flow and checks as on the chip
(kernels interpreted), and its ``main()`` refuses a host without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    return chip_smoke


def test_serving_phases_pass_at_lisa_mini(chip_smoke):
    from repro.configs.lisa_mini import CONFIG as MINI
    params, bns, lut = chip_smoke.build_system(MINI, seed=0)
    times = {}
    out = chip_smoke.serving_phases(MINI, params, bns, lut, 0, times)
    assert set(times) == {"serve, kernels", "serve, jnp reference",
                          "serve, speculative",
                          "compare stages, kernel vs reference"}
    assert out["prefix_hits"] >= 2            # one repeat per operator
    for key in ("engine_token_agreement", "decode_agreement",
                "verify_agreement", "draft_agreement",
                "spec_token_agreement"):
        assert out[key] == 1.0, key
    for key in ("decode_err", "verify_err", "verify_vs_decode_err",
                "draft_err", "engine_mask_err"):
        assert out[key] < 1e-4, key
    # interpreted on CPU: the compiled steps hold no Mosaic kernel
    assert not out["decode_step_kernel"]


def test_serving_phases_fail_on_a_broken_check(chip_smoke, monkeypatch):
    """A failed check raises out of the phase instead of carrying on."""
    from repro.configs.lisa_mini import CONFIG as MINI
    monkeypatch.setattr(chip_smoke, "TOLERANCE", -1.0)
    params, bns, lut = chip_smoke.build_system(MINI, seed=0)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.serving_phases(MINI, params, bns, lut, 0, {})


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_main_refuses_a_host_without_tpu():
    res = _run(os.path.join(ROOT, "chip_smoke.py"), ROOT)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "no TPU" in res.stdout


def test_main_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    res = _run("chip_smoke.py", tmp_path)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
