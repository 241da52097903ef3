"""averylint: each checker catches its fixture positives, passes its
fixture negatives, the baseline workflow round-trips, and the tree
itself lints clean against the committed baseline."""
import json
from pathlib import Path

import pytest

from repro.analysis import baseline as baseline_mod
from repro.analysis import lint

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "analysis"
BAD = FIXTURES / "bad"
GOOD = FIXTURES / "good"


def _findings(tree, checker=None):
    only = [checker] if checker else None
    return lint.lint_paths([tree], tree, only=only)


def _codes(tree, checker=None):
    return {f.code for f in _findings(tree, checker)}


# ---- per-checker: positives caught, negatives pass ----


@pytest.mark.parametrize("checker,codes", [
    ("recompile", {"AV101", "AV102"}),
    ("hostsync", {"AV201", "AV202", "AV203"}),
    ("futures", {"AV301", "AV302"}),
    ("refcount", {"AV401"}),
    ("determinism", {"AV501", "AV502", "AV503", "AV504"}),
    ("observability", {"AV601", "AV602", "AV603"}),
])
def test_checker_catches_bad_and_passes_good(checker, codes):
    assert _codes(BAD, checker) == codes
    assert _findings(GOOD, checker) == []


def test_recompile_granularity():
    """Every distinct churn shape in the fixture is caught, and the
    keyed-cache/constructor/lru/amortized idioms are each exercised in
    the good fixture (parse sanity: the functions exist)."""
    by_symbol = {f.symbol for f in _findings(BAD, "recompile")}
    assert {"per_request_jit", "immediate_invoke_in_loop",
            "bare_expression", "Churner.pump"} <= by_symbol
    good_src = (GOOD / "repro/engine/recompile_cases.py").read_text()
    for idiom in ("lru_cache", "_compiled", "__init__", "lower"):
        assert idiom in good_src


def test_hostsync_flags_traced_callee():
    """AV202 propagates through the traced-region closure: the helper
    is flagged because a jitted function calls it."""
    hits = [f for f in _findings(BAD, "hostsync") if f.symbol == "helper"]
    assert len(hits) == 1 and hits[0].code == "AV202"


@pytest.mark.parametrize("symbol", [
    "NamedStages.__init__.<lambda@", "named_helper"])
def test_hostsync_sees_through_stage_naming(symbol):
    """``jax.jit(named_stage(name, fn))`` traces ``fn`` as
    ``jax.jit(fn)`` would: a lambda body and a module function handed
    to the naming wrapper are both in the traced closure."""
    hits = [f for f in _findings(BAD, "hostsync")
            if f.symbol.startswith(symbol)]
    assert len(hits) == 1 and hits[0].code == "AV202"


def test_refcount_flags_both_acquisitions():
    msgs = {f.message.split("(")[0] for f in _findings(BAD, "refcount")}
    assert any("pool.alloc" in m for m in msgs)
    assert any("pool.retain" in m for m in msgs)


# ---- fingerprints + baseline workflow ----


def test_fingerprint_survives_line_drift(tmp_path):
    src = (BAD / "repro/engine/determinism_cases.py").read_text()
    a = tmp_path / "a" / "repro" / "engine"
    a.mkdir(parents=True)
    (a / "determinism_cases.py").write_text(src)
    fa = lint.lint_paths([tmp_path / "a"], tmp_path / "a")
    # shift every site down ten lines; fingerprints must not move
    (a / "determinism_cases.py").write_text("\n" * 10 + src)
    fb = lint.lint_paths([tmp_path / "a"], tmp_path / "a")
    assert [f.fingerprint for f in fa] == [f.fingerprint for f in fb]
    assert [f.line + 10 for f in fa] == [f.line for f in fb]


def test_baseline_roundtrip(tmp_path):
    findings = _findings(BAD)
    path = tmp_path / baseline_mod.BASELINE_NAME
    baseline_mod.write(path, findings)
    loaded = baseline_mod.load(path)
    new, old = baseline_mod.split(findings, loaded)
    assert new == [] and len(old) == len(findings)
    # a reason survives a rewrite
    fp = findings[0].fingerprint
    loaded[fp] = "known debt"
    baseline_mod.write(path, findings, reasons=loaded)
    assert baseline_mod.load(path)[fp] == "known debt"


def test_driver_exit_codes_and_baseline(tmp_path, capsys):
    assert lint.main([str(BAD), "--no-baseline"]) == 1
    assert lint.main([str(GOOD), "--no-baseline"]) == 0
    assert lint.main([str(tmp_path / "missing")]) == 2
    capsys.readouterr()
    # grandfather everything -> clean; then a fresh finding is new again
    bl = tmp_path / baseline_mod.BASELINE_NAME
    assert lint.main([str(BAD), "--baseline", str(bl),
                      "--write-baseline"]) == 0
    capsys.readouterr()
    assert lint.main([str(BAD), "--baseline", str(bl)]) == 0
    out = capsys.readouterr().out
    assert "baselined" in out and "clean" in out


def test_json_output(capsys):
    lint.main([str(BAD), "--no-baseline", "--json",
               "--checker", "futures"])
    data = json.loads(capsys.readouterr().out)
    assert data["counts"]["new"] == 2
    codes = {f["code"] for f in data["new"]}
    assert codes == {"AV301", "AV302"}
    assert all("fingerprint" in f for f in data["new"])


# ---- the tree itself ----


def test_src_lints_clean_against_committed_baseline(capsys):
    """`python -m repro.analysis.lint src/` — the CI gate itself."""
    assert (REPO / baseline_mod.BASELINE_NAME).is_file()
    rc = lint.main([str(REPO / "src"),
                    "--baseline", str(REPO / baseline_mod.BASELINE_NAME)])
    out = capsys.readouterr().out
    assert rc == 0, f"averylint found new issues in src/:\n{out}"


def test_committed_baseline_is_near_empty():
    """The grandfather list must not silently grow into a dumping
    ground: every entry needs a justification, and there should be at
    most a handful."""
    data = json.loads((REPO / baseline_mod.BASELINE_NAME).read_text())
    assert len(data["entries"]) <= 5
    for entry in data["entries"]:
        assert entry.get("reason", "").strip() not in ("", "TODO: justify")


def test_host_only_modules_have_no_jax_imports():
    """Belt and braces for AV201: the host-only modules really import
    no jax today (the checker test proves detection; this pins the
    current tree)."""
    for rel in ("engine/scheduler.py", "engine/policy.py",
                "engine/faults.py", "engine/observability.py"):
        text = (REPO / "src" / "repro" / rel).read_text()
        assert "import jax" not in text, rel


def test_observability_checker_granularity():
    """Both AV602 idioms in the bad fixture are caught per attribute,
    and every sanctioned bounding idiom appears in the good fixture."""
    hits = [f for f in _findings(BAD, "observability")
            if f.code == "AV602"]
    assert {f.symbol for f in hits} == {"LeakyDecoder.on_event",
                                        "LeakyDecoder.step"}
    good_src = (GOOD / "repro/engine/observability_cases.py").read_text()
    for idiom in ("deque(maxlen", "len(self.events)", "del self.records",
                  "self.order = remaining", "return sess",
                  "self.queue.pop"):
        assert idiom in good_src


def test_av603_catches_both_import_spellings():
    """AV603 resolves clock calls through the import maps: the aliased
    ``import time as _t`` attribute spelling and the ``from time
    import perf_counter`` name spelling are both caught (exactly the
    AV502 loopholes), while the good fixture's injected-wallclock hook
    and a shadowing local ``perf_counter`` stay clean."""
    hits = [f for f in _findings(BAD, "observability")
            if f.code == "AV603"
            and f.path.endswith("observability_cases.py")]
    assert {f.symbol for f in hits} == {"stamp_response", "measure_step"}
    assert len(hits) == 3          # _t.time, perf_counter, _t.monotonic_ns
    msgs = " ".join(f.message for f in hits)
    for name in ("time.time", "time.perf_counter", "time.monotonic_ns"):
        assert name in msgs
    good_src = (GOOD / "repro/engine/observability_cases.py").read_text()
    assert "wallclock" in good_src and "def perf_counter" in good_src
