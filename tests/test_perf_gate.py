"""The perf-regression gate: metric classification (wall-clock metrics
never gate), the diff budgets (direction + tolerance, zero-tolerance
leaks, missing rows/metrics), CLI exit codes, the baseline update
round-trip, and the bench artifact writer."""
import copy
import importlib.util
import json
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location(
    "perf_gate", REPO / "scripts" / "perf_gate.py")
perf_gate = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(perf_gate)


BENCH = {
    "serving/alpha": {
        "us": 1000.0, "req_s": 150.0, "ttft_p50_s": 0.01,
        "ttft_p99_s": 0.5, "page_leaks": 0.0, "seed": 0.0,
        "delivered_under_slo": 0.96, "note": "free-text",
    },
    "serving/beta": {
        "us": 2000.0, "speedup_vs_paged": 1.4, "acceptance_rate": 0.8,
    },
}


def _dump(path, records):
    path.write_text(json.dumps(
        {"benchmark": "BENCH_serving", "records": records}, indent=2))
    return str(path)


# ---- classification ----


@pytest.mark.parametrize("metric,kind", [
    ("us", "ignore"), ("ttft_p50_s", "lower"), ("wait_p95_us", "lower"),
    ("admit_req_s", "ignore"),
    ("req_s", "ignore"), ("delivered_under_slo", "higher"),
    ("jain", "higher"), ("speedup_vs_paged", "ignore"),
    ("page_leaks", "zero"),
    ("seed", "ignore"), ("note", "ignore"), ("compile_events", "ignore"),
    ("ledger_flops_total", "ignore"), ("some_unknown_counter", "ignore"),
])
def test_classify(metric, kind):
    assert perf_gate.classify(metric) == kind


# ---- compare(): budgets and directions ----


def test_identical_bench_is_clean():
    regs, infos = perf_gate.compare(BENCH, BENCH, 0.5, 0.05)
    assert regs == [] and infos == []


def test_timing_regression_beyond_tolerance():
    bench = copy.deepcopy(BENCH)
    bench["serving/alpha"]["ttft_p99_s"] = 5.0      # 10x the baseline
    regs, _ = perf_gate.compare(bench, BENCH, 0.5, 0.05)
    assert len(regs) == 1 and "serving/alpha.ttft_p99_s" in regs[0]
    # ...but within the budget it's noise, not a regression
    bench["serving/alpha"]["ttft_p99_s"] = 0.7      # +40% < +50%
    regs, _ = perf_gate.compare(bench, BENCH, 0.5, 0.05)
    assert regs == []


def test_quality_drop_beyond_tolerance():
    bench = copy.deepcopy(BENCH)
    bench["serving/alpha"]["delivered_under_slo"] = 0.5
    bench["serving/beta"]["acceptance_rate"] = 0.79  # -1.25% < -5%
    regs, _ = perf_gate.compare(bench, BENCH, 0.5, 0.05)
    assert len(regs) == 1
    assert "serving/alpha.delivered_under_slo" in regs[0]


@pytest.mark.parametrize("metric", [
    "us", "req_s", "admit_req_s", "tok_s", "decode_tok_s",
    "speedup_vs_paged", "speedup_vs_no_prefix_reuse", "vs_unsharded",
])
def test_wall_clock_metrics_never_gate(metric):
    """The bench runs on the CPU: a wall-clock metric moved 10x either
    way (the wrong one included) is not a regression."""
    base = copy.deepcopy(BENCH)
    base["serving/alpha"][metric] = 100.0
    for factor in (10.0, 0.1):
        bench = copy.deepcopy(base)
        bench["serving/alpha"][metric] = 100.0 * factor
        regs, _ = perf_gate.compare(bench, base, 0.5, 0.05)
        assert regs == [], (factor, regs)


def test_page_leak_is_zero_tolerance():
    bench = copy.deepcopy(BENCH)
    bench["serving/alpha"]["page_leaks"] = 1.0
    regs, _ = perf_gate.compare(bench, BENCH, 100.0, 1.0)
    assert len(regs) == 1 and "page_leaks" in regs[0]


def test_missing_row_and_metric_are_regressions():
    bench = copy.deepcopy(BENCH)
    del bench["serving/beta"]
    del bench["serving/alpha"]["ttft_p99_s"]
    regs, _ = perf_gate.compare(bench, BENCH, 0.5, 0.05)
    assert any("serving/beta: row missing" in r for r in regs)
    assert any("ttft_p99_s: metric missing" in r for r in regs)
    # ignored metrics going missing is fine (they were never gated)
    bench2 = copy.deepcopy(BENCH)
    del bench2["serving/alpha"]["seed"]
    regs, _ = perf_gate.compare(bench2, BENCH, 0.5, 0.05)
    assert regs == []


def test_new_rows_and_metrics_are_informational():
    bench = copy.deepcopy(BENCH)
    bench["serving/gamma"] = {"us": 5.0}
    bench["serving/alpha"]["thr_p50_s"] = 1.0
    regs, infos = perf_gate.compare(bench, BENCH, 0.5, 0.05)
    assert regs == []
    assert any("serving/gamma: new row" in i for i in infos)
    assert any("thr_p50_s: new metric" in i for i in infos)


# ---- the CLI: exit codes, baseline round-trip, JSON report ----


def test_main_exit_codes(tmp_path, capsys):
    bench = _dump(tmp_path / "bench.json", BENCH)
    base = _dump(tmp_path / "base.json", BENCH)
    assert perf_gate.main(["--bench", bench, "--baseline", base]) == 0
    assert "clean" in capsys.readouterr().out
    worse = copy.deepcopy(BENCH)
    worse["serving/alpha"]["ttft_p99_s"] = 5.0
    bad = _dump(tmp_path / "bad.json", worse)
    assert perf_gate.main(["--bench", bad, "--baseline", base]) == 1
    assert "REGRESSION" in capsys.readouterr().out
    # a raised tolerance admits the same diff
    assert perf_gate.main(["--bench", bad, "--baseline", base,
                           "--tolerance", "10.0"]) == 0
    capsys.readouterr()
    missing = str(tmp_path / "nope.json")
    assert perf_gate.main(["--bench", missing, "--baseline", base]) == 2
    assert perf_gate.main(["--bench", bench, "--baseline", missing]) == 2


def test_update_baseline_roundtrip(tmp_path, capsys):
    worse = copy.deepcopy(BENCH)
    worse["serving/alpha"]["ttft_p99_s"] = 5.0
    bench = _dump(tmp_path / "bench.json", worse)
    base = str(tmp_path / "base.json")
    # an intentional perf change: admit the new numbers, gate is clean
    assert perf_gate.main(["--bench", bench, "--baseline", base,
                           "--update-baseline"]) == 0
    capsys.readouterr()
    assert perf_gate.main(["--bench", bench, "--baseline", base]) == 0
    written = json.loads(Path(base).read_text())["records"]
    assert written["serving/alpha"]["ttft_p99_s"] == 5.0
    # wall-clock metrics never enter the baseline
    assert "us" not in written["serving/alpha"]
    assert "speedup_vs_paged" not in written["serving/beta"]


def test_json_report_and_history(tmp_path, capsys):
    bench = _dump(tmp_path / "bench.json", BENCH)
    base = _dump(tmp_path / "base.json", BENCH)
    assert perf_gate.main(["--bench", bench, "--baseline", base,
                           "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["ok"] is True and report["regressions"] == []
    assert sorted(tmp_path.iterdir()) == [tmp_path / "base.json",
                                          tmp_path / "bench.json"]


def test_write_bench_json_writes_only_its_artifact(tmp_path, monkeypatch):
    """The bench artifact lives under ``benchmarks/artifacts/`` (git
    ignores it): rows merge into it by name, and nothing else — no
    tracked file at the repo root — is written."""
    spec = importlib.util.spec_from_file_location(
        "bench_common", REPO / "benchmarks" / "common.py")
    common = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(common)
    art = tmp_path / "repo" / "benchmarks" / "artifacts"
    monkeypatch.setattr(common, "ART", str(art))
    path = common.write_bench_json(["serving/full,9,req_s=100.0"])
    assert Path(path) == art / "BENCH_serving.json"
    common.write_bench_json(["serving/smoke,5,req_s=42.0"])
    common.write_bench_json(["serving/smoke,5,req_s=43.0"])
    records = json.loads(Path(path).read_text())["records"]
    assert set(records) == {"serving/full", "serving/smoke"}
    assert records["serving/full"] == {"us": 9.0, "req_s": 100.0}
    assert records["serving/smoke"]["req_s"] == 43.0
    written = [p for p in tmp_path.rglob("*") if p.is_file()]
    assert written == [Path(path)]


def test_committed_baseline_holds_no_wall_clock_metric():
    """The committed baseline gates counts and mission-clock numbers
    only: no row carries a metric read off the host's wall clock."""
    baseline = perf_gate.load_bench(str(REPO / "BENCH_baseline.json"))
    assert baseline
    clocked = [f"{name}.{m}" for name, row in baseline.items()
               for m in row if perf_gate.WALL_CLOCK.fullmatch(m)]
    assert clocked == []
