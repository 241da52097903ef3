"""The program's own spans, stage modules and decoder totals, read beside
the benchmark's: the reduction of a small recorded trace, the span
events of a real traced serve, and the decoder's totals against the
stage proxy's counts."""
import time

import jax
import pytest

from perfbench import drive, model, program_spans, trace
from perfbench import traffic as tr
from perfbench.tests import tiny
from perfbench.tests.test_perfbench_trace import _trace


def _program():
    # one decode step inside Driver.pump's span (its launch holds the
    # benchmark's cloud_decode_rows span), then one admission inside
    # Driver.issue's submit span
    return {
        "modules": [[(100, 400, "jit_cloud_decode_rows"),
                     (600, 700, "jit_cloud_prefix")]],
        "spans": [
            (70, 540, "engine.pump", {}),
            (80, 530, "inflight.step", {}),
            (80, 88, "inflight.step.inputs", {}),
            (88, 425, "inflight.step.launch", {}),
            (425, 480, "inflight.step.fetch", {}),
            (480, 530, "inflight.step.sample", {}),
            (565, 715, "engine.submit", {"rid": 7}),
            (570, 712, "inflight.admit", {"rid": 7, "hit": 0}),
        ],
        "lines": {},
    }


def test_the_benchmarks_reduction_reads_as_before():
    red = trace.reduce(_trace(), 0, 1000, {"paged_decode": "paged_decode"})
    assert red["busy_s"] == pytest.approx(400e-9)
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"outside_spans": 100e-9, "pump": 200e-9, "generator_wait": 300e-9})


def test_decode_device_time_per_step():
    red = program_spans.reduce(_trace(), _program(), 0, 1000, steps=1)
    assert red["decode_device_ms"] == pytest.approx(300e-6)
    assert dict(red["modules"]) == pytest.approx(
        {"jit_cloud_decode_rows": 300e-9, "jit_cloud_prefix": 100e-9})


def test_step_host_gap_is_the_idle_inside_steps():
    # idle 80..100 and 400..530 fall inside the step span
    red = program_spans.reduce(_trace(), _program(), 0, 1000, steps=1)
    assert red["step_host_gap_ms"] == pytest.approx(150e-6)
    red = program_spans.reduce(_trace(), _program(), 0, 1000, steps=3)
    assert red["step_host_gap_ms"] == pytest.approx(50e-6)


def test_admit_is_the_mean_admission_starting_in_the_window():
    red = program_spans.reduce(_trace(), _program(), 0, 1000, steps=1)
    assert red["admit_ms"] == pytest.approx(142e-6)
    red = program_spans.reduce(_trace(), _program(), 0, 560, steps=1)
    assert red["admit_ms"] is None


def test_an_idle_gap_in_a_step_is_named_by_the_innermost_span():
    red = program_spans.reduce(_trace(), _program(), 0, 1000, steps=1)
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"outside_spans": 100e-9,
                                  "inflight.step.sample": 200e-9,
                                  "generator_wait": 300e-9})
    # idle inside a program span: 70..100, 400..540, 565..600, 700..715
    assert red["program_idle_share"] == pytest.approx(100.0 * 220 / 600)


def test_idle_is_split_at_span_boundaries():
    red = program_spans.reduce(_trace(), _program(), 0, 1000, steps=1,
                               top=20)
    split = {n: 1e9 * s for n, s in red["idle_split"]}
    assert split == pytest.approx({
        "outside_spans": 80, "pump": 20, "engine.pump": 20,
        "inflight.step.inputs": 8, "inflight.step.launch": 7,
        "cloud_decode_rows": 30, "inflight.step.fetch": 55,
        "inflight.step.sample": 50, "submit": 10, "engine.submit": 8,
        "inflight.admit": 12, "cloud_prefix": 30, "generator_wait": 270})
    assert sum(split.values()) == pytest.approx(600)
    name, seconds, start = red["largest_gaps"][0]
    assert (name, seconds, start) == ("generator_wait",
                                      pytest.approx(300e-9),
                                      pytest.approx(700e-9))


def test_innermost_segments_cover_the_window():
    spans = [(10, 50, "a"), (20, 30, "b"), (60, 200, "c")]
    assert program_spans.innermost_segments(spans, 0, 100) == [
        (0, 10, "outside_spans"), (10, 20, "a"), (20, 30, "b"),
        (30, 50, "a"), (50, 60, "outside_spans"), (60, 100, "c")]


def test_a_trace_without_program_names_reads_nothing():
    empty = {"modules": [[]], "spans": [], "lines": {}}
    red = program_spans.reduce(_trace(), empty, 0, 1000, steps=1)
    assert red["decode_device_ms"] is None
    assert red["step_host_gap_ms"] is None
    assert red["admit_ms"] is None
    assert red["program_idle_share"] is None
    # the gaps are then named by the benchmark's spans alone
    assert dict(red["idle_gaps"]) == pytest.approx(
        dict(trace.reduce(_trace(), 0, 1000, {})["idle_gaps"]))


def test_overlap_of_sorted_intervals():
    assert program_spans.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert program_spans.overlap([(0, 10)], [(10, 20)]) == 0
    assert program_spans.overlap([], [(0, 1)]) == 0


def test_module_names_drop_the_program_id():
    assert program_spans.module_name("jit_cloud_decode_rows(123)") == \
        "jit_cloud_decode_rows"
    assert program_spans.module_name("jit_pool_write") == "jit_pool_write"


# ---- a real tiny serve, traced on the CPU ----


def _serve(mix, rounds, seed=2**31 + 5, profile_dir=None):
    """A tiny engine behind the stage proxy, ``rounds`` requests from
    every UAV of ``mix``, served to the end."""
    pcfg = model.pipeline_config(tiny.CONFIG)
    uavs = tr.fleet(mix)
    tiers = sorted({u.tier for u in uavs if u.tier})
    params, bottlenecks = model.make_weights(
        pcfg, tiny.CONFIG["bottleneck_tiers"], seed)
    frames = model.make_frames(pcfg, bottlenecks, tiers,
                               int(mix["frame_pool"]), seed)
    engine, proxy, sessions = drive.build_engine(pcfg, params, bottlenecks,
                                                 mix, uavs, traced=False)
    proxy.recording = True
    source = tr.RequestSource(mix, pcfg.llm.vocab_size,
                              int(mix["frame_pool"]), seed)
    driver = drive.Driver(engine, sessions, uavs, frames, source, False,
                          t_base=time.perf_counter())
    if profile_dir is not None:
        jax.profiler.start_trace(profile_dir)
    for _ in range(rounds):
        for i in range(len(uavs)):
            driver.issue(i, driver.now())
    while driver.outstanding:
        driver.pump()
    if profile_dir is not None:
        jax.profiler.stop_trace()
    return engine, proxy, driver


def test_program_spans_come_back_with_their_args(tmp_path):
    """On a recorded serve the benchmark's loader keeps its own spans
    alone, and the program's admission and submit spans carry the
    request's rid as an event stat: each admission joins its submit."""
    _, _, driver = _serve(tiny.CLOSED, 1, profile_dir=str(tmp_path))
    base = trace.load(str(tmp_path))
    assert {h[2] for h in base["host"]} <= set(trace.HOST_SPANS)
    prog = program_spans.load(str(tmp_path))
    names = {name for _, _, name, _ in prog["spans"]}
    assert {"engine.submit", "engine.pump", "inflight.admit",
            "inflight.step", "inflight.step.fetch"} <= names
    submits = {a["rid"]: s for s, _, name, a in prog["spans"]
               if name == "engine.submit"}
    admits = [(s, e, a) for s, e, name, a in prog["spans"]
              if name == "inflight.admit"]
    assert len(admits) == len(driver.records) == len(submits)
    for s, e, a in admits:
        assert a["hit"] in (0, 1)
        assert submits[a["rid"]] <= s < e


# ---- the decoder's totals against the stage proxy's counts ----


FLEETS = {"prefix-miss": (tiny.CLOSED, 3), "mixed": (tiny.OPEN, 2)}

TOTALS = {
    "inflight_steps": lambda c: len(c["decode_ctx"]),
    "live_rows": lambda c: sum(map(len, c["decode_ctx"])),
    "inflight_attended_positions": lambda c: sum(map(sum, c["decode_ctx"])),
    "inflight_prefix_misses": lambda c: len(c["prefill_len"]),
    "inflight_prefill_tokens": lambda c: sum(c["prefill_len"]),
    "inflight_sam_tails": lambda c: len(c["sam_rank"]),
    "inflight_masks": lambda c: c["mask"],
}


@pytest.fixture(scope="module", params=sorted(FLEETS))
def served(request):
    mix, rounds = FLEETS[request.param]
    engine, proxy, _ = _serve(mix, rounds)
    stats = dict(engine.stats)
    stats["live_rows"] = round(stats["inflight_steps"]
                               * stats["mean_live_slots"])
    return request.param, stats, proxy.counts


@pytest.mark.parametrize("key", sorted(TOTALS))
def test_decoder_totals_match_the_stage_proxy(served, key):
    fleet, stats, counts = served
    assert counts["decode_ctx"], "the serve ran no decode step"
    if fleet == "mixed":
        assert counts["sam_rank"] and counts["mask"]
    assert stats[key] == TOTALS[key](counts)
