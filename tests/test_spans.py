"""The program's names in a profiler trace (``repro.core.spans``): every
jitted stage lowers to an XLA module named after the stage, the in-flight
serve opens only spans of the vocabulary, nested as documented, with
every stage call inside an admission or a step, and the decode and
verify step histograms time the step up to the fetch of its outputs."""
import jax
import numpy as np
import pytest

from repro.core import spans
from repro.core.intent import Intent
from repro.engine import AveryEngine
from repro.engine import inflight as inflight_mod
from repro.engine import speculative as spec_mod
from repro.engine.speculative import SpeculativeConfig

from test_engine import LUT, _edge_requests

PLAIN = ("edge_context", "edge_insight", "bottleneck_encode",
         "cloud_prefix", "pool_write", "cloud_decode_rows",
         "cloud_verify_rows", "cloud_sam_feats", "cloud_mask",
         "cloud_context", "cloud_insight", "cloud_context_gen",
         "cloud_insight_gen")
DRAFT = ("draft_prefill", "draft_step", "draft_insert")
DECODER = ("greedy_pick",)
SHARDED = ("cloud_prefix", "pool_write", "cloud_decode_rows",
           "cloud_verify_rows") + DRAFT
STAGES = ("cloud_prefix", "pool_write", "cloud_decode_rows",
          "cloud_verify_rows", "cloud_sam_feats", "cloud_mask")


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
                              lut=LUT, max_new_tokens=3, flash_decode=False,
                              page_size=4)


def _serve(decoder, reqs):
    for i, (p, q, it) in enumerate(reqs):
        decoder.submit(i, it, p, q, lambda out: None)
    decoder.drain()


@pytest.fixture(scope="module")
def jitted(system):
    """Every jit the serving stack builds while it serves each stage
    once, plain, drafted and sharded on one device, with the arguments
    of its first call: {(family, name): (jitted, args)}."""
    from repro.core.paging import PagePool
    from repro.engine.inflight import InflightDecoder
    from repro.launch.mesh import make_local_mesh
    from repro.sharding.serving import ShardedServingContext

    real_jit, seen = jax.jit, {}

    def recording_jit(fn, **kw):
        jit = real_jit(fn, **kw)
        key = ("sharded" if "in_shardings" in kw else "plain",
               getattr(fn, "__name__", ""))

        def call(*args):
            seen.setdefault(key, (jit, args))
            return jit(*args)
        return call

    spec = SpeculativeConfig(draft_tokens=2)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "jit", recording_jit)
        spec_mod._draft_fns.cache_clear()   # draft jits built afresh
        inflight_mod._greedy_pick_jit.cache_clear()   # and the pick
        ex = _executor(system)
        reqs = _edge_requests(ex, 3, seed=4)
        ctx_req, ins_req = reqs[2], reqs[0]
        for p, q, _ in (ctx_req, ins_req):
            ex.cloud_generate_batch([p], [q])
        ex.cloud_context_batch([ctx_req[0]], [ctx_req[1]])
        ex.cloud_insight_batch([ins_req[0]], [ins_req[1]])
        _serve(InflightDecoder(ex, slots=2), reqs)
        _serve(InflightDecoder(ex, slots=2, spec=spec), reqs)
        sh = ShardedServingContext(ex, make_local_mesh(model=1))
        for s in (None, spec):
            pool = PagePool(page_size=sh.page_size, placement=sh.place_pool,
                            shards=sh.model_shards)
            _serve(InflightDecoder(sh, slots=2, pool=pool, spec=s), reqs)
    spec_mod._draft_fns.cache_clear()
    inflight_mod._greedy_pick_jit.cache_clear()
    return seen


@pytest.mark.parametrize("family,stage",
                         [("plain", s) for s in PLAIN + DRAFT + DECODER]
                         + [("sharded", s) for s in SHARDED])
def test_stage_lowers_to_its_named_module(jitted, family, stage):
    jit, args = jitted[(family, stage)]
    head = jit.lower(*args).as_text().split("\n", 1)[0]
    assert head.startswith(f"module @jit_{stage} "), head


# ---- program spans of an in-flight serve ----


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: every span with
    its args, its parent, and the spans open at each stage call."""

    def __init__(self):
        self.spans, self.stack = [], []

    def annotation(self, name, **args):
        rec = self

        class Span:
            def __init__(self):
                self.name, self.args, self.parent = name, dict(args), None

            def __enter__(self):
                self.parent = rec.stack[-1].name if rec.stack else None
                rec.stack.append(self)
                rec.spans.append(self)
                return self

            def __exit__(self, *exc):
                assert rec.stack.pop() is self, "spans must nest"
                return False

            def set_metadata(self, **kw):
                self.args.update(kw)
        return Span()


class _StageWatch:
    """The executor, with the open spans noted at every stage call
    (draft stages included, through the ``draft_fns`` hook)."""

    def __init__(self, inner, rec):
        self._inner, self._rec, self.calls = inner, rec, []

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name not in STAGES:
            return attr
        return self._watch(name, attr)

    def _watch(self, name, fn):
        def call(*args):
            self.calls.append((name, [s.name for s in self._rec.stack]))
            return fn(*args)
        return call

    def draft_fns(self, pcfg, width, params):
        return tuple(self._watch(n, f) for n, f in zip(
            DRAFT, spec_mod._draft_fns(pcfg, width)))


@pytest.fixture(scope="module", params=["plain", "speculative"])
def served(request, system):
    rec = _Recorder()
    watch = _StageWatch(_executor(system), rec)
    engine = AveryEngine(lut=LUT, executor=watch, batching="inflight",
                         max_batch=2,
                         speculative=request.param == "speculative")
    reqs = _edge_requests(watch._inner, 4, seed=9)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "TraceAnnotation", rec.annotation)
        futs = [engine.submit_packet(p, q, it, time_s=float(i))
                for i, (p, q, it) in enumerate(reqs)]
        engine.pump()
        while not all(f.done() for f in futs):
            engine.pump()
    assert all(f.result().failure is None for f in futs)
    return rec, watch, futs, request.param


def test_spans_are_from_the_vocabulary(served):
    rec, _, _, _ = served
    assert {s.name for s in rec.spans} <= set(spans.SPANS)
    assert {"engine.submit", "engine.pump", "inflight.admit",
            "inflight.step", "inflight.step.inputs", "inflight.step.launch",
            "inflight.step.fetch", "inflight.step.sample",
            "inflight.finish"} <= {s.name for s in rec.spans}


PARENTS = {
    "engine.submit": {None},
    "engine.pump": {None},
    "inflight.admit": {"engine.submit", "engine.pump", "inflight.step"},
    "inflight.step": {"engine.submit", "engine.pump"},
    "inflight.step.inputs": {"inflight.step"},
    "inflight.step.launch": {"inflight.step"},
    "inflight.step.fetch": {"inflight.step"},
    "inflight.step.sample": {"inflight.step"},
    "inflight.finish": {"inflight.step.sample"},
    "inflight.draft": {"inflight.admit", "inflight.step"},
}


def test_children_nest_inside_their_parents(served):
    rec, _, _, _ = served
    for s in rec.spans:
        assert s.parent in PARENTS[s.name], (s.name, s.parent)


def test_stage_calls_sit_inside_an_admission_or_a_step(served):
    _, watch, _, mode = served
    called = {name for name, _ in watch.calls}
    assert {"cloud_prefix", "pool_write", "cloud_sam_feats",
            "cloud_mask"} <= called
    if mode == "speculative":
        assert set(DRAFT) | {"cloud_verify_rows"} <= called
    for name, stack in watch.calls:
        assert "inflight.admit" in stack or "inflight.step" in stack, \
            (name, stack)
        if name in DRAFT:
            assert stack[-1] == "inflight.draft", (name, stack)
    step_calls = [stack for name, stack in watch.calls
                  if name in ("cloud_decode_rows", "cloud_verify_rows")]
    assert step_calls and all(s[-1] == "inflight.step.launch"
                              for s in step_calls)


def test_submit_and_admit_carry_the_request_id(served):
    rec, _, futs, _ = served
    rids = sorted(f.request.request_id for f in futs)
    submits = [s.args["rid"] for s in rec.spans if s.name == "engine.submit"]
    admits = [s for s in rec.spans if s.name == "inflight.admit"]
    finishes = [s.args["rid"] for s in rec.spans
                if s.name == "inflight.finish"]
    assert sorted(submits) == rids
    assert sorted(a.args["rid"] for a in admits) == rids
    assert sorted(finishes) == rids
    assert all(a.args["hit"] in (0, 1) for a in admits)


# ---- the step histograms time the step as the host sees it ----


class _SlowFetch:
    """A step output whose copy to the host advances the fake wall
    clock."""

    def __init__(self, arr, clock):
        self.arr, self.clock = arr, clock

    def __array__(self, dtype=None, copy=None):
        self.clock[0] += 1.0
        return np.asarray(self.arr, dtype)


class _SlowFetchExecutor:
    def __init__(self, inner, clock):
        self._inner, self.clock = inner, clock

    def __getattr__(self, name):
        return getattr(self._inner, name)

    @property
    def step_counts(self):
        # the step's device counters ride the same copy as its greedy
        # ids (the logits stay on the device)
        return {"expert_picks": _SlowFetch(np.int32(0), self.clock)}


@pytest.mark.parametrize("mode,histogram", [("plain", "decode_step_s"),
                                            ("speculative", "verify_step_s")])
def test_step_histogram_includes_the_fetch(system, mode, histogram):
    clock = [0.0]
    ex = _SlowFetchExecutor(_executor(system), clock)
    engine = AveryEngine(lut=LUT, executor=ex, batching="inflight",
                         max_batch=2, wallclock=lambda: clock[0],
                         speculative=mode == "speculative")
    reqs = [r for r in _edge_requests(ex._inner, 3, seed=2)
            if r[2] is Intent.CONTEXT] * 2
    for i, (p, q, it) in enumerate(reqs):
        engine.submit_packet(p, q, it, time_s=float(i))
    engine.drain()
    h = engine.metrics.histogram(histogram)
    assert h.count > 0
    assert h.p50 >= 1.0


# ---- the decoder's totals in stats() ----


def test_decoder_totals_survive_decoder_retirement(system):
    """A drain retires the idle decoder; its totals fold into the
    engine's, so stats() reads the same before and after."""
    ex = _executor(system)
    engine = AveryEngine(lut=LUT, executor=ex, batching="inflight",
                         max_batch=2)
    reqs = _edge_requests(ex, 3, seed=6)
    futs = [engine.submit_packet(p, q, it, time_s=float(i))
            for i, (p, q, it) in enumerate(reqs)]
    while not all(f.done() for f in futs):
        engine.pump()
    keys = [k for k in engine.stats if k.startswith("inflight_")
            and k not in ("inflight_steps", "inflight_cancelled")]
    live = {k: engine.stats[k] for k in keys}
    engine.drain()
    assert not engine._inflight
    assert {k: engine.stats[k] for k in keys} == live
    n_insight = sum(it is Intent.INSIGHT for _, _, it in reqs)
    assert live["inflight_admissions"] == len(reqs)
    assert live["inflight_sam_tails"] == live["inflight_masks"] == n_insight
    assert live["inflight_attended_positions"] > 0
