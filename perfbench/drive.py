"""Set-up and the measured window of one run.

The system under test is ``AveryEngine(batching="inflight")`` with the
compiled kernels, driven through ``submit_packet`` and ``pump``: the
packet has already crossed the link (``LoopbackTransport``, the engine's
default), each UAV is one session under ``StaticTierPolicy``, and the
engine's ``time_s`` is the wall time since the engine was built.

``StageProxy`` sits between the engine and the executor. It counts the
stage calls the per-layer readers need (decode rows and their context
lengths, prefills, SAM tails, masks) and, in traced runs, wraps each in a
``jax.profiler.TraceAnnotation``. It never blocks.

A request's latency runs from its due time to the end of the first
engine call after which its future is done, on ``time.perf_counter``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from perfbench import traffic as tr

# how long past the close an open-loop run waits for answers still due
DRAIN_LIMIT_S = 60.0


def span(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


class StageProxy:
    """The executor as the engine sees it, with counts and spans."""

    def __init__(self, inner, traced: bool = False):
        self._inner = inner
        self.traced = traced
        self.recording = False
        self.counts = self._empty()

    @staticmethod
    def _empty() -> Dict[str, Any]:
        return {"decode_ctx": [], "prefill_len": [], "sam_rank": [],
                "mask": 0}

    def reset(self) -> None:
        self.counts = self._empty()

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def cloud_prefix(self, ctx, query):
        if self.recording:
            self.counts["prefill_len"].append(
                int(np.shape(ctx)[-2]) + int(np.shape(query)[-1]))
        with span("cloud_prefix", self.traced):
            return self._inner.cloud_prefix(ctx, query)

    def pool_write(self, pool, paged_kv, page_ids):
        with span("pool_write", self.traced):
            return self._inner.pool_write(pool, paged_kv, page_ids)

    def cloud_decode_rows(self, pool, page_table, positions, tokens, pos,
                          write_slot):
        if self.recording:
            # a live row has a page other than the trash page (id 0);
            # it attends its cached positions and the token it feeds
            pt, ps = np.asarray(page_table), np.asarray(positions)
            live = (pt != 0).any(axis=1)
            self.counts["decode_ctx"].append(
                ((ps[live] >= 0).sum(axis=1) + 1).tolist())
        with span("cloud_decode_rows", self.traced):
            return self._inner.cloud_decode_rows(pool, page_table, positions,
                                                 tokens, pos, write_slot)

    def cloud_sam_feats(self, packet):
        if self.recording:
            self.counts["sam_rank"].append(
                int(packet.content["codes"].shape[-1]))
        with span("cloud_sam_feats", self.traced):
            return self._inner.cloud_sam_feats(packet)

    def cloud_mask(self, feats, seg):
        if self.recording:
            self.counts["mask"] += 1
        with span("cloud_mask", self.traced):
            return self._inner.cloud_mask(feats, seg)


class CompileCounter:
    """Traces and backend compiles JAX reports while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if self.on and event in self.EVENTS:
            self.n += 1


@dataclasses.dataclass
class Record:
    """One request as the benchmark sent it and as it came back."""
    uav: int
    intent: str
    tier: Optional[str]
    frame: int
    query: np.ndarray
    due: float                        # perf_counter seconds
    sent: float
    future: Any = None
    done: Optional[float] = None
    tokens: Optional[np.ndarray] = None
    logits0: Optional[np.ndarray] = None
    mask: Optional[np.ndarray] = None
    failure: Optional[str] = None


def model_mesh(devs):
    """The program's serving mesh (``launch.mesh.make_local_mesh``) with
    every one of ``devs`` on its "model" axis."""
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(model=len(devs))
    if set(mesh.devices.flat) != set(devs):
        raise SystemExit(f"a {len(devs)}-chip cell needs a host of "
                         f"{len(devs)} chips; the mesh spans {mesh.size}")
    return mesh


def build_engine(pcfg, params, bottlenecks, mix, uavs, traced: bool,
                 mesh=None):
    """The executor with the compiled kernels, behind the proxy, in an
    in-flight engine with one session per UAV; with ``mesh``, the
    engine's sharded serving (``AveryEngine(mesh=...)``).

    Options the program cannot choose itself are set here, from shapes:
    the page pool is sized for every slot's prefix and answer pages plus
    the prefix store's, so it never grows (a growth recompiles every
    pool-shaped stage), and the store keeps as many prefixes as there
    are slots. Slots, page size and kernels are the program's defaults.
    """
    from repro.core import DualStreamExecutor
    from repro.core.lut import paper_lut
    from repro.engine import AveryEngine, StaticTierPolicy
    lut = paper_lut()
    executor = DualStreamExecutor(pcfg, params, bottlenecks, lut,
                                  max_new_tokens=int(mix["answer_len"]))
    page = executor.page_size
    slots = AveryEngine.__init__.__kwdefaults__["max_batch"]
    n_prefix = -(-(pcfg.clip_tokens + int(mix["query_len"])) // page)
    n_answer = -(-int(mix["answer_len"]) // page)
    kv_pages = 1 + (2 * slots + 1) * n_prefix + slots * n_answer
    engine = AveryEngine(lut=lut, executor=executor, batching="inflight",
                         kv_pages=kv_pages, max_prefixes=slots, mesh=mesh)
    # the proxy goes between the engine and what it serves through (with
    # a mesh, the sharded context the engine put around the executor);
    # the engine makes its decoders from ``executor`` when first asked
    proxy = StageProxy(engine.executor, traced=traced)
    engine.executor = proxy
    default_tier = lut.tiers[0].name
    sessions = [engine.session(u.name,
                               policy=StaticTierPolicy(u.tier or default_tier))
                for u in uavs]
    return engine, proxy, sessions


class Driver:
    """Issues requests into the engine and watches their futures."""

    def __init__(self, engine, sessions, uavs, frames, source, traced,
                 t_base: float):
        from repro.core import packets as pk
        from repro.core.intent import Intent
        self._pk, self._intent = pk, Intent
        self.engine, self.sessions, self.uavs = engine, sessions, uavs
        self.frames, self.source, self.traced = frames, source, traced
        self.t_base = t_base
        self.records: List[Record] = []
        self.outstanding: List[Record] = []
        self.finished: List[Record] = []   # stamped, not yet taken
        self._seq = 0

    def now(self) -> float:
        return time.perf_counter()

    def issue(self, uav: int, due: float) -> Record:
        u = self.uavs[uav]
        pick = self.source.next()
        key = "context" if u.intent == "context" else u.tier
        f = self.frames[key][pick["frame"] % len(self.frames[key])]
        t_eng = max(0.0, self.now() - self.t_base)
        if u.intent == "context":
            packet = self._pk.make_context_packet(self._seq, t_eng, f["ctx"])
            intent = self._intent.CONTEXT
        else:
            packet = self._pk.make_insight_packet(
                self._seq, t_eng, u.tier, f["codes"], f["scales"],
                clip_feats=f["clip"])
            intent = self._intent.INSIGHT
        self._seq += 1
        rec = Record(uav, u.intent, u.tier, pick["frame"], pick["query"],
                     due=due, sent=self.now())
        with span("submit", self.traced):
            rec.future = self.engine.submit_packet(
                packet, pick["query"], intent, time_s=t_eng,
                session=self.sessions[uav])
        self.records.append(rec)
        self.outstanding.append(rec)
        self.collect()
        return rec

    def pump(self) -> None:
        with span("pump", self.traced):
            self.engine.pump()
        self.collect()

    def take_finished(self) -> List[Record]:
        """Requests stamped done since the last call."""
        done, self.finished = self.finished, []
        return done

    def collect(self) -> None:
        """Stamp every request whose future resolved since the last look
        (a submit can finish others: it runs a decode step)."""
        t = self.now()
        left = []
        for rec in self.outstanding:
            if rec.future.done():
                resp = rec.future.result()
                rec.done = t
                rec.failure = resp.failure
                if resp.failure is None:
                    rec.tokens = np.asarray(resp.tokens)
                    rec.logits0 = np.asarray(resp.answer_logits)
                    if resp.mask_logits is not None:
                        rec.mask = np.asarray(resp.mask_logits, np.float32)
                rec.future = None
                self.finished.append(rec)
            else:
                left.append(rec)
        self.outstanding = left

    def wait(self, until: float) -> None:
        with span("generator_wait", self.traced):
            dt = until - self.now()
            if dt > 0:
                time.sleep(dt)


def warm_up(driver: Driver, uavs) -> None:
    """One request of each kind the fleet sends, served to the end: every
    stage compiles at the cell's shapes and the page pool is made."""
    seen = set()
    for i, u in enumerate(uavs):
        if (u.intent, u.tier) not in seen:
            seen.add((u.intent, u.tier))
            driver.issue(i, driver.now())
    while driver.outstanding:
        driver.pump()
    driver.records.clear()
    driver.take_finished()


def run_closed(driver: Driver, mix, seconds: float, on_open, on_close):
    """Closed loop: every client sends again as its answer returns. A
    pre-roll of ``preroll_answers`` answers brings the batch to its steady
    state before the window opens. Returns (t_open, t_close)."""
    n = len(driver.uavs)
    for i in range(n):
        driver.issue(i, driver.now())
    answered = 0
    while answered < int(mix["preroll_answers"]):
        done = driver.take_finished()
        if not done:
            driver.pump()
        for rec in done:
            answered += 1
            driver.issue(rec.uav, driver.now())
    driver.records = list(driver.outstanding)
    t_open = on_open()
    t_close = t_open + seconds
    while driver.now() < t_close:
        # a submit runs a decode step and can finish other requests:
        # their clients send again before the next pump
        done = driver.take_finished()
        if not done:
            driver.pump()
        for rec in done:
            if rec.done < t_close:
                driver.issue(rec.uav, rec.done)
    on_close()
    return t_open, t_close


def run_open(driver: Driver, schedule: List[tr.Due], seconds: float,
             on_open, on_close):
    """Open loop: requests go in when due, late if the engine was busy;
    after the last is due, the engine serves what is left, for at most
    ``DRAIN_LIMIT_S``. Returns (t_open, t_close)."""
    t_open = on_open()
    t_close = t_open + seconds
    i = 0
    while True:
        now = driver.now()
        while i < len(schedule) and t_open + schedule[i].t <= now:
            driver.issue(schedule[i].uav, t_open + schedule[i].t)
            i += 1
            now = driver.now()
        if driver.outstanding:
            driver.pump()
        elif i < len(schedule):
            driver.wait(t_open + schedule[i].t)
        else:
            break
        if now > t_close + DRAIN_LIMIT_S:
            break
    on_close()
    return t_open, t_close
