"""Token-level continuous batching: the in-flight decode batch over a
paged, shared-prefix KV cache.

The ``MicrobatchScheduler`` closes a microbatch before serving it — a
request that arrives one step after a generate batch launched waits for
the whole batch. The ``InflightDecoder`` removes that barrier: between
any two decode steps a newly arrived request is prefilled into a free
slot and rides the remaining steps of the running batch (ROADMAP
"in-flight batching", the vLLM-style continuous batching discipline).

KV is **paged** (``core.paging``): each slot addresses the shared page
pool through a per-row page table instead of owning a contiguous
``width`` ring. Admission is keyed on prefix reuse — the ``[ctx; query]``
prefix is content-hashed per operator, the first frame pays the LLM
prefill and pins read-only prefix pages, and every repeat-prefix frame
(successive frames of one UAV under a standing query) maps the same
pages plus fresh private decode pages and skips the prefill entirely.
So N UAVs x M frames pay N prefix prefills, and slot KV memory scales
with distinct prefixes + live decode tokens, not slots x width.

Per slot lifecycle (mirroring ``vlm.llm_generate``'s seg convention):
prefix prefill (or store hit) emits token 0; each lockstep decode step
feeds the slot's last token at its own position into its own write slot;
after ``T`` steps the slot's final step has read the <SEG> hidden state
at the last generated token, the mask decodes from the per-frame SAM
features (always computed — frames differ even when the prefix repeats),
and the slot's private pages free for reuse. Slots may mix tiers and
intents; Context requests ride the same T decode steps as Insight ones,
matching ``cloud_generate_batch`` exactly (the equivalence tests pin
token-level parity, including under slot reuse).

One decoder serves one query length (page tables are fixed-shape per
qlen); decoders on one engine share one ``PagePool``, so prefix pages
cached by a retired decoder stay warm for its successors.

Admission order is pluggable (``engine.scheduler``): the default
``FifoScheduler`` reproduces the historical single-deque behavior;
``QoSScheduler`` adds intent-aware classes, weighted-fair + strict-
priority pops, bounded queues, and preemption — an urgent queued
request parks the lowest-ranked active decode (pages rolled back, its
generated tokens carried along) and the victim later resumes token-
exactly by replaying them from its prefix. Expired deadlines resolve
at the admission boundary, before any prefill is paid.

With a ``SpeculativeConfig`` the decoder runs the draft/verify loop
(``engine.speculative``): each pump step first lets the Context-stream
``DraftModel`` propose k tokens per speculating row, then scores every
row's chunk — its last accepted token plus the drafts, plain rows a
chunk of one — through the serving model in a single paged multi-token
pass (``cloud_verify_rows``). Greedy acceptance advances each row by
1..k+1 tokens per step; decode pages are allocated ahead for the draft
overhang and rolled back past the accepted length on rejection
(``PagePool.grow_to``/``rollback_to``), and the acceptance-rate stats
feed the control policy's drafting gate. Output is token-exact with the
plain path (and with ``llm_generate``) by construction — a draft is
accepted only where it equals the serving model's own greedy pick.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass, fields
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packets as pk
from repro.core.intent import Intent
from repro.core.paging import (TRASH_PAGE, PagePool, pages_for,
                               prefix_digest, prefix_positions)
from repro.core.spans import named_stage, span
from repro.engine.faults import CloudStageError
from repro.engine.observability import Tracer
from repro.engine.scheduler import FifoScheduler, qos_class
from repro.engine.speculative import (DraftModel, SpecStats,
                                      SpeculativeConfig, greedy_accept)


@dataclass
class DecodeTotals:
    """Running totals of the device work one decoder launched (the
    engine folds retired decoders' totals into its own, like
    ``SpecStats``). Each is one integer, bumped where the work is
    launched, so the totals stay bounded however long a mission runs."""
    attended_positions: int = 0  # per fed token: the pos + 1 it attends
    admissions: int = 0      # requests admitted into a slot
    prefix_misses: int = 0   # admissions that ran the prefix prefill
    prefill_tokens: int = 0  # prefix tokens those prefills ran
    sam_tails: int = 0       # SAM tails run (Insight admissions)
    masks: int = 0           # masks decoded
    # a trunk with a dropless MoE share (``moe.moe_held``), counted on the
    # device by the decode/verify stage over its live tokens (not idle
    # rows or pad entries), all MoE layers: routed picks that landed on
    # held experts, and held (layer, expert) pairs with at least one pick
    expert_picks: int = 0
    experts_hit: int = 0
    # bytes the steps copied back to the host: greedy ids, counters, and
    # the <SEG> states of a step on which a row may finish
    fetched_bytes: int = 0

    def add_counts(self, counts: Dict[str, Any]) -> None:
        """Fold one step's device counters (named as the fields) in."""
        for name, value in counts.items():
            setattr(self, name, getattr(self, name) + int(value))

    def merge(self, other: "DecodeTotals") -> None:
        for f in fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))

    def as_dict(self) -> Dict[str, int]:
        return {f"inflight_{f.name}": getattr(self, f.name)
                for f in fields(self)}


def greedy_pick(logits):
    """The greedy token of each row over the last axis: ``(slots, V)``
    logits give ``(slots,)`` ids, a verify step's ``(slots, C, V)`` give
    ``(slots, C)``. Ties go to the first maximum, as ``np.argmax``."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _greedy_pick_jit():
    """One jitted pick for every decoder: decoders retire on
    ``engine.drain()``, and a fresh ``jax.jit`` each would recompile it
    on every burst (as ``speculative._draft_fns``)."""
    return jax.jit(named_stage("greedy_pick", greedy_pick))


@dataclass
class _PendingRequest:
    seq_id: int
    intent: Intent
    packet: pk.Packet
    query: np.ndarray
    on_done: Callable[[Dict[str, Any]], None]
    operator_id: str = ""
    speculative: Optional[bool] = None   # None -> decoder default
    # scheduling state (see engine.scheduler)
    priority: int = 0                 # strict band; higher admits first
    deadline: Optional[float] = None  # mission-clock expiry
    t_enqueue: float = 0.0            # when this wait segment started
    queue_wait: float = 0.0           # total time queued (all segments)
    resumes: int = 0                  # times parked by preemption
    resume_tokens: Optional[List[int]] = None  # generated-so-far tokens
    t_first_token: Optional[float] = None  # first admission (TTFT anchor)


@dataclass
class _SlotState:
    req: _PendingRequest
    tokens: List[int]                 # greedy answer tokens so far
    logits0: np.ndarray               # (1, V) first-token logits
    feats: Optional[Any]              # (1, T_sam, d_sam) or None (context)
    pos: int                          # absolute position of the next token
    joined_step: int                  # global step index at admission
    prefix_ids: Tuple[int, ...]       # shared prefix pages (one ref held)
    private_ids: List[int]            # this slot's decode pages
    prefix_hit: bool
    speculative: bool = False         # drafting enabled for this row
    seg: Optional[np.ndarray] = None  # <SEG> state once the final token fed
    steps_done: int = 0
    batch_acc: int = 0                # sum of co-active slots over steps
    replay: Optional[Deque[int]] = None  # parked tokens to re-decode
    t_admit: float = 0.0              # this residency segment's start
    flops: float = 0.0                # attributed cloud FLOPs (cost ledger)
    hbm_bytes: float = 0.0            # attributed HBM traffic (cost ledger)


class InflightDecoder:
    """Drives the executor's paged in-flight stages over a fixed slot
    layout.

    One decoder serves one query length (the prefill shape); the engine
    keys decoders by qlen the same way the microbatch scheduler keys
    batches. ``submit`` admits into a free slot immediately (prefix
    lookup/prefill + page allocation); ``step`` advances every live slot
    one token; ``drain`` runs admission + steps until no work remains.
    """

    def __init__(self, executor, slots: int = 8,
                 pool: Optional[PagePool] = None,
                 spec: Optional[SpeculativeConfig] = None,
                 spec_gate: Optional[Callable[[SpecStats], bool]] = None,
                 spec_prefix_rows: Optional[Dict[Any, Any]] = None,
                 scheduler: Optional[Any] = None,
                 clock: Optional[Callable[[], float]] = None,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[Any] = None,
                 wallclock: Optional[Callable[[], float]] = None,
                 profiler: Optional[Any] = None,
                 cost: Optional[Any] = None):
        self.executor = executor
        # the step's greedy pick, run on the logits the stage returned so
        # only ids come back to the host; an attribute, so the recompile
        # census (``analysis.sanitizers``) walks it with the decoder
        self.greedy_pick = _greedy_pick_jit()
        # device-level observability (engine.profiler): the profiler
        # wraps lazily built draft models; the cost model attributes
        # analytic FLOPs/HBM bytes to each request as it decodes
        self._profiler = profiler
        self._cost = cost
        # observability (engine.observability): the engine threads its
        # tracer/registry through; a standalone decoder records nothing
        self.tracer = tracer if tracer is not None else Tracer()
        self._metrics = metrics
        self._wallclock = wallclock
        # admission policy (engine.scheduler): the engine passes a
        # per-decoder spawn sharing fleet-wide telemetry/rate buckets;
        # standalone decoders default to plain FIFO
        self.scheduler = scheduler if scheduler is not None \
            else FifoScheduler()
        self._clock = clock or (lambda: 0.0)
        self.slots = int(slots)
        self.T = int(executor.max_new_tokens)
        self.pool = pool if pool is not None else PagePool(
            page_size=executor.page_size)
        if self.pool.page_size != executor.page_size:
            raise ValueError(
                f"pool page_size {self.pool.page_size} != executor "
                f"page_size {executor.page_size}")
        # speculative decoding: config + the policy's drafting gate; the
        # DraftModel is built lazily once the prefix geometry is known
        self.spec = spec
        self.spec_gate = spec_gate or (lambda stats: True)
        self.spec_stats = SpecStats()
        # engine-shared draft prefill rows (survive decoder retirement,
        # like the target's prefix pages); None -> private to this decoder
        self.spec_prefix_rows = spec_prefix_rows
        self.draft: Optional[DraftModel] = None
        self.active: Dict[int, _SlotState] = {}
        self.qlen: Optional[int] = None
        # per-slot paging state, shaped once qlen is known
        self.page_tables: Optional[np.ndarray] = None   # (slots, n_pages)
        self.positions: Optional[np.ndarray] = None     # (slots, W_virtual)
        self.step_idx = 0                 # global decode-step counter
        self.n_steps = 0
        self.n_slot_steps = 0             # sum of live slots across steps
        self.totals = DecodeTotals()
        self.n_served = 0
        self.n_cancelled = 0              # requests removed via cancel()
        self.n_stage_faults = 0           # CloudStageErrors absorbed
        self.n_preempted = 0              # rows parked for urgent work
        self.n_rejected = 0               # shed at enqueue (queue bound)
        self.n_expired = 0                # dead on arrival at admission
        self._admitting = False           # reentrancy guard (see admit)

    @property
    def pending(self):
        """Compat view of queued admissions. The FIFO path exposes its
        real deque (tests/benches seed it directly); QoS schedulers
        return a read-only snapshot across their class queues."""
        q = getattr(self.scheduler, "queue", None)
        return q if q is not None else self.scheduler.snapshot()

    # ---- geometry (fixed once qlen is known) ----

    @property
    def prefix_len(self) -> int:
        return self.executor.pcfg.clip_tokens + self.qlen

    @property
    def n_prefix_pages(self) -> int:
        return pages_for(self.prefix_len, self.pool.page_size)

    @property
    def n_private_pages(self) -> int:
        return pages_for(self.T, self.pool.page_size)

    @property
    def width(self) -> int:
        """Virtual sequence width of one row (page-padded)."""
        return (self.n_prefix_pages + self.n_private_pages) \
            * self.pool.page_size

    @property
    def has_work(self) -> bool:
        return bool(self.scheduler.has_pending or self.active)

    # ---- queueing ----

    def submit(self, seq_id: int, intent: Intent, packet: pk.Packet, query,
               on_done: Callable[[Dict[str, Any]], None],
               operator_id: str = "",
               speculative: Optional[bool] = None,
               priority: int = 0,
               deadline: Optional[float] = None,
               t_submit: Optional[float] = None) -> None:
        """``speculative``: per-request drafting override — None follows
        the decoder's config (drafting iff a ``SpeculativeConfig`` was
        given), False forces a plain row even on a speculating decoder
        (plain and speculating rows share the verify batch).

        ``priority``/``deadline``/``t_submit`` feed the scheduler:
        strict band, mission-clock expiry (expired items resolve
        ``failure="deadline"`` *before* paying a prefill), and the
        enqueue timestamp for time-in-queue accounting. A bounded or
        rate-limited scheduler may shed the request here — ``on_done``
        then fires immediately with ``failure="rejected"``."""
        query = np.asarray(query).reshape(-1, np.asarray(query).shape[-1])
        if query.shape[0] != 1:
            raise ValueError(
                "in-flight slots hold one sequence each; split "
                f"{query.shape[0]}-row packets at the edge")
        if self.qlen is None:
            self.qlen = int(query.shape[-1])
        elif int(query.shape[-1]) != self.qlen:
            raise ValueError(
                f"decoder serves qlen={self.qlen}, got {query.shape[-1]}")
        now = self._clock()
        item = _PendingRequest(seq_id, intent, packet, query, on_done,
                               operator_id, speculative=speculative,
                               priority=int(priority), deadline=deadline,
                               t_enqueue=t_submit if t_submit is not None
                               else now)
        reason = self.scheduler.enqueue(item, now)
        if reason is not None:
            self.n_rejected += 1
            item.on_done({
                "seq_id": item.seq_id, "intent": item.intent,
                "tier_name": item.packet.tier_name,
                "failure": "rejected", "reason": reason})
            return
        self.admit()

    # ---- admission: prefix reuse + page allocation between steps ----

    @staticmethod
    def _prefix_ctx(packet: pk.Packet) -> np.ndarray:
        """The context features feeding the LLM prefix — the CLIP stream
        riding in either packet kind."""
        return packet.content["clip" if packet.kind == "insight" else "ctx"]

    def admit(self) -> int:
        """Admit queued requests into free slots in scheduler order,
        then let urgent queued work preempt. A ``CloudStageError`` from
        an admission stage fails only that request — its pages are
        unwound refcount-safely by ``_admit_one`` and ``on_done`` fires
        with a ``cloud_error`` failure — and admission continues.
        Reentrant calls (an ``on_done`` callback resubmitting a retry
        mid-admission) are no-ops; the outer loop picks up whatever they
        queued."""
        if self._admitting:
            return 0
        self._admitting = True
        try:
            admitted = 0
            now = self._clock()
            while self.scheduler.has_pending \
                    and len(self.active) < self.slots:
                item = self.scheduler.pop_next(now)
                if item is None:
                    break
                admitted += self._try_admit(item, now)
            # preemption: an urgent pending request (deadline at risk,
            # or latency-class/priority patience exceeded) evicts the
            # lowest-ranked active decode; the victim parks token-
            # exactly and requeues at the front of its class. Bounded
            # by ``slots`` — each round parks one strictly lower-ranked
            # victim, so chains terminate.
            for _ in range(self.slots):
                if not (self.scheduler.has_pending and self.active):
                    break
                pick = self.scheduler.pick_preemption(self.active, now)
                if pick is None:
                    break
                item, victim = pick
                self._park_slot(victim, self.active[victim])
                admitted += self._try_admit(item, now)
            return admitted
        finally:
            self._admitting = False

    def _try_admit(self, item: _PendingRequest, now: float) -> int:
        """Admit one popped item. An already-expired deadline resolves
        ``failure="deadline"`` here — *before* the prefill — so a dead
        request can never waste cloud compute on its way out."""
        if item.deadline is not None and now >= item.deadline:
            self.n_expired += 1
            self.scheduler.note_expired()
            item.on_done({
                "seq_id": item.seq_id, "intent": item.intent,
                "tier_name": item.packet.tier_name,
                "failure": "deadline"})
            return 0
        try:
            # the span ends with the request's first token on the host
            with span("inflight.admit", rid=item.seq_id) as sp:
                slot, st = self._admit_one(item)
                sp.set_metadata(hit=int(st.prefix_hit))
            self.scheduler.note_admitted(item, now)
            st.t_admit = now
            if item.t_first_token is None:
                item.t_first_token = now   # token 0 exists from here on
            if self.tracer.enabled:
                rid = item.seq_id
                self.tracer.span(rid, "queue", item.t_enqueue,
                                 max(now, item.t_enqueue))
                if item.resumes and item.resume_tokens is not None:
                    self.tracer.point(rid, "resume", now, slot=slot,
                                      replayed=len(item.resume_tokens))
                self.tracer.span(
                    rid, "prefix_hit" if st.prefix_hit else "prefill",
                    now, now, slot=slot)
            return 1
        except CloudStageError as e:
            self.n_stage_faults += 1
            item.on_done({
                "seq_id": item.seq_id, "intent": item.intent,
                "tier_name": item.packet.tier_name,
                "failure": "cloud_error", "error": str(e)})
            return 0

    def _admit_one(self, item: _PendingRequest
                   ) -> Tuple[int, _SlotState]:
        """Prefill one request into a free slot; returns the slot and
        its state. Any stage failure unwinds exactly the pages acquired
        so far and re-raises, so a fault mid-admission never leaks a
        page or corrupts the prefix store (a faulted miss leaves the
        store either without the entry or with a fully written one)."""
        page = self.pool.page_size
        ctx = self._prefix_ctx(item.packet)
        key = (item.operator_id, prefix_digest(ctx, item.query))
        entry = self.pool.lookup_prefix(key)
        hit = entry is not None
        if not hit:
            logits0, paged = self.executor.cloud_prefix(ctx, item.query)
            self.totals.prefix_misses += 1
            self.totals.prefill_tokens += self.prefix_len
            self.pool.ensure(
                self.n_prefix_pages, like=paged,
                capacity_hint=1 + self.slots * (self.n_prefix_pages
                                                + self.n_private_pages))
            ids = self.pool.alloc(self.n_prefix_pages)
            try:
                self.pool.kv = self.executor.pool_write(self.pool.kv, paged,
                                                        ids)
            except Exception:
                self.pool.release(ids)
                raise
            entry = self.pool.put_prefix(key, ids, self.prefix_len,
                                         np.asarray(logits0))
        else:
            # a hit rides the stored pages: take this request's ref
            # (a miss already owns its pages' alloc reference)
            self.pool.retain(entry.page_ids)
        # SAM feats before decode-page allocation: a feats fault unwinds
        # by dropping this request's prefix ref alone (the store keeps
        # its own ref, so a retry hits the cached prefix)
        try:
            feats = (self.executor.cloud_sam_feats(item.packet)
                     if item.packet.kind == "insight" else None)
        except Exception:
            self.pool.release(entry.page_ids)
            raise
        if feats is not None:
            self.totals.sam_tails += 1
        speculative = (self.spec is not None
                       and item.speculative is not False)
        # speculating rows allocate decode pages lazily per verify
        # chunk (grow ahead of acceptance, roll back on rejection);
        # plain rows keep the whole answer's pages up front
        private = ([] if speculative
                   else self.pool.alloc(self.n_private_pages))
        slot = min(set(range(self.slots)) - set(self.active))
        if self.page_tables is None:
            n_pages = self.n_prefix_pages + self.n_private_pages
            self.page_tables = np.full((self.slots, n_pages),
                                       TRASH_PAGE, np.int32)
            self.positions = np.full((self.slots, self.width), -1,
                                     np.int32)
        self.page_tables[slot] = (list(entry.page_ids) + private
                                  + [TRASH_PAGE]
                                  * (self.n_private_pages
                                     - len(private)))
        self.positions[slot] = -1
        self.positions[slot, :self.n_prefix_pages * page] = \
            prefix_positions(self.prefix_len, self.n_prefix_pages, page)
        if speculative:
            if self.draft is None:
                self.draft = self._make_draft()
            # same key as the target prefix store: repeat-prefix
            # frames skip the draft prefill too (honouring the
            # pool's sharing knob so baselines stay baselines)
            with span("inflight.draft"):
                self.draft.admit(slot, ctx, item.query,
                                 key=key if self.pool.share_prefixes
                                 else None)
        st = _SlotState(
            req=item, tokens=[int(np.argmax(entry.logits0[0]))],
            logits0=entry.logits0, feats=feats, pos=self.prefix_len,
            joined_step=self.step_idx, prefix_ids=entry.page_ids,
            private_ids=private, prefix_hit=hit,
            speculative=speculative)
        if self._cost is not None and not hit:
            # a prefix hit rides cached pages: only the miss pays (and is
            # charged for) the full-sequence prefill
            st.flops = self._cost.prefill_flops(self.prefix_len)
        if item.resume_tokens:
            # a parked victim resumes from its prefix: token 0 re-emerges
            # from the (cached or re-prefilled) prefix logits, the rest
            # replay through the decode loop. Greedy decoding makes the
            # replay byte-identical to the original run, so the resumed
            # request stays token-exact with an uninterrupted one.
            st.replay = deque(item.resume_tokens[1:])
        self.active[slot] = st
        self.totals.admissions += 1
        return slot, st

    # ---- cancellation (deadline enforcement) ----

    def cancel(self, seq_id: int) -> bool:
        """Remove one request from the decoder — pending or mid-decode —
        releasing its slot and pages refcount-safely. The caller (the
        engine's deadline sweep) resolves the request's future; the
        decoder only reclaims resources. Returns False when ``seq_id``
        is not here (already finished, or queued on another decoder)."""
        if self.scheduler.remove(seq_id):
            self.n_cancelled += 1
            return True
        for s, st in list(self.active.items()):
            if st.req.seq_id == seq_id:
                self._release_slot(s, st)
                self.n_cancelled += 1
                self.admit()          # the freed slot lets queued work in
                return True
        return False

    def _make_draft(self) -> DraftModel:
        cfg = self.spec
        draft = DraftModel(
            cfg.draft_params or self.executor.params,
            cfg.draft_pcfg or self.executor.pcfg,
            slots=self.slots, prefix_len=self.prefix_len,
            max_new_tokens=self.T, draft_tokens=cfg.draft_tokens,
            flash_decode=getattr(self.executor, "flash_decode", False),
            prefix_rows=self.spec_prefix_rows,
            prefix_cap=self.pool.max_prefixes,
            # sharded serving context: draft stages jitted with mesh
            # shardings so the draft rides the same tensor parallelism
            fns_factory=getattr(self.executor, "draft_fns", None))
        if self._profiler is not None:
            draft = self._profiler.wrap_draft(draft)
        return draft

    # ---- the lockstep decode step ----

    def step(self) -> int:
        """Advance every live slot (no-op when idle); returns the number
        of requests that finished on this step. Plain rows advance one
        token; speculating rows advance by however many drafted tokens
        the serving model accepts (1..k+1), sharing the same verify
        batch."""
        if not self.active:
            return 0
        with span("inflight.step"):
            draft_rows = {}
            if self.spec is not None and self.draft is not None:
                # resumed rows replay their parked tokens through the
                # plain path first (drafting against a replay is
                # pointless — the outcome is already known); they rejoin
                # drafting once the replay drains
                candidates = {s: st for s, st in self.active.items()
                              if st.speculative and len(st.tokens) < self.T
                              and not st.replay}
                if candidates and self.spec_gate(self.spec_stats):
                    draft_rows = candidates
                elif candidates:
                    self.spec_stats.disabled_steps += 1
            if draft_rows:
                return self._step_verify(draft_rows)
            return self._step_plain()

    def _step_plain(self) -> int:
        """One single-token decode step over all live rows (the non-
        speculative path; also serves speculating rows whose drafting
        the policy has disabled, and rows that only need their final
        <SEG> read)."""
        base = self.n_prefix_pages * self.pool.page_size
        with span("inflight.step.inputs"):
            toks = np.zeros((self.slots, 1), np.int32)
            # free rows decode garbage through the trash page (their page
            # tables were reset on release); outputs are discarded
            pos = np.zeros((self.slots,), np.int32)
            write_slot = np.zeros((self.slots,), np.int32)
            for s, st in self.active.items():
                # speculating rows manage decode pages lazily — make sure
                # the slot being written is covered (no-op for plain
                # rows, whose pages were allocated up front)
                self._grow_private(s, st, len(st.tokens))
                toks[s, 0] = st.tokens[-1]
                pos[s] = st.pos
                write_slot[s] = base + len(st.tokens) - 1
        wc = self._wallclock
        w0 = wc() if wc is not None else 0.0
        try:
            with span("inflight.step.launch"):
                logits, seg, self.pool.kv = self.executor.cloud_decode_rows(
                    self.pool.kv, self.page_tables, self.positions, toks,
                    pos, write_slot)
        except CloudStageError as e:
            return self._fail_step(e)
        with span("inflight.step.fetch"):
            # only the final step of a row reads its <SEG> state
            ids, seg = self._fetch(logits, seg, finishing=any(
                len(st.tokens) >= self.T for st in self.active.values()))
        # the step as the host sees it: launch, device and the copy back
        if wc is not None and self._metrics is not None:
            self._metrics.histogram("decode_step_s").observe(wc() - w0)
        live = len(self.active)
        self.n_steps += 1
        self.n_slot_steps += live
        now = self._clock()
        finished = 0
        with span("inflight.step.sample"):
            for s, st in list(self.active.items()):
                n = len(st.tokens)
                self.positions[s, base + n - 1] = st.pos
                st.steps_done += 1
                st.batch_acc += live
                # one fed token attending st.pos + 1 cached positions
                self.totals.attended_positions += st.pos + 1
                if self._cost is not None:
                    st.flops += self._cost.token_flops(st.pos + 1)
                    st.hbm_bytes += self._cost.token_hbm_bytes(st.pos + 1)
                if self.tracer.enabled:
                    self.tracer.point(st.req.seq_id, "decode_step", now,
                                      slot=s, step=self.step_idx)
                if n < self.T:
                    if st.replay:
                        # replaying a parked run: the stored token IS the
                        # greedy pick (deterministic decode), so feeding
                        # it keeps the resumed row token-exact
                        st.tokens.append(st.replay.popleft())
                        self.scheduler.note_replayed()
                    else:
                        st.tokens.append(int(ids[s]))
                    st.pos += 1
                    continue
                # final step: this row's seg is the <SEG> state at the
                # last generated token (llm_generate's convention for
                # every T)
                st.seg = seg[s]
                finished += self._finish_slot(s, st)
        self.step_idx += 1
        if finished:
            self.admit()              # freed slots let queued requests in
        return finished

    def _step_verify(self, draft_rows: Dict[int, _SlotState]) -> int:
        """One speculative verify step: drafting rows carry their last
        accepted token plus k Context-stream drafts, every other live
        row a chunk of one; a single paged multi-token pass scores them
        all, greedy acceptance advances each row, and decode pages past
        each row's accepted length roll back."""
        k = self.spec.draft_tokens
        C = k + 1
        page = self.pool.page_size
        base = self.n_prefix_pages * page
        with span("inflight.draft"):
            proposals = self.draft.draft(
                {s: st.tokens for s, st in draft_rows.items()}, k,
                budgets={s: self.T - len(st.tokens)
                         for s, st in draft_rows.items()})
        with span("inflight.step.inputs"):
            toks = np.zeros((self.slots, C), np.int32)
            pos = np.zeros((self.slots,), np.int32)
            write_slot = np.zeros((self.slots,), np.int32)
            clens = np.ones((self.slots,), np.int32)
            n_drafted: Dict[int, int] = {}
            for s, st in self.active.items():
                n = len(st.tokens)
                toks[s, 0] = st.tokens[-1]
                pos[s] = st.pos
                write_slot[s] = base + n - 1
                if s in proposals:
                    j = min(k, self.T - n)    # never draft past the answer
                    n_drafted[s] = j
                    toks[s, 1:1 + j] = proposals[s][:j]
                    clens[s] = 1 + j
                # cover the chunk (incl. the draft overhang) with decode
                # pages
                self._grow_private(s, st, n - 1 + int(clens[s]))
        wc = self._wallclock
        w0 = wc() if wc is not None else 0.0
        try:
            with span("inflight.step.launch"):
                logits, seg, self.pool.kv = self.executor.cloud_verify_rows(
                    self.pool.kv, self.page_tables, self.positions, toks,
                    pos, write_slot, clens)
        except CloudStageError as e:
            return self._fail_step(e)
        with span("inflight.step.fetch"):
            # a row can finish only if its chunk reaches the last token
            ids, seg = self._fetch(logits, seg, finishing=any(
                len(st.tokens) + n_drafted.get(s, 0) >= self.T
                for s, st in self.active.items()))
        if wc is not None and self._metrics is not None:
            self._metrics.histogram("verify_step_s").observe(wc() - w0)
        live = len(self.active)
        self.n_steps += 1
        self.n_slot_steps += live
        now = self._clock()
        finished = 0
        with span("inflight.step.sample"):
            for s, st in list(self.active.items()):
                n = len(st.tokens)
                j = n_drafted.get(s, 0)
                # chunk token i attends st.pos + i + 1 positions
                c = int(clens[s])
                self.totals.attended_positions += \
                    c * (st.pos + 1) + c * (c - 1) // 2
                if self._cost is not None:
                    # every fed chunk token costs device compute whether
                    # or not its draft is accepted — rejected drafts are
                    # real FLOPs, which is exactly what the ledger should
                    # show
                    for i in range(c):
                        st.flops += self._cost.token_flops(st.pos + i + 1)
                        st.hbm_bytes += self._cost.token_hbm_bytes(
                            st.pos + i + 1)
                # greedy[i]: the serving model's own pick after chunk
                # token i
                greedy = ids[s, :1 + j]
                m = greedy_accept(toks[s, 1:1 + j], greedy) if j else 0
                # chunk tokens 0..m are now committed: the real last
                # token plus m accepted drafts
                for i in range(m + 1):
                    self.positions[s, base + n - 1 + i] = st.pos + i
                new = [int(g) for g in greedy[:m + 1]][:self.T - n]
                st.tokens.extend(new)
                st.pos += len(new)
                if st.replay:
                    # a resumed row riding someone else's verify batch
                    # advances by the model's own greedy picks —
                    # identical to the parked tokens — so its replay
                    # drains in step
                    for _ in new:
                        if st.replay:
                            st.replay.popleft()
                            self.scheduler.note_replayed()
                st.steps_done += 1
                st.batch_acc += live
                if self.tracer.enabled:
                    self.tracer.point(st.req.seq_id, "verify_step", now,
                                      slot=s, step=self.step_idx,
                                      drafted=j, accepted=int(m))
                if j:
                    # accepted drafts the draft model itself fed
                    # (d_1..d_{j-1} — the j-th came off the last feed's
                    # logits) already live in its cache at their
                    # committed positions: skip their catch-up feed next
                    # round
                    self.draft.commit(s, n + min(m, j - 1))
                    self.spec_stats.note_chunk(j, m, len(new),
                                               metrics=self._metrics)
                    # rollback: free decode pages past the accepted length
                    dropped = self.pool.rollback_to(st.private_ids, n + m)
                    if dropped:
                        self.spec_stats.pages_rolled_back += len(dropped)
                        lo = self.n_prefix_pages + len(st.private_ids)
                        self.page_tables[s, lo:lo + len(dropped)] = \
                            TRASH_PAGE
                if n - 1 + m >= self.T - 1:
                    # the answer's final token was fed and accepted in
                    # this chunk: its hidden state is the <SEG> read
                    st.seg = seg[s, self.T - n]
                    finished += self._finish_slot(s, st)
        self.step_idx += 1
        if finished:
            self.admit()
        return finished

    def _fetch(self, logits, seg, finishing: bool):
        """A step's greedy ids on the host, picked on the device from the
        logits the stage returned, and its <SEG> states when ``finishing``
        (else None). The device counters the same program left on the
        executor (``step_counts``; no other stage call comes between the
        launch and this fetch) come back in the same copy and go into the
        totals."""
        got = jax.device_get((self.greedy_pick(logits),
                              seg if finishing else None,
                              getattr(self.executor, "step_counts", {})))
        self.totals.fetched_bytes += sum(
            np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(got))
        ids, seg, counts = got
        self.totals.add_counts(counts)
        return ids, seg

    def _grow_private(self, slot: int, st: _SlotState, tokens: int) -> None:
        """Extend one row's private decode pages to cover ``tokens``
        virtual slots (speculative allocation ahead of acceptance) and
        map the fresh pages into its page table."""
        lo = self.n_prefix_pages + len(st.private_ids)
        fresh = self.pool.grow_to(st.private_ids, tokens)
        if fresh:
            self.page_tables[slot, lo:lo + len(fresh)] = fresh

    def _fail_step(self, err: CloudStageError) -> int:
        """A batch-wide decode/verify stage died: the step failed for
        every live row (the paged pass is one device call). Release all
        slots first — pages back, tables parked — then report each
        request as a ``cloud_error`` (callbacks may resubmit retries
        into the now-free slots), then admit queued work."""
        self.n_stage_faults += 1
        failed = list(self.active.items())
        for s, st in failed:
            self._release_slot(s, st)
        for _, st in failed:
            st.req.on_done({
                "seq_id": st.req.seq_id, "intent": st.req.intent,
                "tier_name": st.req.packet.tier_name,
                "failure": "cloud_error", "error": str(err)})
        self.admit()
        return 0

    def _finish_slot(self, s: int, st: _SlotState) -> int:
        """Deliver a finished row: decode its mask from the stored SAM
        feats and the captured <SEG> state, hand the result back, and
        release its pages."""
        with span("inflight.finish", rid=st.req.seq_id):
            if self.tracer.enabled:
                # close this residency segment: preemption round-trips give
                # one decode span per segment, bounded by park/resume points
                now = self._clock()
                self.tracer.span(st.req.seq_id, "decode", st.t_admit,
                                 max(now, st.t_admit), slot=s,
                                 tokens=len(st.tokens))
            mask = None
            if st.feats is not None:
                try:
                    mask = np.asarray(self.executor.cloud_mask(
                        st.feats, st.seg[None]))
                except CloudStageError as e:
                    self.n_stage_faults += 1
                    self._release_slot(s, st)
                    st.req.on_done({
                        "seq_id": st.req.seq_id, "intent": st.req.intent,
                        "tier_name": st.req.packet.tier_name,
                        "failure": "cloud_error", "error": str(e)})
                    return 1
                self.totals.masks += 1
            st.req.on_done({
                "seq_id": st.req.seq_id,
                "intent": st.req.intent,
                "tier_name": st.req.packet.tier_name,
                "answer_logits": st.logits0,
                "mask_logits": mask,
                "tokens": np.asarray(st.tokens, np.int32)[None, :],
                "batch_size": st.batch_acc / max(1, st.steps_done),
                "joined_step": st.joined_step,
                "prefix_hit": st.prefix_hit,
                "speculative": st.speculative,
                "preemptions": st.req.resumes,
                "queue_wait": st.req.queue_wait,
                "t_first_token": st.req.t_first_token,
                "cloud_flops": st.flops if self._cost is not None else None,
                "cloud_hbm_bytes": st.hbm_bytes
                if self._cost is not None else None,
            })
            if st.req.resumes:
                self.scheduler.note_resumed_served()
            self._release_slot(s, st)
            self.n_served += 1
            return 1

    def _release_slot(self, slot: int, st: _SlotState) -> None:
        """Return the slot's pages (prefix ref + private pages) and park
        its row on the trash page so later steps can't touch live KV."""
        self.pool.release(st.prefix_ids)
        self.pool.release(st.private_ids)
        self.page_tables[slot] = TRASH_PAGE
        self.positions[slot] = -1
        if st.speculative and self.draft is not None:
            self.draft.release(slot)
        del self.active[slot]

    def _park_slot(self, slot: int, st: _SlotState) -> None:
        """Preempt one active decode: roll its private pages back to
        empty (``PagePool.rollback_to`` — the same machinery as a
        speculative rejection, dropped all the way), drop its prefix
        reference, and requeue the request at the front of its class
        carrying its generated-so-far tokens. Re-admission replays them
        from the (usually still cached) prefix, token-exactly."""
        if self.tracer.enabled:
            now = self._clock()
            self.tracer.span(st.req.seq_id, "decode", st.t_admit,
                             max(now, st.t_admit), slot=slot,
                             tokens=len(st.tokens))
            self.tracer.point(st.req.seq_id, "park", now, slot=slot)
        self.pool.rollback_to(st.private_ids, 0)
        self.pool.release(st.prefix_ids)
        self.page_tables[slot] = TRASH_PAGE
        self.positions[slot] = -1
        if st.speculative and self.draft is not None:
            self.draft.release(slot)
        del self.active[slot]
        item = st.req
        # fold any undrained replay back in: tokens already committed
        # to st.tokens are the authoritative resume point
        item.resume_tokens = list(st.tokens)
        item.resumes += 1
        item.t_enqueue = self._clock()
        self.n_preempted += 1
        self.scheduler.note_preempted()
        self.scheduler.requeue_preempted(item, item.t_enqueue)

    def pump(self, max_steps: int = 1) -> None:
        # admission first: pending requests must start even when no batch
        # is running (the engine's lazy-drive paths reach here with
        # ``active`` empty but ``pending`` not)
        self.admit()
        for _ in range(max_steps):
            if not self.active:
                break
            self.step()

    def drain(self) -> None:
        self.admit()
        while self.active:
            self.step()

    @property
    def mean_live_slots(self) -> float:
        return self.n_slot_steps / max(1, self.n_steps)
