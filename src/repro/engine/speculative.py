"""Speculative decoding for the Insight path: Context-stream drafts,
paged multi-token verification.

AVERY's dual-stream design keeps a small, high-frequency Context model
warm next to the large Insight model. Speculative decoding turns that
asymmetry into serving throughput: the small model *drafts* k candidate
answer tokens autoregressively, and the serving model *verifies* all of
them (plus the row's last accepted token) in one paged multi-token pass
(``vlm.llm_verify_step_paged`` over the shared page pool). Under greedy
decoding, a draft token is accepted iff it equals the serving model's
own greedy continuation at that position, so the emitted stream is
token-exact with ``llm_generate`` — acceptance only changes how many
serving-model passes the answer costs, never its content.

Per verify round a row emits between 1 token (first draft rejected: the
serving model's correction) and min(k+1, tokens remaining) tokens (all
drafts accepted + one bonus from the final logits). The draft model
rides a per-slot contiguous ring cache and needs **no rollback**:
rejected draft writes sit at positions ahead of the committed stream,
the position mask hides them, and the real token at that position
overwrites the slot when it is eventually fed. The *paged* serving
cache does roll back — ``PagePool.rollback_to`` frees decode pages past
the accepted length after every round (``core.paging``).

The acceptance rate is a self-awareness signal: ``SpecStats`` feeds the
engine's ``ControlPolicy`` (``AdaptivePolicy.allow_speculation``), which
disables drafting when acceptance falls below a floor — the same
embodied Sense/Evaluate/Select loop the paper applies to tier
selection, applied to the serving substrate itself.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import vlm
from repro.core.spans import named_stage


@functools.lru_cache(maxsize=None)
def _draft_fns(pcfg, width: int):
    """Jitted draft-model stages, cached per (config, cache width) at
    module level: decoders retire on ``engine.drain()`` and their
    ``DraftModel``s with them — fresh ``jax.jit`` wrappers would
    recompile the (unchanged) draft stages on every burst. Configs are
    frozen dataclasses, so they key the cache directly; params ride in
    as arguments and never retrigger compilation."""
    prefill = jax.jit(named_stage(
        "draft_prefill",
        lambda p, c, q: vlm.llm_prefill(p, pcfg, c, q, width=width)))
    step = jax.jit(named_stage(
        "draft_step",
        lambda p, ca, t, pos: vlm.llm_decode_step(p, pcfg, ca, t, pos)))
    insert = jax.jit(named_stage("draft_insert", DraftModel._insert_row))
    return prefill, step, insert


@dataclass(frozen=True)
class SpeculativeConfig:
    """Knobs of the speculative-decoding subsystem (the engine's
    ``speculative=`` argument accepts one of these, ``True`` for the
    defaults, or an int for ``draft_tokens``)."""
    draft_tokens: int = 3          # k: drafts proposed per verify round
    # drafting disables when cumulative acceptance falls below the floor
    # (after min_draft_samples drafted tokens) — the policy hook
    # ``ControlPolicy.allow_speculation`` applies these
    acceptance_floor: float = 0.35
    min_draft_samples: int = 16
    # draft model override: defaults to the target's own (warm) Context-
    # stream LLM — lisa_mini geometry, shared weights, so drafts are
    # free-of-divergence; plug a distinct small LM via these two
    draft_params: Optional[dict] = None
    draft_pcfg: Optional[Any] = None

    def __post_init__(self):
        if self.draft_tokens < 1:
            raise ValueError(
                f"draft_tokens must be >= 1, got {self.draft_tokens}")


@dataclass
class SpecStats:
    """Cumulative speculation telemetry (one per decoder; the engine
    aggregates across decoders). ``acceptance_rate`` is the self-
    awareness signal the control policy gates drafting on."""
    drafted: int = 0            # draft tokens submitted to verification
    accepted: int = 0           # draft tokens the serving model agreed with
    emitted: int = 0            # tokens emitted by drafting rows
    row_steps: int = 0          # (row, verify-step) pairs that drafted
    disabled_steps: int = 0     # steps the policy vetoed drafting on
    pages_rolled_back: int = 0  # KV pages freed by speculative rollback

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def tokens_per_step(self) -> float:
        """Mean tokens emitted per drafting row per verify step — 1.0 is
        the plain-decode floor; k+1 the full-acceptance ceiling."""
        return self.emitted / self.row_steps if self.row_steps else 0.0

    def note_chunk(self, drafted: int, accepted: int, emitted: int,
                   metrics: Optional[Any] = None) -> None:
        """Fold one drafting row's verify-chunk outcome in; with a
        ``MetricsRegistry`` attached the per-chunk acceptance fraction
        also feeds the ``spec_accept_rate`` histogram (the registry's
        view of the same self-awareness signal the policy gates on)."""
        self.drafted += drafted
        self.accepted += accepted
        self.emitted += emitted
        self.row_steps += 1
        if metrics is not None and drafted:
            metrics.histogram("spec_accept_rate").observe(
                accepted / drafted)

    def merge(self, other: "SpecStats") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))

    def as_dict(self) -> Dict[str, float]:
        return {
            "spec_drafted": self.drafted,
            "spec_accepted": self.accepted,
            "spec_acceptance_rate": self.acceptance_rate,
            "spec_tokens_per_step": self.tokens_per_step,
            "spec_disabled_steps": self.disabled_steps,
            "spec_pages_rolled_back": self.pages_rolled_back,
        }


def greedy_accept(drafts: Sequence[int], greedy: Sequence[int]
                  ) -> int:
    """Greedy acceptance rule: number of leading draft tokens that equal
    the serving model's own greedy continuation at their position
    (``greedy[i]`` = argmax of the verify logits after chunk token i, so
    draft i+1 is accepted iff it equals ``greedy[i]``)."""
    m = 0
    while m < len(drafts) and int(drafts[m]) == int(greedy[m]):
        m += 1
    return m


class DraftModel:
    """The Context-stream draft model, batched over the in-flight slots.

    Wraps a lisa_mini-geometry LM (by default the target's own LLM
    weights — the warm Context model) behind the contiguous
    prefill/decode path: ``admit`` prefills a slot's ``[ctx; query]``
    prefix into its row of a ``(slots, width)`` ring cache, ``draft``
    runs lockstep batched single-token steps (per-row positions) that
    catch up on newly committed tokens and then self-feed k proposals.

    No rollback is needed here: a rejected draft's k/v sits at a
    position ahead of the committed stream, the position mask hides it
    from every later step, and the slot is overwritten when the real
    token at that position is fed. Idle rows park their step on the
    reserved last ring slot (``width - 1``), which no real position ever
    maps to.
    """

    def __init__(self, params: dict, pcfg: Any, *, slots: int,
                 prefix_len: int, max_new_tokens: int, draft_tokens: int,
                 flash_decode: bool = False,
                 prefix_rows: Optional[Dict[Any, Dict]] = None,
                 prefix_cap: Optional[int] = None,
                 fns_factory: Optional[Any] = None):
        self.pcfg = dataclasses.replace(
            pcfg, llm=pcfg.llm.replace(use_flash_decode=flash_decode))
        self.params = params
        self.slots = int(slots)
        self.prefix_len = int(prefix_len)
        # widest real position: catching up tokens[: T] then self-feeding
        # k-1 drafts reaches prefix + T + k - 2; slot width-1 is the park
        self.width = self.prefix_len + int(max_new_tokens) \
            + int(draft_tokens)
        self.park_pos = self.width - 1
        self.cache: Optional[Dict] = None
        # emitted (target-committed) tokens each row has consumed
        self.fed = np.zeros((self.slots,), np.int64)
        self.n_steps = 0           # batched draft decode steps (telemetry)
        self.n_prefills = 0
        # prefilled [ctx; query] cache rows keyed like the target's
        # prefix store, so repeat-prefix admissions skip the draft
        # prefill too (LRU-capped: entries are one (1, width) ring
        # each). The dict may be shared across decoders — the engine
        # passes one per engine, next to its kv_pool, so the rows
        # survive decoder retirement like the target's prefix pages do;
        # entries are namespaced by ring width so mixed-qlen decoders
        # can't hand each other wrong-shaped rows.
        self._prefix_rows: Dict[Any, Dict] = (
            prefix_rows if prefix_rows is not None else {})
        self._prefix_cap = (prefix_cap if prefix_cap is not None
                            else 2 * self.slots)
        # ``fns_factory`` (sharded serving): the engine's serving
        # context supplies jitted prefill/step/insert with explicit
        # mesh shardings (``ShardedServingContext.draft_fns``); the
        # default is the module-level jit cache, which survives decoder
        # retirement the same way
        if fns_factory is not None:
            self._prefill, self._step, self._insert = fns_factory(
                self.pcfg, self.width, self.params)
        else:
            self._prefill, self._step, self._insert = _draft_fns(self.pcfg,
                                                                 self.width)

    @staticmethod
    def _insert_row(dst: Dict, src: Dict, row) -> Dict:
        """Scatter a 1-row prefill cache into row ``row`` of the slot
        cache: kv leaves (L, B, W, ...) at axis 1, positions (B, W)."""
        return {
            "groups": jax.tree.map(lambda d, s: d.at[:, row].set(s[:, 0]),
                                   dst["groups"], src["groups"]),
            "positions": dst["positions"].at[row].set(src["positions"][0]),
        }

    def admit(self, row: int, ctx, query, key: Any = None) -> None:
        """Prefill one slot's ``[ctx; query]`` prefix into its cache row.
        ``key`` (the target prefix store's (operator, digest) key) lets
        repeat-prefix admissions reuse the stored prefill row instead of
        re-running the draft prefill — the draft-side analogue of the
        page pool's prefix sharing (here by copy, since the ring cache
        is per-row mutable)."""
        skey = (key, self.width) if key is not None else None
        row_cache = self._prefix_rows.get(skey) if skey is not None else None
        if row_cache is None:
            ctx = jnp.asarray(ctx)
            if ctx.shape[-1] != self.pcfg.llm.d_model:
                raise ValueError(
                    f"draft model width {self.pcfg.llm.d_model} does not "
                    f"match context features {ctx.shape[-1]}")
            _, _, row_cache = self._prefill(self.params, ctx,
                                            jnp.asarray(query))
            self.n_prefills += 1
            if skey is not None:
                self._prefix_rows[skey] = row_cache
                while len(self._prefix_rows) > self._prefix_cap:
                    self._prefix_rows.pop(next(iter(self._prefix_rows)))
        else:                          # refresh recency
            self._prefix_rows[skey] = self._prefix_rows.pop(skey)
        if self.cache is None:
            self.cache = jax.tree.map(
                lambda a: jnp.zeros((a.shape[0], self.slots)
                                    + a.shape[2:], a.dtype),
                row_cache["groups"])
            self.cache = {
                "groups": self.cache,
                "positions": jnp.full((self.slots, self.width), -1,
                                      jnp.int32),
            }
        self.cache = self._insert(self.cache, row_cache,
                                  jnp.int32(row))
        self.fed[row] = 0

    def release(self, row: int) -> None:
        self.fed[row] = 0          # admit() re-prefills the row wholesale

    def commit(self, row: int, n_fed: int) -> None:
        """Mark emitted tokens up to ``n_fed`` as already consumed: an
        accepted draft's k/v sits in this cache at exactly the position
        the committed token occupies (same token, same position — it
        *was* the draft), so the next round needn't re-feed it. Only
        moves forward; the rejected tail is left to the position mask."""
        self.fed[row] = max(self.fed[row], n_fed)

    def draft(self, jobs: Dict[int, List[int]], k: int,
              budgets: Optional[Dict[int, int]] = None
              ) -> Dict[int, List[int]]:
        """One drafting round: for each row in ``jobs`` (row -> emitted
        token list), feed the emitted tokens it hasn't consumed yet,
        then self-feed until the row's proposal budget is collected
        (``budgets[row]``, default k — callers cap it by the tokens the
        verify step can still use, so answer tails don't burn draft
        steps on discarded proposals). All rows advance in lockstep
        batched decode steps; rows that finish early (or aren't
        drafting) park on the reserved slot. Returns row -> proposed
        tokens."""
        if not jobs:
            return {}
        want = {r: min(k, (budgets or {}).get(r, k)) for r in jobs}
        queue = {r: list(toks[int(self.fed[r]):]) for r, toks in
                 jobs.items()}
        for r, pend in queue.items():
            assert pend, f"row {r} has no unfed committed token"
        pos_next = {r: self.prefix_len + int(self.fed[r]) for r in jobs}
        drafts: Dict[int, List[int]] = {r: [] for r in jobs}
        while any(len(drafts[r]) < want[r] for r in jobs):
            toks = np.zeros((self.slots, 1), np.int32)
            pos = np.full((self.slots,), self.park_pos, np.int32)
            feeding = []
            for r in jobs:
                if len(drafts[r]) >= want[r]:
                    continue
                t = queue[r].pop(0) if queue[r] else drafts[r][-1]
                toks[r, 0] = t
                pos[r] = pos_next[r]
                pos_next[r] += 1
                feeding.append(r)
            logits, _, self.cache = self._step(self.params, self.cache,
                                               jnp.asarray(toks),
                                               jnp.asarray(pos))
            logits = np.asarray(logits)
            self.n_steps += 1
            for r in feeding:
                if not queue[r]:       # fed the stream tail or a draft
                    drafts[r].append(int(np.argmax(logits[r])))
        for r, toks_ in jobs.items():
            self.fed[r] = len(toks_)
        return drafts
