"""Dual-stream execution modes (paper §4.1–§4.3) + the batched cloud
serving engine.

``Stream`` names the two semantically distinct execution modes; the
``DualStreamExecutor`` bundles the jitted edge/cloud stage functions for a
trained LISA pipeline plus the per-tier bottlenecks, and exposes
``run_context`` / ``run_insight`` used by the serving runtime and the
mission simulator.

Cloud serving is batched: ``cloud_context_batch`` / ``cloud_insight_batch``
stack multiple packets of the same tier into one device call, and
``cloud_generate_batch`` serves multi-token answers through the
prefill + flash-decode KV-cache path (``vlm.llm_prefill`` /
``vlm.llm_decode_step``). The in-flight stages serve the paged
shared-prefix cache instead: ``cloud_prefix`` prefills a [ctx; query]
prefix into fixed-size KV pages, ``pool_write`` scatters them into the
shared page pool, and ``cloud_decode_rows`` advances every live slot one
token through per-row page tables (``vlm.llm_decode_step_paged``; the
allocator/prefix-store bookkeeping lives in ``core.paging``). Request
counts are padded up to a small set of bucket sizes and every jitted
stage is held in an explicit compile cache keyed on (stage, tier,
bucket, query_len), so varying request counts never retrigger XLA
compilation.

The executor is deliberately channel-agnostic: it returns the numpy
payloads + packets; the runtime decides what the (simulated or pod-
disaggregated) link does with them.
"""
from __future__ import annotations

import dataclasses
import enum
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.lisa7b import LISAPipelineConfig
from repro.core import bottleneck as bn
from repro.core import packets as pk
from repro.core import vlm
from repro.core.lut import SystemLUT, Tier
from repro.core.spans import named_stage


class Stream(enum.Enum):
    CONTEXT = "context"   # high-frequency, low-resolution awareness
    INSIGHT = "insight"   # low-frequency, high-fidelity grounding


def _pool_write(dst: Dict, src: Dict, page_ids) -> Dict:
    """Scatter one prefilled prefix's pages (leaves (L, n, page, ...))
    into the shared page pool (leaves (L, P, page, ...)) at
    ``page_ids`` (n,)."""
    return jax.tree.map(lambda d, s: d.at[:, page_ids].set(s), dst, src)


def _pad_rows(arr: np.ndarray, bucket: int) -> np.ndarray:
    """Pad axis 0 up to ``bucket`` by repeating the last row (rows past the
    real count are sliced away after the call)."""
    n = arr.shape[0]
    if n == bucket:
        return arr
    reps = np.repeat(arr[-1:], bucket - n, axis=0)
    return np.concatenate([arr, reps], axis=0)


@dataclass
class DualStreamExecutor:
    pcfg: LISAPipelineConfig
    params: dict
    bottlenecks: Dict[str, dict]          # tier name -> bottleneck params
    lut: SystemLUT
    # batch buckets for the cloud stages: request counts are padded up to
    # the smallest bucket >= n so the jit cache sees a fixed shape set
    buckets: Tuple[int, ...] = (1, 2, 4, 8, 16)
    # answer length for the generate path (continuous-batching serving)
    max_new_tokens: int = 4
    # route decode attention through the flash-decode Pallas kernel
    flash_decode: bool = True
    # KV page size (token slots per page) for the paged in-flight cache
    page_size: int = 16

    def __post_init__(self):
        pcfg = self.pcfg
        self.buckets = tuple(sorted(self.buckets))
        # decode steps run with the flash-decode kernel on the attention
        # hot loop; prefill keeps the full-sequence path
        self._gen_pcfg = dataclasses.replace(
            pcfg, llm=pcfg.llm.replace(use_flash_decode=self.flash_decode))
        # every stage is jitted under its own name (core.spans), so its
        # XLA module is jit_<stage> in a profiler trace
        self._edge_context = jax.jit(named_stage(
            "edge_context", lambda p, img: vlm.clip_encode(p, pcfg, img)))
        self._edge_insight = jax.jit(named_stage(
            "edge_insight", lambda p, img: vlm.sam_head(p, pcfg, img)))
        # one shared jitted bottleneck encode for every tier (tiers differ
        # only in code rank, which the jit cache keys on via shape)
        self._encode = jax.jit(named_stage(
            "bottleneck_encode", lambda bp, a: bn.encode(bp, a)))
        # explicit compile cache: (stage, tier, bucket, query_len) ->
        # jitted callable.
        # Each entry owns exactly one compiled executable (bucket shapes
        # are fixed), so len(self._compiled) == number of XLA compiles.
        self._compiled: Dict[Tuple, Callable] = {}
        # in-flight decode stages (token-level continuous batching): one
        # paged decode step over all live slots with per-row positions and
        # page tables, the prefix-page scatter into the shared pool, and
        # the standalone mask decode
        self._decode_paged = jax.jit(named_stage(
            "cloud_decode_rows",
            lambda p, pool, pt, posarr, tok, pos, ws:
            vlm.llm_decode_step_paged(p, self._gen_pcfg, pool, pt, posarr,
                                      tok, pos, ws)))
        # speculative verify: one paged multi-token pass over every live
        # slot's chunk (last accepted token + drafts); the jit cache keys
        # on the chunk width C via the tokens shape
        self._verify_paged = jax.jit(named_stage(
            "cloud_verify_rows",
            lambda p, pool, pt, posarr, tok, pos, ws, cl:
            vlm.llm_verify_step_paged(p, self._gen_pcfg, pool, pt, posarr,
                                      tok, pos, ws, cl)))
        self._mask_decode = jax.jit(named_stage(
            "cloud_mask",
            lambda p, feats, seg: vlm.mask_decode(p, pcfg, feats, seg)))
        self._pool_write = jax.jit(named_stage("pool_write", _pool_write))
        # device counters of the last decode/verify step, returned by the
        # same program as its logits: the routed-expert counts of a trunk
        # with a dropless MoE share (``vlm.llm_decode_step_paged``), no
        # leaves otherwise. The in-flight decoder fetches them with the
        # greedy ids of that step, before it launches the next; they sit
        # here and not in the stage's return, which every wrapper of the
        # stage takes as (logits, seg, pool)
        self.step_counts: Dict = {}

    # ---- compile cache ----

    def _stage_fn(self, stage: str, width: Optional[int] = None) -> Callable:
        pcfg, T = self.pcfg, self.max_new_tokens
        gcfg = dataclasses.replace(
            pcfg, llm=pcfg.llm.replace(use_flash_decode=self.flash_decode))

        if stage == "cloud_sam_feats":
            def fn(p, bp, codes, scales):
                a = bn.decode(bp, codes, scales, out_dtype=pcfg.sam.adtype)
                return vlm.sam_tail(p, pcfg, a)
        elif stage == "cloud_prefix":
            page = self.page_size

            def fn(p, ctx, query):
                logits0, _, paged = vlm.llm_prefill_paged(p, pcfg, ctx,
                                                          query, page)
                # one request per pool row: drop the unit batch axis so
                # leaves are (L, n_pages, page, ...), the pool-write unit
                return logits0, jax.tree.map(lambda a: a[:, 0], paged)
        elif stage == "cloud_insight":
            def fn(p, bp, codes, scales, ctx, query):
                a = bn.decode(bp, codes, scales, out_dtype=pcfg.sam.adtype)
                feats = vlm.sam_tail(p, pcfg, a)
                answer_logits, seg = vlm.llm_reason(p, pcfg, ctx, query)
                return vlm.mask_decode(p, pcfg, feats, seg), answer_logits
        elif stage == "cloud_context":
            def fn(p, ctx, query):
                return vlm.llm_reason(p, pcfg, ctx, query)[0]
        elif stage == "cloud_insight_gen":
            def fn(p, bp, codes, scales, ctx, query):
                a = bn.decode(bp, codes, scales, out_dtype=pcfg.sam.adtype)
                feats = vlm.sam_tail(p, pcfg, a)
                tokens, logits0, seg = vlm.llm_generate(p, gcfg, ctx, query, T)
                return vlm.mask_decode(p, pcfg, feats, seg), logits0, tokens
        elif stage == "cloud_context_gen":
            def fn(p, ctx, query):
                tokens, logits0, _ = vlm.llm_generate(p, gcfg, ctx, query, T)
                return logits0, tokens
        else:
            raise ValueError(stage)
        return fn

    def _jitted(self, stage: str, tier_name: Optional[str], bucket: int,
                qlen: int, width: Optional[int] = None) -> Callable:
        # max_new_tokens / flash_decode / page_size are baked into the
        # staged fns, so they are part of the key: mutating them after some
        # buckets have compiled must not serve stale answers from the old
        # entries
        key = (stage, tier_name, bucket, qlen, self.max_new_tokens,
               self.flash_decode, self.page_size, width)
        fn = self._compiled.get(key)
        if fn is None:
            fn = jax.jit(named_stage(stage,
                                     self._stage_fn(stage, width=width)))
            self._compiled[key] = fn
        return fn

    @property
    def num_compiled_stages(self) -> int:
        return len(self._compiled)

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n; oversized direct calls round up to a
        multiple of the largest bucket instead of failing (the scheduler
        never builds such microbatches, but per-packet callers may pass
        arbitrarily large frame batches, as the seed path allowed)."""
        for b in self.buckets:
            if n <= b:
                return b
        top = self.buckets[-1]
        return ((n + top - 1) // top) * top

    # ---- edge side ----

    def edge_context(self, images, seq_id: int, now: float
                     ) -> Tuple[pk.Packet, np.ndarray]:
        ctx = np.asarray(self._edge_context(self.params, images))
        return pk.make_context_packet(seq_id, now, ctx), ctx

    def edge_insight(self, images, tier: Tier, seq_id: int, now: float,
                     ctx: Optional[np.ndarray] = None) -> pk.Packet:
        """``ctx``: precomputed CLIP context features for this frame (e.g.
        from an ``edge_context`` call on the same image) — passing them
        keeps the edge at one CLIP pass per frame."""
        a = self._edge_insight(self.params, images)
        codes, scales = self._encode(self.bottlenecks[tier.name], a)
        if ctx is None:
            ctx = np.asarray(self._edge_context(self.params, images))
        return pk.make_insight_packet(seq_id, now, tier.name,
                                      np.asarray(codes), np.asarray(scales),
                                      clip_feats=np.asarray(ctx))

    # ---- cloud side (single packet, kept as the thin compat wrappers) ----

    def cloud_context(self, packet: pk.Packet, query) -> np.ndarray:
        return self.cloud_context_batch([packet], [np.asarray(query)])[0]

    def cloud_insight(self, packet: pk.Packet, query
                      ) -> Tuple[np.ndarray, np.ndarray]:
        return self.cloud_insight_batch([packet], [np.asarray(query)])[0]

    # ---- cloud side (batched serving engine) ----

    def _stack(self, packets: Sequence[pk.Packet],
               queries: Sequence[np.ndarray], keys: Sequence[str]
               ) -> Tuple[List[np.ndarray], np.ndarray, List[int], int]:
        """Concatenate per-packet content rows + queries along the batch
        axis and pad to the bucket. Returns (stacked content arrays in
        ``keys`` order, stacked queries, per-packet row counts, bucket)."""
        rows = [np.asarray(q).reshape(-1, np.asarray(q).shape[-1])
                for q in queries]
        counts = [p.content[keys[0]].shape[0] for p in packets]
        if any(r.shape[0] != c for r, c in zip(rows, counts)):
            raise ValueError(
                f"query batch rows {[r.shape[0] for r in rows]} do not match "
                f"packet batch rows {counts}")
        n = sum(counts)
        bucket = self.bucket_for(n)
        content = [_pad_rows(np.concatenate(
            [np.asarray(p.content[k]) for p in packets], axis=0), bucket)
            for k in keys]
        query = _pad_rows(np.concatenate(rows, axis=0), bucket)
        return content, query, counts, bucket

    @staticmethod
    def _split(arrs: Sequence[np.ndarray], counts: Sequence[int]
               ) -> List[Tuple[np.ndarray, ...]]:
        """Slice off the pad rows and split back into per-packet results."""
        out, lo = [], 0
        for c in counts:
            out.append(tuple(np.asarray(a[lo:lo + c]) for a in arrs))
            lo += c
        return out

    def cloud_context_batch(self, packets: Sequence[pk.Packet],
                            queries: Sequence[np.ndarray]
                            ) -> List[np.ndarray]:
        """Batched Context stage: K packets -> K answer-logit arrays."""
        (ctx,), query, counts, bucket = self._stack(packets, queries, ["ctx"])
        fn = self._jitted("cloud_context", None, bucket, query.shape[-1])
        logits = fn(self.params, jnp.asarray(ctx), jnp.asarray(query))
        return [r[0] for r in self._split([logits], counts)]

    def cloud_insight_batch(self, packets: Sequence[pk.Packet],
                            queries: Sequence[np.ndarray]
                            ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Batched Insight stage: K same-tier packets -> K
        (mask_logits, answer_logits) pairs."""
        tier = self._same_tier(packets)
        content, query, counts, bucket = self._stack(
            packets, queries, ["codes", "scales", "clip"])
        fn = self._jitted("cloud_insight", tier, bucket, query.shape[-1])
        mask, logits = fn(self.params, self.bottlenecks[tier],
                          *map(jnp.asarray, content), jnp.asarray(query))
        return self._split([mask, logits], counts)

    def cloud_generate_batch(self, packets: Sequence[pk.Packet],
                             queries: Sequence[np.ndarray]
                             ) -> List[Tuple[np.ndarray, ...]]:
        """Batched multi-token serving through the KV-cache decode path.
        Context packets -> (answer_logits, tokens); Insight packets ->
        (mask_logits, answer_logits, tokens). ``tokens`` is the greedy
        ``max_new_tokens``-long answer."""
        if packets[0].kind == "context":
            (ctx,), query, counts, bucket = self._stack(packets, queries,
                                                        ["ctx"])
            fn = self._jitted("cloud_context_gen", None, bucket, query.shape[-1])
            logits, tokens = fn(self.params, jnp.asarray(ctx),
                                jnp.asarray(query))
            return self._split([logits, tokens], counts)
        tier = self._same_tier(packets)
        content, query, counts, bucket = self._stack(
            packets, queries, ["codes", "scales", "clip"])
        fn = self._jitted("cloud_insight_gen", tier, bucket, query.shape[-1])
        mask, logits, tokens = fn(self.params, self.bottlenecks[tier],
                                  *map(jnp.asarray, content),
                                  jnp.asarray(query))
        return self._split([mask, logits, tokens], counts)

    # ---- cloud side (in-flight / paged continuous batching) ----
    #
    # The one-shot ``cloud_generate_batch`` serves a closed microbatch end
    # to end. The in-flight stages below split that into page-table ops:
    # the [ctx; query] prefix prefills once into fixed-size KV pages
    # (shared read-only across repeat-prefix requests), per-frame SAM
    # feats compute separately, and each decode step advances every live
    # row against the shared page pool with per-row positions, page
    # tables, and write slots (the engine's ``InflightDecoder`` owns the
    # allocator + prefix-store bookkeeping in ``core.paging``).

    def cloud_sam_feats(self, packet: pk.Packet) -> np.ndarray:
        """Per-frame Insight tail: bottleneck decode + SAM suffix ->
        mask features. Runs on every admission (frames differ even when
        the LLM prefix repeats)."""
        tier = packet.tier_name
        rows = packet.content["codes"].shape[0]
        fn = self._jitted("cloud_sam_feats", tier, rows, 0)
        return fn(self.params, self.bottlenecks[tier],
                  jnp.asarray(packet.content["codes"]),
                  jnp.asarray(packet.content["scales"]))

    def cloud_prefix(self, ctx, query) -> Tuple[np.ndarray, Dict]:
        """Prefill one request's [ctx; query] prefix into KV pages.
        Returns (first-token logits (1, V), paged KV with leaves
        (L, n_pages, page_size, ...)) — the unit the page-pool scatter
        (``pool_write``) consumes. One sequence per call: pool rows are
        per-request."""
        query = np.asarray(query).reshape(-1, np.asarray(query).shape[-1])
        rows, qlen = query.shape
        if rows != 1:
            raise ValueError(
                f"prefix prefill is per-sequence, got {rows} rows")
        fn = self._jitted("cloud_prefix", None, rows, qlen)
        return fn(self.params, jnp.asarray(ctx), jnp.asarray(query))

    def pool_write(self, pool: Dict, paged_kv: Dict, page_ids) -> Dict:
        """Scatter a prefilled prefix's pages into the shared page pool
        at ``page_ids``; returns the new pool value."""
        return self._pool_write(pool, paged_kv,
                                jnp.asarray(page_ids, jnp.int32))

    def cloud_decode_rows(self, pool: Dict, page_table, positions, tokens,
                          pos, write_slot
                          ) -> Tuple[np.ndarray, np.ndarray, Dict]:
        """One paged decode step over all slots. pool {"groups": [kv, ...]}
        with leaves (L, P, page, ...); page_table (slots, n_pages) i32
        (idle rows parked on the trash page); positions
        (slots, n_pages*page) i32 absolute slot positions (-1 empty);
        tokens (slots, 1) i32; pos / write_slot (slots,) i32 — idle rows
        write into the trash page and their outputs are discarded.
        Returns (answer_logits, seg, new pool); the same device program
        leaves the step's counters in ``step_counts``."""
        logits, seg, pool, self.step_counts = self._decode_paged(
            self.params, pool, jnp.asarray(page_table, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(tokens, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(write_slot, jnp.int32))
        return logits, seg, pool

    def cloud_verify_rows(self, pool: Dict, page_table, positions, tokens,
                          pos, write_slot, chunk_len
                          ) -> Tuple[np.ndarray, np.ndarray, Dict]:
        """One speculative verify step over all slots: tokens
        (slots, C) i32 chunks (last accepted token + drafts, pad past
        ``chunk_len``); pos / write_slot (slots,) i32 starts; chunk_len
        (slots,) i32 real chunk entries per row (pad entries write to
        the trash page and their logits are discarded). Returns
        (answer_logits (slots, C, V), seg (slots, C, d_sam), new pool)
        — ``vlm.llm_verify_step_paged`` semantics, counters in
        ``step_counts`` as for ``cloud_decode_rows``."""
        logits, seg, pool, self.step_counts = self._verify_paged(
            self.params, pool, jnp.asarray(page_table, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(tokens, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(write_slot, jnp.int32),
            jnp.asarray(chunk_len, jnp.int32))
        return logits, seg, pool

    def cloud_mask(self, feats, seg) -> np.ndarray:
        """<SEG>-conditioned mask decode from stored sam feats (the final
        in-flight stage for Insight requests)."""
        return self._mask_decode(self.params, jnp.asarray(feats),
                                 jnp.asarray(seg))

    @staticmethod
    def _same_tier(packets: Sequence[pk.Packet]) -> str:
        tiers = {p.tier_name for p in packets}
        if len(tiers) != 1:
            raise ValueError(f"mixed tiers in one microbatch: {tiers} — "
                             "bucket packets by tier before batching")
        return next(iter(tiers))
