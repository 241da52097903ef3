"""Sharded paged serving: tensor-parallel prefill / decode / verify of
the serving stack on a device mesh.

Every prior serving layer — batching (PR 1), the engine front door
(PR 2), the paged shared-prefix KV cache (PR 3), speculative decoding
(PR 4) — ran single-device while ``sharding/specs.py`` and
``launch/mesh.py`` only served the *training* state. This module closes
that gap: a ``ShardedServingContext`` wraps a ``DualStreamExecutor`` and
re-exposes the paged in-flight stages (``cloud_prefix`` /
``pool_write`` / ``cloud_decode_rows`` / ``cloud_verify_rows``) plus the
Context-stream draft stages as jitted entry points with **explicit
``in_shardings``/``out_shardings``** over a ``Mesh``, so
``InflightDecoder``, ``DualStreamExecutor`` and the engine work
unchanged on top.

Layout (the megatron discipline the training specs already use):

  * params — replicated-or-model-sharded by the ``specs.param_specs``
    key-path rules (attention heads / d_ff column-parallel over
    "model", output projections row-parallel, norms replicated);
  * paged KV pool — kv-heads axis over "model", the **page axis
    replicated** (every shard holds its head slice of every page), so a
    page-table gather is local on each shard and page-table updates
    never round-trip through the host;
  * page tables, positions, token ids, logits, per-row scalars —
    replicated (``specs.serving_specs``).

The decode/verify **Pallas kernels** have a per-shard head-count path:
under ``shard_map`` each shard would run the kernel on
``n_kv_heads / mesh.shape["model"]`` heads (the ``group`` and
``heads_per_batch`` grid math is already per-shard-shape-driven, so the
kernel body needs no change — only smaller K). GSPMD does not partition
a ``pallas_call``, so until the kernels run under ``shard_map`` the
sharded context pins ``use_flash_decode=False`` and serves the jnp
reference attention, which XLA partitions automatically (one all-reduce
after the row-parallel output projection per layer).

Weights built already sharded by the same key-path rules (a jitted init
with those ``out_shardings``) are used as they are: no chip ever holds
the whole model.

Exactness: sharding only changes *where* each head's arithmetic runs
and the reduction order of the output-projection sum, not the
computation — sharded decode/verify is token-exact with the unsharded
``llm_generate`` path (pinned in ``tests/test_sharding.py`` and the
``--sharded`` benchmark).

Run the end-to-end selftest on a forced host-platform mesh::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.sharding.serving --model=2
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import vlm
from repro.core.spans import named_stage
from repro.sharding import specs as sh


class ShardedServingContext:
    """Executor facade that runs the paged serving stack under a mesh.

    Owns a model-sharded copy of the weights (``device_put`` once at
    construction) and a lazy cache of jitted stages whose in/out
    shardings come from the ``specs`` key-path rules — the first call
    of each stage shapes its sharding trees via ``jax.eval_shape``,
    after which the stage behaves exactly like the executor method it
    replaces. Edge stages, SAM tail, mask decode, and the closed
    microbatch paths delegate to the wrapped executor (they are
    per-frame work, not the decode hot loop; on a real deployment the
    vision tail would shard the same way — see docs/serving.md).
    """

    def __init__(self, executor: Any, mesh: Mesh):
        self.inner = executor
        self.mesh = mesh
        self.pcfg = executor.pcfg
        self.page_size = executor.page_size
        self.max_new_tokens = executor.max_new_tokens
        self.lut = executor.lut
        # GSPMD does not partition a pallas_call: serve the jnp attention
        # ref, which XLA partitions over the head-sharded operands
        self.flash_decode = False
        self._gen_pcfg = dataclasses.replace(
            self.pcfg, llm=self.pcfg.llm.replace(use_flash_decode=False))
        self.model_shards = (mesh.shape["model"]
                             if "model" in mesh.axis_names else 1)
        self._rep = NamedSharding(mesh, P())
        pspecs = sh.param_specs(self.pcfg.llm, executor.params, mesh)
        self.param_shardings = sh.to_shardings(mesh, pspecs)
        self.params = jax.device_put(executor.params, self.param_shardings)
        self._stages: Dict[Any, Callable] = {}
        self.step_counts: Dict = {}     # as DualStreamExecutor's

    def __getattr__(self, name: str) -> Any:
        if name == "inner":
            raise AttributeError(name)
        return getattr(self.inner, name)

    # ---- sharding trees ----

    def _kv_sh(self, tree: Any) -> Any:
        """NamedShardings for any serving pytree (pool / paged prefix /
        draft ring / page tables / logits) via the key-path rules."""
        return sh.to_shardings(self.mesh, sh.serving_specs(tree, self.mesh))

    def place_pool(self, kv: Any) -> Any:
        """Place (or re-place after growth) the page pool's device
        buffers with the serving shardings — ``PagePool(placement=...)``
        calls this from ``ensure`` so the pool stays mesh-resident."""
        return jax.device_put(kv, self._kv_sh(kv))

    # ---- lazy jitted stages with explicit shardings ----

    def _lazy(self, key: Any, fn: Callable, in_sh: Callable,
              out_sh: Callable) -> Callable:
        """One jitted stage per key; in/out shardings are computed from
        the first call's arguments/abstract outputs (the sharding trees
        are shape-polymorphic, so later shapes re-trace under the same
        jit without re-deriving them). The stage is jitted under its
        key's name (the first entry of a tuple key)."""
        stage = self._stages.get(key)
        if stage is None:
            box: Dict[str, Callable] = {}
            named = named_stage(key[0] if isinstance(key, tuple) else key,
                                fn)

            def call(*args):
                jitted = box.get("jitted")
                if jitted is None:
                    outs = jax.eval_shape(fn, *args)
                    jitted = box["jitted"] = jax.jit(
                        named, in_shardings=in_sh(args),
                        out_shardings=out_sh(outs))
                return jitted(*args)

            stage = self._stages[key] = call
        return stage

    @property
    def num_compiled_stages(self) -> int:
        return self.inner.num_compiled_stages + len(self._stages)

    # ---- the paged in-flight stages (InflightDecoder's contract) ----

    def cloud_prefix(self, ctx, query) -> Tuple[Any, Dict]:
        import numpy as np
        query = np.asarray(query).reshape(-1, np.asarray(query).shape[-1])
        if query.shape[0] != 1:
            raise ValueError(
                f"prefix prefill is per-sequence, got {query.shape[0]} rows")
        pcfg, page = self.pcfg, self.page_size

        def fn(p, c, q):
            logits0, _, paged = vlm.llm_prefill_paged(p, pcfg, c, q, page)
            return logits0, jax.tree.map(lambda a: a[:, 0], paged)

        stage = self._lazy(
            "cloud_prefix", fn,
            lambda args: (self.param_shardings, self._rep, self._rep),
            lambda outs: (self._rep, self._kv_sh(outs[1])))
        return stage(self.params, jnp.asarray(ctx), jnp.asarray(query))

    def pool_write(self, pool: Dict, paged_kv: Dict, page_ids) -> Dict:
        def fn(dst, src, ids):
            return jax.tree.map(lambda d, s: d.at[:, ids].set(s), dst, src)

        stage = self._lazy(
            "pool_write", fn,
            lambda args: (self._kv_sh(args[0]), self._kv_sh(args[1]),
                          self._rep),
            lambda outs: self._kv_sh(outs))
        return stage(pool, paged_kv, jnp.asarray(page_ids, jnp.int32))

    def cloud_decode_rows(self, pool: Dict, page_table, positions, tokens,
                          pos, write_slot) -> Tuple[Any, Any, Dict]:
        pcfg = self._gen_pcfg

        def fn(p, pl, pt, posarr, tok, ps, ws):
            return vlm.llm_decode_step_paged(p, pcfg, pl, pt, posarr, tok,
                                             ps, ws)

        stage = self._lazy(
            "cloud_decode_rows", fn,
            lambda args: (self.param_shardings, self._kv_sh(args[1]))
            + (self._rep,) * 5,
            lambda outs: (self._rep, self._rep, self._kv_sh(outs[2]),
                          self._rep))
        logits, seg, pool, self.step_counts = stage(
            self.params, pool, jnp.asarray(page_table, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(tokens, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(write_slot, jnp.int32))
        return logits, seg, pool

    def cloud_verify_rows(self, pool: Dict, page_table, positions, tokens,
                          pos, write_slot, chunk_len
                          ) -> Tuple[Any, Any, Dict]:
        pcfg = self._gen_pcfg

        def fn(p, pl, pt, posarr, tok, ps, ws, cl):
            return vlm.llm_verify_step_paged(p, pcfg, pl, pt, posarr, tok,
                                             ps, ws, cl)

        stage = self._lazy(
            "cloud_verify_rows", fn,
            lambda args: (self.param_shardings, self._kv_sh(args[1]))
            + (self._rep,) * 6,
            lambda outs: (self._rep, self._rep, self._kv_sh(outs[2]),
                          self._rep))
        logits, seg, pool, self.step_counts = stage(
            self.params, pool, jnp.asarray(page_table, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(tokens, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(write_slot, jnp.int32),
            jnp.asarray(chunk_len, jnp.int32))
        return logits, seg, pool

    # ---- the Context draft stages (DraftModel's fns_factory hook) ----

    def draft_fns(self, pcfg: Any, width: int, params: dict
                  ) -> Tuple[Callable, Callable, Callable]:
        """Sharded draft-model stages: same contract as
        ``speculative._draft_fns`` (prefill, step, insert) with the
        draft params model-sharded and the contiguous ring cache's
        kv-heads over "model". The draft may run a different geometry
        (``lisa_nano``) than the target — its specs are derived from
        its own param tree."""
        from repro.engine.speculative import DraftModel
        rep = self._rep
        psh = sh.to_shardings(self.mesh,
                              sh.param_specs(pcfg.llm, params, self.mesh))
        prefill = self._lazy(
            ("draft_prefill", pcfg, width),
            lambda p, c, q: vlm.llm_prefill(p, pcfg, c, q, width=width),
            lambda args: (psh, rep, rep),
            lambda outs: (rep, rep, self._kv_sh(outs[2])))
        step = self._lazy(
            ("draft_step", pcfg, width),
            lambda p, ca, t, pos: vlm.llm_decode_step(p, pcfg, ca, t, pos),
            lambda args: (psh, self._kv_sh(args[1]), rep, rep),
            lambda outs: (rep, rep, self._kv_sh(outs[2])))
        insert = self._lazy(
            ("draft_insert", pcfg, width),
            DraftModel._insert_row,
            lambda args: (self._kv_sh(args[0]), self._kv_sh(args[1]), rep),
            lambda outs: self._kv_sh(outs))
        return prefill, step, insert


# ---------------------------------------------------------------------------
# selftest: sharded decode + verify token-exact vs unsharded llm_generate
# ---------------------------------------------------------------------------


def _selftest(model: int = 2, n_requests: int = 3,
              answer_tokens: int = 3, executor: Any = None) -> None:
    """End-to-end exactness pin on the local host mesh: sharded paged
    decode and sharded speculative verify vs the unsharded one-shot
    generate path. The in-process test hands in its fixture
    ``executor``; the ``__main__``/subprocess path builds a random-init
    one. Force a multi-device host platform *before* any jax import
    (the test and CI wrappers set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in the
    environment); with 2 forced devices and ``model=2`` this is the
    1x2 mesh, with 8 the CI smoke's 2x4."""
    import numpy as np

    from repro.core.intent import Intent
    from repro.core.paging import PagePool
    from repro.data import floodseg
    from repro.engine.inflight import InflightDecoder
    from repro.engine.speculative import SpeculativeConfig
    from repro.launch.mesh import make_local_mesh

    if executor is None:
        from repro.core import DualStreamExecutor, paper_lut, profile as prof
        from repro.configs.lisa_mini import CONFIG as PCFG
        lut = paper_lut()
        params, bns, _ = prof.random_init_system(PCFG, lut=lut)
        executor = DualStreamExecutor(
            pcfg=PCFG, params=params, bottlenecks=bns, lut=lut,
            max_new_tokens=answer_tokens, flash_decode=False, page_size=4)
    lut = executor.lut
    mesh = make_local_mesh(model=model)
    ctx = ShardedServingContext(executor, mesh)

    rng = np.random.RandomState(3)
    reqs = []
    for i in range(n_requests):
        kind = "any" if i % 3 == 2 else "segment"
        b = floodseg.make_batch(rng, 1, kind, augment=False)
        img = jnp.asarray(b["images"])
        if kind == "any":
            pkt, _ = executor.edge_context(img, i, 0.0)
            reqs.append((pkt, b["query"], Intent.CONTEXT))
        else:
            pkt = executor.edge_insight(img, lut.tiers[i % 2], i, 0.0)
            reqs.append((pkt, b["query"], Intent.INSIGHT))

    for spec in (None, SpeculativeConfig(draft_tokens=2)):
        pool = PagePool(page_size=ctx.page_size, placement=ctx.place_pool,
                        shards=ctx.model_shards)
        dec = InflightDecoder(ctx, slots=2, pool=pool, spec=spec)
        done: Dict[int, Dict] = {}
        for i, (pkt, q, it) in enumerate(reqs):
            dec.submit(i, it, pkt, q,
                       lambda out: done.setdefault(out["seq_id"], out))
        dec.drain()
        for i, (pkt, q, it) in enumerate(reqs):
            ref = executor.cloud_generate_batch([pkt], [q])[0]
            mode = "verify" if spec is not None else "decode"
            assert np.array_equal(done[i]["tokens"], ref[-1]), (mode, i)
            np.testing.assert_allclose(
                done[i]["answer_logits"],
                ref[-2] if it is Intent.CONTEXT else ref[1], atol=1e-3)
            if it is Intent.INSIGHT:
                np.testing.assert_allclose(done[i]["mask_logits"], ref[0],
                                           atol=1e-3)
        stats = pool.stats()
        assert stats["kv_pool_bytes"] > 0
        assert stats["kv_pool_bytes_per_shard"] \
            == stats["kv_pool_bytes"] // ctx.model_shards
    print(f"sharded serving selftest: decode + speculative verify "
          f"token-exact on mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"(model_shards={ctx.model_shards}, devices={mesh.size})")


if __name__ == "__main__":
    import sys
    model_arg = 2
    for a in sys.argv[1:]:
        if a.startswith("--model="):
            model_arg = int(a.split("=", 1)[1])
    _selftest(model=model_arg)
