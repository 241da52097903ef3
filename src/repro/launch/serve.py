import os
import sys

if "--dryrun" in sys.argv:
    # pod-disaggregated lowering needs the production device count; must be
    # set before jax initialises (same contract as launch/dryrun.py).
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")

"""Serving launcher.

Local mode (default): closed-loop dual-stream serving of the trained
lisa-mini system over a simulated channel — batched operator requests,
intent gating, Algorithm-1 tier control:

  python -m repro.launch.serve --duration 120

Pod-disaggregated dry-run (DESIGN.md §4.1): lowers a split serve step on
the 2x16x16 multi-pod mesh where pod 0 ("edge") runs the SAM head +
bottleneck encoder and pod 1 ("cloud") decodes + runs the tail; the
boundary codes cross the pod axis via ppermute inside shard_map. Prints
the inter-pod collective bytes with and without the bottleneck:

  python -m repro.launch.serve --dryrun
"""
import argparse
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def serve_local(duration_s: float, seed: int, max_batch: int = 8,
                smoke: bool = False, batching: str = "microbatch") -> None:
    """Closed-loop local serving through the ``AveryEngine`` front door.

    ``smoke=True`` skips the offline training phase (random-init weights,
    paper LUT) so CI can exercise the full engine path — intent gate,
    policy, transport, batched cloud serving — in seconds. ``batching``
    picks the cloud discipline: closed tier-bucketed microbatches or the
    token-level in-flight batch (``"inflight"``)."""
    from repro.configs.lisa_mini import CONFIG as pcfg
    from repro.core import DualStreamExecutor, Intent
    from repro.core.vlm import iou_metrics
    from repro.data import floodseg, requests
    from repro.engine import AdaptivePolicy, AveryEngine, ChannelTransport
    from repro.network import paper_trace

    from repro.core import profile as prof
    if smoke:
        print("[serve] smoke mode: random-init weights, paper LUT")
        params, bns_by_name, lut = prof.random_init_system(pcfg, seed=seed)
    else:
        print("[serve] training lisa-mini system (offline phase, small "
              "budget)")
        params, params_ft, bns = prof.train_full_system(
            pcfg, steps=120, bn_steps=80, ft_steps=60, log=lambda s: None)
        lut = prof.build_lut(pcfg, params, params_ft, bns, eval_batches=2)
        bns_by_name = {lut.tiers[i].name: bns[r]
                       for i, r in enumerate(sorted(bns, reverse=True))}
    execu = DualStreamExecutor(pcfg=pcfg, params=params,
                               bottlenecks=bns_by_name, lut=lut)
    trace = paper_trace(seed=seed, duration_s=int(duration_s))
    engine = AveryEngine(
        lut=lut, executor=execu,
        transport=ChannelTransport.from_trace(trace),
        policy=AdaptivePolicy(), max_batch=max_batch, batching=batching)
    session = engine.session("operator-0")
    rng = np.random.RandomState(seed)

    # edge loop: each operator request goes through the engine — intent
    # gate, tier policy, edge encode, channel, cloud scheduler; full
    # microbatches are served as soon as they form (continuous batching),
    # stragglers at the end of the stream
    truth = {}
    futures = []
    for req in requests.mission_requests(seed, duration_s):
        batch = floodseg.make_batch(rng, 1, req.kind, augment=False,
                                    cls=req.cls)
        fut = session.submit(prompt=req.prompt,
                             images=jnp.asarray(batch["images"]),
                             query=batch["query"], time_s=req.time_s)
        truth[fut.request.request_id] = batch
        futures.append(fut)
    engine.drain()

    ious, ctx_correct = [], []
    for fut in futures:
        res = fut.result()
        if not res.feasible:           # no tier sustained F_I: never served
            continue
        batch = truth[res.request_id]
        if res.intent is Intent.CONTEXT:
            ctx_correct.append(
                float(np.argmax(res.answer_logits[0]) == batch["answer"][0]))
        else:
            m = iou_metrics(jnp.asarray(res.mask_logits),
                            jnp.asarray(batch["mask"]))
            ious.append(float(m["avg_iou"]))
    stats = engine.stats
    detail = (f"{stats['inflight_steps']:.0f} in-flight decode steps (mean "
              f"{stats['mean_live_slots']:.1f} live slots"
              if batching == "inflight" else
              f"{stats['n_microbatches']:.0f} microbatches (mean batch "
              f"{stats['mean_batch_size']:.1f}")
    print(f"[serve] served {len(ctx_correct)} context + {len(ious)} insight "
          f"requests over {duration_s:.0f}s in {detail}, "
          f"{stats['compiled_stages']:.0f} compiled cloud stages)")
    if ctx_correct:
        print(f"[serve] context answer accuracy: {np.mean(ctx_correct):.3f}")
    if ious:
        print(f"[serve] insight Average IoU:     {np.mean(ious):.3f}")
    lat = [r.latency_s for r in engine.transport.records]
    print(f"[serve] mean packet latency: {np.mean(lat):.3f}s "
          f"(p95 {np.percentile(lat, 95):.3f}s)")


# ---------------------------------------------------------------------------
# pod-disaggregated dry-run
# ---------------------------------------------------------------------------


def serve_dryrun() -> None:
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.configs.lisa7b import CONFIG as pcfg
    from repro.core import bottleneck as bn
    from repro.core import vlm
    from repro.launch.dryrun import collective_bytes
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=True)
    d = pcfg.sam.d_model
    rank = bn.rank_for_ratio(d, 0.25, 2)
    B = 32                                      # images per serve step (2/chip-row)

    aparams = jax.eval_shape(
        lambda: vlm.init_lisa(pcfg, jax.random.PRNGKey(0)))
    abn = jax.eval_shape(
        lambda: bn.init_bottleneck(jax.random.PRNGKey(0),
                                   bn.BottleneckSpec(d, rank, 2)))
    images = jax.ShapeDtypeStruct((B, pcfg.image_size, pcfg.image_size, 3),
                                  jnp.bfloat16)
    query = jax.ShapeDtypeStruct((B, 8), jnp.int32)

    def split_serve(params, bnp, images, query):
        """Edge pod (pod 0) computes the head + compressed codes; ppermute
        moves ONLY the codes across the pod axis; cloud pod (pod 1) decodes
        and finishes. Data-parallel over ("data",) within each pod; model
        dim unsharded here (the encoder fits one chip's slice at B/16)."""
        def inner(imgs, q):
            a = vlm.sam_head(params, pcfg, imgs)                 # edge
            codes, scales = bn.encode(bnp, a)
            codes = jax.lax.ppermute(codes, "pod", [(0, 1)])     # the link
            scales = jax.lax.ppermute(scales, "pod", [(0, 1)])
            a_hat = bn.decode(bnp, codes, scales,
                              out_dtype=pcfg.sam.adtype)         # cloud
            feats = vlm.sam_tail(params, pcfg, a_hat)
            ctx = vlm.clip_encode(params, pcfg, imgs)
            ans, seg = vlm.llm_reason(params, pcfg, ctx, q)
            return vlm.mask_decode(params, pcfg, feats, seg)
        return shard_map(
            inner, mesh=mesh,
            in_specs=(P(("data",)), P(("data",))),
            out_specs=P(("data",)),
            check_vma=False)(images, query)

    with mesh:
        lowered = jax.jit(split_serve).lower(aparams, abn, images, query)
        compiled = lowered.compile()
    coll = collective_bytes(compiled.as_text())
    raw_bytes = B * pcfg.sam_tokens * d * 2      # uncompressed boundary
    comp_bytes = B * pcfg.sam_tokens * (rank + 4)
    print("[serve-dryrun] pod-disaggregated split serve step compiled on "
          f"{mesh.shape}")
    print(f"[serve-dryrun] collective-permute bytes (per device): "
          f"{coll['collective-permute']:.3g}")
    print(f"[serve-dryrun] boundary payload: uncompressed={raw_bytes/1e6:.2f}"
          f"MB vs bottlenecked={comp_bytes/1e6:.2f}MB "
          f"({raw_bytes/comp_bytes:.1f}x reduction on the pod link)")
    print(compiled.memory_analysis())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="cloud scheduler microbatch / in-flight slot cap")
    ap.add_argument("--smoke", action="store_true",
                    help="skip offline training: random-init weights + "
                         "paper LUT (fast engine smoke for CI)")
    ap.add_argument("--batching", choices=("microbatch", "inflight"),
                    default="microbatch",
                    help="cloud serving discipline: closed microbatches or "
                         "token-level in-flight batching")
    args = ap.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    if args.dryrun:
        serve_dryrun()
    else:
        serve_local(args.duration, args.seed, args.max_batch,
                    smoke=args.smoke, batching=args.batching)


if __name__ == "__main__":
    main()
