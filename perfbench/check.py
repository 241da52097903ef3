"""Whether what the timed path served is correct.

After the window, a sample drawn from the seed of the requests the engine
finished goes through the float32 reference, teacher-forced on the tokens
the engine served. Numbers compared, each against its limit in
``limits/<cell>.json``:

* ``token_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best at that position, over every served
  token of the sample (prefill for the first token, paged decode through
  the cache for the rest). Greedy serving reads 0 up to near-ties.
* ``first_logits_err``: the largest relative L2 error of the first-token
  logits the prefill produced.
* ``mask_err``: the relative L2 error of the sample's Insight masks
  taken together (SAM tail, the <SEG> state after decoding, mask
  decode). Pooled over frames: one 64x64 mask's error swings threefold
  from seed to seed.

The trunk's reference is the configuration's architecture module's
(``arch/<arch>.py``); the SAM tail and mask are ``reference.py``'s. The
control is the reference in fp8 (``mode="fp8"``) in the program's
place: its gap is read at the token it puts first at each position of
the same prompts and tokens.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import arch, reference as ref

NUMBERS = ("token_gap", "first_logits_err", "mask_err")


def sample(records, per_intent: Dict[str, int], seed: int) -> List[Any]:
    """Finished requests drawn from the seed, ``per_intent[intent]`` of
    each intent. Every answer has the executor's one answer length, so
    any such sample holds the longest."""
    rng = np.random.default_rng([int(seed), 5])
    out = []
    for intent, n in sorted(per_intent.items()):
        ok = [r for r in records if r.intent == intent and r.done is not None
              and r.failure is None]
        out += [ok[i] for i in rng.permutation(len(ok))[:n]]
    return out


def _rel(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _ctx(frames, rec) -> np.ndarray:
    f = frames["context" if rec.intent == "context" else rec.tier]
    return f[rec.frame % len(f)]


def numbers(params, bottlenecks, cfg, frames, picked, control: bool = False
            ) -> Dict[str, float]:
    """The compared numbers over ``picked``; with ``control`` the fp8
    reference stands in for the program's outputs."""
    out = {"token_gap": 0.0, "first_logits_err": 0.0, "mask_err": 0.0}
    if not picked:
        return {k: float("inf") for k in out}
    ctx = np.concatenate([_ctx(frames, r)["ctx" if r.intent == "context"
                                            else "clip"] for r in picked])
    query = np.concatenate([r.query for r in picked])
    tokens = np.concatenate([r.tokens for r in picked])
    trunk = arch.resolve(cfg).trunk
    logits, seg = trunk(params, cfg, ctx, query, tokens)
    if control:
        c_logits, c_seg = trunk(params, cfg, ctx, query, tokens, mode="fp8")
        chosen = jnp.argmax(c_logits, axis=-1)
        first = np.asarray(c_logits[:, 0])
    else:
        chosen = jnp.asarray(tokens)
        first = np.concatenate([r.logits0 for r in picked])
    got = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    out["token_gap"] = float(jnp.max(jnp.max(logits, axis=-1) - got))
    out["first_logits_err"] = max(
        _rel(first[i], logits[i, 0]) for i in range(len(picked)))
    have_all, want_all = [], []
    for i, r in enumerate(picked):
        if r.intent != "insight":
            continue
        f = _ctx(frames, r)
        want = ref.mask(params, bottlenecks[r.tier], cfg, f["codes"],
                        f["scales"], seg[i:i + 1])
        if control:
            have = ref.mask(params, bottlenecks[r.tier], cfg, f["codes"],
                            f["scales"], c_seg[i:i + 1], mode="fp8")
        else:
            have = r.mask
        have_all.append(np.asarray(have, np.float32).ravel())
        want_all.append(np.asarray(want, np.float32).ravel())
    if want_all:
        out["mask_err"] = _rel(np.concatenate(have_all),
                               np.concatenate(want_all))
    jax.block_until_ready(logits)
    return out


def verdict(values: Dict[str, float], limits: Dict[str, float]
            ) -> Dict[str, Dict[str, float]]:
    """Each number compared beside its limit, in a fixed order."""
    return {k: {"value": values[k], "limit": float(limits[k])}
            for k in NUMBERS if k in limits}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return bool(checks) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
