"""A tiny AVERY pipeline and its traffic, for runs of the harness on the
CPU (the kernels interpreted)."""
import copy
import time

CONFIG = {
    "name": "tiny", "source": "test", "dtype": "bfloat16",
    "trunk": {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
              "hidden_act": "silu", "rms_norm_eps": 1e-5,
              "rope_theta": 10000.0, "attention_bias": True},
    "sam": {"depth": 3, "embed_dim": 32, "num_heads": 2, "mlp_dim": 64,
            "image_size": 64, "patch_size": 16, "layer_norm_eps": 1e-6,
            "split_layer": 1},
    "clip": {"depth": 1, "embed_dim": 32, "num_heads": 2, "mlp_dim": 64,
             "image_size": 32, "patch_size": 16, "layer_norm_eps": 1e-6},
    "bottleneck_tiers": ["High Accuracy", "Balanced", "High Throughput"],
}

CLOSED = {"loop": "closed", "query_len": 4, "answer_len": 4, "clients": 4,
          "fleet": [{"intent": "context", "share": 1.0}], "frame_pool": 4,
          "preroll_answers": 2, "sample": {"context": 4}}

OPEN = {"loop": "open", "query_len": 4, "answer_len": 4, "gap_shape": 1.8,
        "gap_floor": 0.4,
        "fleet": [{"intent": "context", "uavs": 1, "rate_hz": 4.0},
                  {"intent": "insight", "tier": "High Accuracy", "uavs": 1,
                   "rate_hz": 2.0},
                  {"intent": "insight", "tier": "Balanced", "uavs": 1,
                   "rate_hz": 2.0}],
        "frame_pool": 4, "sample": {"context": 2, "insight": 2}}


def spec(mix, limits, per_layer=(), chips=1, config=CONFIG):
    return {"cell": {"name": "tiny", "chips": chips},
            "config": copy.deepcopy(config), "traffic": copy.deepcopy(mix),
            "limits": dict(limits),
            "end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": list(per_layer)}


def run(mix, limits, seed=2**31 + 77, seconds=2.0, chips=1, config=CONFIG,
        **kw):
    from perfbench import harness
    return harness.run(spec(mix, limits, chips=chips, config=config), seed,
                       seconds, False, time.perf_counter(), on_chip=False,
                       **kw)
