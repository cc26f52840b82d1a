"""Small specs of the cells for the CPU tests: the program's smoke
configurations (whose head_dim stays at the full model's), traffic sized
for a few seconds."""

from __future__ import annotations

import copy

DEEPSEEK = {
    "name": "deepseek-7b-smoke",
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 4, "head_dim": 128, "vocab_size": 256,
               "rms_norm_eps": 1e-6, "rope_theta": 10000.0},
    "served_dtype": "bfloat16",
    "program": {"arch": "deepseek-7b-smoke", "overrides": {}},
}

STABLELM2 = {
    "name": "stablelm-2-1.6b-smoke",
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 4, "head_dim": 64, "vocab_size": 256,
               "layer_norm_eps": 1e-5, "partial_rotary_factor": 0.25,
               "rope_theta": 10000, "use_qkv_bias": True},
    "served_dtype": "bfloat16",
    "program": {"arch": "stablelm-1.6b-smoke",
                "overrides": {"qkv_bias": True}},
}

OPEN = {
    "driver": "serve", "loop": "open",
    "arrivals": {"process": "poisson", "rate_per_s": 6.0},
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
               "min": 8, "max": 64},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
               "min": 2, "max": 12},
    "greedy": True,
    "serve": {"slots": 4, "page_size": 8, "kv_pool_tokens": 512},
    "check": {"tokens": 24, "limits": {"max_gap": 0.05, "logit_err": 0.1}},
}

CLOSED = {
    "driver": "serve", "loop": "closed", "clients": 3,
    "prompt": {"dist": "loguniform", "min": 16, "max": 48},
    "output": {"dist": "uniform", "min": 4, "max": 12},
    "greedy": True,
    "serve": {"slots": 3, "page_size": 8, "kv_pool_tokens": 192},
    "check": {"tokens": 24, "limits": {"max_gap": 0.05, "logit_err": 0.1}},
}


def spec(config: dict, traffic: dict, root: str, name: str = "smoke") -> dict:
    return {"cell": {"name": name, "chips": 1},
            "config": copy.deepcopy(config),
            "traffic": copy.deepcopy(traffic),
            "end_to_end": [], "per_layer": [], "root": root}
