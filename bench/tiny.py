"""A tiny dense GQA configuration and traffic mix, for the benchmark's own
CPU tests: every feature of the served configurations (GQA, QKV bias, an
untied head, the chunk lane, decode blocks) at sizes a test run holds."""

CONFIG = {
    "name": "tiny", "family": "dense", "architecture": "dense_gqa",
    "reference": "dense_gqa",
    "torch_dtype": "bfloat16", "num_hidden_layers": 4, "hidden_size": 128,
    "intermediate_size": 256, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 2048,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "qkv_bias": True, "tie_word_embeddings": False, "max_new_tokens": 32,
}

MIX = {
    "loop": "closed", "concurrency": 4, "prefill_chunk": 32,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.6,
                      "min": 8, "max": 96},
    "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                      "min": 8, "max": 32},
    "warmup_requests": 4, "check_requests": 4,
}

# set as a cell's limit is set, from CPU readings of this size
# (bench/control.py's readings): the program's widest gap is at most 0.068
# over seeds 1-12 and 2**31 + 11; the fp8 control's at least 0.586 over
# seeds 2, 5, 7 and 2**31 + 11
LIMIT = 0.25
# a cell file's contents: the limit, and a rate that makes a 0.1 s window
# two requests per client
LIMITS = {"widest_logit_gap": LIMIT, "requests_per_s": 80.0}
