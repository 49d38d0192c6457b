"""A tiny Qwen2-shaped configuration and traffic mix for CPU tests of the
benchmark: the same keys as the real files, at sizes a test run holds."""
from bench import harness


def config() -> dict:
    cfg = harness.load_json(harness.BENCH / "configs" / "qwen2p5_3b.json")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=128, num_hidden_layers=2, vocab_size=512)
    return cfg


def chat_mix() -> dict:
    mix = harness.load_json(harness.BENCH / "traffic" / "chat.json")
    mix.update(
        server={"n_slots": 4, "max_len": 256, "block_size": 16,
                "n_blocks": None, "chunk_len": 32, "chunk_budget": 1},
        system_prompts={"count": 2, "tokens": 64}, sessions=60, turns=[2, 3],
        user_tokens={"median": 24, "sigma": 0.6, "min": 8, "max": 48},
        answer_tokens={"median": 12, "sigma": 0.45, "min": 4, "max": 24},
        quantile_block=16, user_stride=1, answer_stride=7,
        check={"tokens": 40, "max_requests": 4})
    mix["arrivals"] = dict(mix["arrivals"], lanes=6)
    return mix

