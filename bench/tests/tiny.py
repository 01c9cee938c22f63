"""A small configuration and small mixes of the same kinds as the cells',
for driving the harness on the CPU."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def config() -> dict:
    c = json.loads((ROOT / "bench" / "configs" / "phi4-mini-3.8b.json").read_text())
    c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, vocab_size=512,
             deployment={"batch_slots": 4, "max_len": 128, "chips": 1})
    # at this size on the CPU sound runs read gaps of at most 0.002 and the
    # float8 control at least 0.04 (eight seeds of the two mixes)
    c["check"] = {"logit_gap": 0.01}
    return c


def chat() -> dict:
    m = json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())
    m.update(rate_per_s=8.0, check_tokens=24, check_tokens_per_request=8,
             prompt_len={"dist": "lognormal", "median": 24, "sigma": 1.0, "min": 8, "max": 64},
             output_len={"dist": "lognormal", "median": 8, "sigma": 0.8, "min": 4, "max": 32})
    return m


def docs() -> dict:
    m = json.loads((ROOT / "bench" / "traffic" / "docs.json").read_text())
    m.update(backlog=4, block=8, check_tokens=24, check_tokens_per_request=8,
             prompt_len={"dist": "uniform", "min": 70, "max": 120},
             output_len={"dist": "uniform", "min": 2, "max": 6})
    return m


PEAKS = {"bf16_flops": 197e12, "hbm_bw": 819e9}
