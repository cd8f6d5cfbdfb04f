"""A tiny configuration and mixes of the benchmark's cells for CPU tests."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=512)


def conf(name="qwen3-1.7b"):
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    c.update(name="tiny", **TINY)
    return c


def serve_mix():
    m = json.loads((ROOT / "bench" / "traffic"
                    / "decode-heavy.robust-m8.json").read_text())
    m.update(clients=3, slots=3, max_len=64, decode_block=4,
             pool_requests=32, ramp_blocks=2)
    m["prompt"].update(mean=8, max=32, buckets=[8, 16, 32])
    m["output"].update(mean=6, max=24)
    m["check"]["requests"] = 2
    return m


def train_mix():
    m = json.loads((ROOT / "bench" / "traffic"
                    / "rrs-vrmom.byz1.json").read_text())
    m.update(seq=32)
    return m
