"""The configuration files hold the published values they cite, and
``reduced`` names exactly the keys that differ from them."""
import json

import pytest

from tiny import BENCH, ROOT

# config.json of each source, as published (the numbers and flags that
# shape the model)
PUBLISHED = {
    "qwen2.5-3b": {
        "hidden_size": 2048, "intermediate_size": 11008, "num_hidden_layers": 36,
        "num_attention_heads": 16, "num_key_value_heads": 2, "vocab_size": 151936,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
        "max_position_embeddings": 32768, "hidden_act": "silu",
    },
    "yi-6b": {
        "hidden_size": 4096, "intermediate_size": 11008, "num_hidden_layers": 32,
        "num_attention_heads": 32, "num_key_value_heads": 4, "vocab_size": 64000,
        "rope_theta": 5000000.0, "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
        "max_position_embeddings": 4096, "hidden_act": "silu",
    },
}


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_widths_are_published(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    differ = {k for k, v in PUBLISHED[name].items() if cfg[k] != v}
    assert differ == set(cfg["reduced"])
    entry = {c["name"]: c for c in _bench()["configs"]}.get(name)
    if entry is not None:
        assert entry["file"] == f"bench/configs/{name}.json"
        assert set(entry["reduced"]) == differ
    assert differ <= {"num_hidden_layers"}  # depth is the only cut
    for k in differ:
        assert cfg["published"][k] == PUBLISHED[name][k]
        assert 1 <= cfg[k] < PUBLISHED[name][k]


def test_every_config_is_used():
    b = _bench()
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
