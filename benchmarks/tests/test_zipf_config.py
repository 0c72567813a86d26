"""`ysb_keys64k_zipf_mesh4` is what it says it is: the uniform twin's file
but for the key draw, and a stream whose first key range owns 88 % of the
records."""

import numpy as np

from benchmarks import harness
from benchmarks.stream import build_cycle

CONFIG, TWIN = "ysb_keys64k_zipf_mesh4", "ysb_keys64k_mesh4"


def test_the_file_differs_from_the_uniform_twin_in_the_key_draw_only():
    cfg = harness.load_json("configs", CONFIG + ".json")
    twin = harness.load_json("configs", TWIN + ".json")
    assert {k for k in set(cfg) | set(twin) if cfg.get(k) != twin.get(k)} == \
        {"name", "source", "assumed", "stream"}
    assert len(cfg["source"]) <= 200 and cfg["name"] == CONFIG
    assert set(twin["assumed"]) < set(cfg["assumed"])
    assert cfg["stream"]["draw_order"] == twin["stream"]["draw_order"]
    for col, other in zip(cfg["stream"]["columns"], twin["stream"]["columns"]):
        if col["name"] == "campaign_id":
            assert col.pop("dist") == {"kind": "zipf", "s": 1.0}
        assert col == other
    assert not any(k.startswith("parallel.mesh.") and k not in (
        "parallel.mesh.enabled", "parallel.mesh.devices")
        for k in cfg["options"])          # no skew switch


def test_a_cycle_puts_88_pct_on_the_first_key_range_and_8_6_on_key_0():
    cfg = harness.load_json("configs", CONFIG + ".json")
    traffic = harness.rehearsal_traffic(
        harness.load_json("traffic", "catchup.json"))
    cycle = build_cycle(cfg["stream"], traffic, 3000000007, wrap=4096)
    keys = cycle.column("campaign_id").astype(np.int64)
    assert cycle.events == 400_000 and keys.max() < 65_536
    owner = np.bincount(keys // 16_384, minlength=4) / cycle.events
    assert abs(100 * owner[0] - 88.1) < 1.0
    assert list(np.argsort(-owner)) == [0, 1, 2, 3]
    assert abs(100 * np.mean(keys == 0) - 8.57) < 0.3
