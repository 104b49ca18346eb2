"""Operations and bytes against hand counts, the peak table, the traffic
generator, and that everything BENCHMARK.json names is found by name."""

import json
import os
import shutil

import pytest

from chipbench import harness, ops, traffic

OLMO = harness.load_json(harness.HERE, "configs", "olmo-1b.json")
MAMBA = harness.load_json(harness.HERE, "configs", "mamba2-780m.json")


def test_olmo_counts():
    # per layer: 2048 * (2048 + 2 * 2048) + 2048 * 2048 + 3 * 2048 * 8192
    #          = 67,108,864; 16 layers, plus the tied 50,304 x 2048 head
    assert ops.matmul_params(OLMO) == 16 * 67_108_864 + 50_304 * 2048
    assert ops.matmul_params(OLMO) == 1_176_764_416
    # 6N + 12 L H hd T at T = 2048: 7,060,586,496 + 805,306,368
    assert ops.train_flops_per_token(OLMO, 2048) == 7_865_892_864
    # K and V, 16 layers, 16 heads of 128, 2 bytes
    assert ops.kv_token_bytes(OLMO) == 131_072
    # a request of 3 prompt tokens and 3 served: decode inputs 1 and 2
    # attend 4 and 5 tokens
    assert ops.decode_least_bytes(OLMO, [(3, 3)]) == 131_072 * 9
    # one f32 page: 16 tokens x 16 heads x 128; a gather writes the whole
    # 16 x 64-page view and reads the live pages: a request of 20 prompt
    # tokens and 3 served feeds back inputs 1 and 2, on 2 pages each
    page = 16 * 16 * 128 * 4
    assert ops.gather_page_bytes(OLMO, 16, "float32") == page
    assert ops.paged_gather_least_bytes(
        OLMO, 16, 1024, 16, "float32", [(20, 3)], calls=64, steps=2) \
        == 64 * 16 * 64 * page + 32 * (2 + 2) * page
    # 2 prompt tokens and 2 served: 3 tokens fed, contexts 1 + 2 + 3
    assert ops.serve_flops(OLMO, [(2, 2)]) == \
        3 * 2 * 1_176_764_416 + 4 * 16 * 16 * 128 * 6
    assert ops.weight_bytes(OLMO) == 2 * 1_176_764_416


def test_mamba2_counts():
    # per layer: in_proj 1536 x (2*3072 + 256 + 48), out_proj 3072 x 1536,
    # conv 4 x (3072 + 256)
    layer = 1536 * 6448 + 3072 * 1536 + 4 * 3328
    assert ops.matmul_params(MAMBA) == 48 * layer + 50_280 * 1536
    # 2 Q N G + H (2 Q P + 4 N P) = 65,536 + 48 * 65,536 per layer
    assert ops.ssd_flops(MAMBA) == 48 * 3_211_264
    assert ops.attention_flops(MAMBA, 100) == 0.0
    assert ops.train_flops_per_token(MAMBA, 2048) == \
        6 * ops.matmul_params(MAMBA) + 3 * 48 * 3_211_264


def test_peaks_known_and_unknown():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", ["decode-heavy", "chat"])
def test_every_call_holds_the_same_sizes(name):
    t = harness.load_json(harness.HERE, "traffic", f"{name}.json")
    a = traffic.call_sizes(t, 0)
    b = traffic.call_sizes(t, 7)
    assert a != b                                # another order
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert len(a) == t["requests_per_call"]
    # the same seed and call give the same requests; another seed other
    # tokens in the same sizes
    r1 = traffic.call_requests(t, 9, 3, 100)
    r2 = traffic.call_requests(t, 9, 3, 100)
    r3 = traffic.call_requests(t, 2 ** 33 + 5, 3, 100)
    assert all((p1 == p2).all() and o1 == o2
               for (p1, o1), (p2, o2) in zip(r1, r2))
    assert [(len(p), o) for p, o in r1] == [(len(p), o) for p, o in r3]
    assert any((p1 != p3).any() for (p1, _), (p3, _) in zip(r1, r3))


def test_length_multiset_by_hand():
    assert traffic.length_multiset(
        {"values": [128, 256, 512], "probs": [0.5, 0.3, 0.2]}, 24) == \
        [128] * 12 + [256] * 7 + [512] * 5
    assert traffic.length_multiset({"log_uniform": [16, 64]}, 2) == [23, 45]


def test_benchmark_names_its_files():
    bench = harness.benchmark()
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.per_layer and cell.end_to_end
        t = cell.traffic
        if t["kind"] == "closed_calls":
            e = cell.settings["engine"]
            worst = max(traffic.length_multiset(t["prompt_len"], 1000)) + \
                max(traffic.length_multiset(t["output_len"], 1000))
            assert worst <= e["max_len"]     # no request is refused


def test_dropped_cell_is_found(tmp_path):
    """A cell added as files alone, with no edit to any code, is found."""
    root = tmp_path
    shutil.copytree(harness.HERE, root / "chipbench")
    bench = harness.benchmark()
    bench["workloads"].append({"name": "olmo1b-serve-new", "config":
                               "olmo-1b", "traffic": "chat", "chips": 1,
                               "why": "a new cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve" in m["name"]:
            m["workloads"].append("olmo1b-serve-new")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    settings = harness.load_json(harness.HERE, "cells",
                                 "olmo1b-serve-chat.json")
    (root / "chipbench" / "cells" / "olmo1b-serve-new.json").write_text(
        json.dumps(settings))
    cell = harness.load_cell("olmo1b-serve-new", str(root))
    assert cell.entry == "serve" and cell.config["name"] == "olmo-1b"
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    assert "decode_step_ms.serve" in {m["name"] for m in cell.per_layer}
    # a cell file that BENCHMARK.json does not list is not run
    (root / "chipbench" / "cells" / "olmo1b-serve-gone.json").write_text(
        json.dumps(settings))
    with pytest.raises(harness.BenchError, match="not in BENCHMARK.json"):
        harness.load_cell("olmo1b-serve-gone", str(root))
    assert os.path.isfile(root / "chipbench" / "run.py")
