"""bench/cost.py against hand counts for both configurations."""
import json

import pytest

from cost import Cost, least_seconds
from harness import BENCH_DIR


def conf(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


EDGE = conf("paper-edge.p8-paged")
GRANITE = conf("granite-3-8b.l8.p8-paged")


def test_paper_edge_params():
    c = Cost.from_config(EDGE)
    # q 768x768, k and v 768x256 each, o 768x768
    assert c.attn_params == 768 * 768 * 2 + 2 * 768 * 256 == 1_572_864
    assert c.mlp_params == 768 * 4096 + 2048 * 768 == 4_718_592
    assert c.head_params == 768 * 32000
    # 12 layers, embedding and an untied head: about 125M
    assert c.params == 12 * 6_291_456 + 2 * 24_576_000 == 124_649_472


def test_granite_stage_params():
    c = Cost.from_config(GRANITE)
    assert c.attn_params == 4096 * 4096 * 2 + 2 * 4096 * 1024 == 41_943_040
    assert c.mlp_params == 3 * 4096 * 12800 == 157_286_400
    # 8 layers and the tied embedding: about 1.8B
    assert c.params == 8 * 199_229_440 + 49155 * 4096 == 1_795_174_400


def test_flops_per_token():
    c = Cost.from_config(EDGE)
    mm = 12 * 6_291_456 + 768 * 32000
    assert c.token_flops(1) == 2 * mm + 12 * 4 * 12 * 64
    assert c.token_flops(1000) - c.token_flops(999) == 12 * 4 * 12 * 64
    # a prompt: every position through the layers, the head at the last
    n = 300
    want = 2 * (mm - 768 * 32000) * n + 2 * 768 * 32000 \
        + 12 * 4 * 12 * 64 * n * (n + 1) / 2
    assert c.prompt_flops(n) == pytest.approx(want)


def test_declared_bytes():
    c = Cost.from_config(EDGE)
    # posit8 matrices at 1 B, the posit16 head at 2 B
    assert c.weight_bytes == 12 * 6_291_456 + 2 * 768 * 32000
    # posit8 K and V codes (64 B a head) and one f32 scale a head each
    assert c.kv_row_bytes == 2 * 4 * (64 + 4)
    g = Cost.from_config(GRANITE)
    assert g.weight_bytes == 8 * 199_229_440 + 2 * 4096 * 49155
    assert g.kv_row_bytes == 2 * 8 * (128 + 4)


def test_live_row_bytes_and_least_time():
    c = Cost.from_config(EDGE)
    # 16 slots, 2500 live rows each: only those rows count
    rows = 16 * 2500
    f, b = c.attention_cost(rows)
    assert f == 4 * 12 * 64 * rows
    assert b == rows * 2 * 4 * 68
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert least_seconds(f, b, peaks) == pytest.approx(b / 819e9)
    gf, gb = c.generate_cost(16, rows)
    assert gb == c.weight_bytes + 16 * 768 * 2 + 12 * 544 * (rows + 16)
    assert gf == 2 * c.matmul_params * 16 + 12 * 4 * 12 * 64 * rows


def test_posit4_kv_halves_codes():
    c = Cost.from_config(EDGE, kv_format="posit4")
    assert c.kv_row_bytes == 2 * 4 * (32 + 4)
